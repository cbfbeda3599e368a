"""ShortConvMoETransformer: gated short-convolution layers with a
per-slot TAIL between grouped-query attention layers with paged rows —
the block of LFM2-24B-A2B (`lfm2_moe`), whose published widths the
benchmark serves (benchmark/configs/lfm2-24b-a2b.json): three
convolution layers to one attention layer, pre-norm, a dense MLP in the
leading layers and an expert layer WITHOUT a shared expert after, the
head tied to the embedding.

    a = x + Mix(norm_in(x));  y = a + FFN(norm_pre_mlp(a))

`layer_kinds` names each layer's `Mix`: "conv" (nn/short_conv.py: two
rows of hidden numbers a slot, whatever the context) or "attn"
(nn/gqa_attention.py: `n_heads` query heads on `n_kv_heads` K/V heads
with a norm a head and rotary positions, one K row and one V row a
token in the page pool, stored in `param_dtype`). Everything else —
the feed-forward halves, the held experts, embedding, seeded weights,
counters — is LatentMoETransformer's, with what this block differs in
passed as data (`n_shared=0`, `route_eps`, `tie_embeddings`). To
engine/decode_program.py it describes the pool of its "attn" layers
alone and, for the "conv" layers, the second kind of state of the
contract there (`mix_kind`, `state_shape`, `state_step`,
`state_chunk`): the second model to do so, after
zoo/hybrid_delta.py, and with a state layer in the leading dense
position. Its decode step reads the pool itself (`decode_from_pool`:
each slot's live pages, no gathered window).
"""

from __future__ import annotations

from typing import Sequence

from deeplearning4j_tpu.zoo.latent_moe import LatentMoETransformer


class ShortConvMoETransformer(LatentMoETransformer):
    def __init__(self, layer_kinds: Sequence[str] = ("conv", "attn", "conv",
                                                     "conv", "conv"),
                 n_kv_heads: int = 2, head_dim: int = 16,
                 conv_taps: int = 3, **kw):
        kw.setdefault("n_shared", 0)
        kw.setdefault("sandwich_norm", False)
        kw.setdefault("router_bias", True)
        kw.setdefault("route_eps", 1e-6)
        kw.setdefault("tie_embeddings", True)
        kw.setdefault("rope_theta", 1e6)
        kinds = tuple(str(k) for k in layer_kinds)
        kw.setdefault("n_dense_layers", 1)
        kw["n_moe_layers"] = len(kinds) - int(kw["n_dense_layers"])
        super().__init__(**kw)
        if not kinds or set(kinds) - {"conv", "attn"} \
                or kw["n_moe_layers"] < 0:
            raise ValueError(f"layer_kinds {kinds}: one of 'conv', 'attn' "
                             f"a layer, n_dense_layers of them at the least")
        if self.n_heads % int(n_kv_heads) or int(head_dim) % 2 \
                or self.rope_theta is None:
            raise ValueError(
                f"{self.n_heads} query heads on {n_kv_heads} K/V heads of "
                f"{head_dim}: whole groups, rotary pairs and a rotary base")
        self.layer_kinds = kinds
        self.n_kv_heads, self.head_dim = int(n_kv_heads), int(head_dim)
        self.conv_taps = int(conv_taps)

    def _mix_shapes(self, layer: int) -> dict:
        h, d = self.hidden, self.head_dim
        if self.layer_kinds[layer] == "conv":
            return {"norm_in": (h,), "w_in": (h, 3 * h),
                    "conv_w": (self.conv_taps, h), "w_out": (h, h)}
        return {"norm_in": (h,), "wq": (h, self.n_heads * d),
                "wk": (h, self.n_kv_heads * d),
                "wv": (h, self.n_kv_heads * d), "q_norm": (d,),
                "k_norm": (d,), "wo": (self.n_heads * d, h)}

    def init(self) -> "ShortConvMoETransformer":
        """LatentMoETransformer's seeded weights, the selection bias
        moved from a gain's range to a small one of its own."""
        from deeplearning4j_tpu.zoo.hybrid_delta import settle

        super().init()
        self.params["layers"] = tuple(
            settle(lp) for lp in self.params["layers"])
        return self

    # ----------------------------------- what DecodeProgram builds from
    kv_page_axis = 2

    @property
    def n_page_layers(self) -> int:
        """The attention layers alone cache rows in the page pool."""
        return self.layer_kinds.count("attn")

    @property
    def mix_kind(self):
        return tuple("state" if k == "conv" else "pages"
                     for k in self.layer_kinds)

    def kv_shape(self, n_pages: int, page_size: int):
        """GPT-2's pool of token rows (zoo/decoder.py), a row the K/V
        heads' n_kv_heads * head_dim numbers, in `param_dtype`."""
        return (self.n_page_layers, 2, n_pages, page_size,
                self.n_kv_heads * self.head_dim)

    state_dtype = "float32"

    def state_shape(self, max_slots: int):
        from deeplearning4j_tpu.nn.short_conv import state_shape

        return state_shape(self.layer_kinds.count("conv"), max_slots,
                           self.conv_taps, self.hidden)

    def project(self, lp, x, positions):
        import jax

        from deeplearning4j_tpu.nn.gqa_attention import project

        with jax.named_scope("qkv"):
            return project(lp, x, positions, self.n_heads, self.n_kv_heads,
                           self.rope_theta, self.eps)

    def write_cells(self, pool, li, cell, page, offset):
        """pool[li, io, page, offset] = the K row or the V row, whole
        (`page` and `offset` per slot in the decode step; from a chunk
        a page id a page with `offset` the whole page, or one page and
        its offsets: the advanced indices broadcast)."""
        k, v = cell
        pool = pool.at[li, 0, page, offset].set(k.astype(pool.dtype))
        return pool.at[li, 1, page, offset].set(v.astype(pool.dtype))

    def read_window(self, pool, li, page_ids):
        """[.., cells, n_kv_heads * head_dim] each of K and V: the
        pages' rows in ring-cell order, as stored; ONE gather over the
        whole pool a plane (zoo/decoder.py says why)."""
        import jax.numpy as jnp

        def rows(io):
            pages = pool[li, io, page_ids]       # [.., P, page_size, C]
            return jnp.reshape(pages, pages.shape[:-3] + (-1,)
                               + pages.shape[-1:])

        return rows(0), rows(1)

    def decode_from_pool(self, lp, x, q, pool, li, page_ids, live,
                         active):
        """The decode step's attention read straight from the pool:
        each active row's live pages alone."""
        import jax

        from deeplearning4j_tpu.nn.gqa_attention import gqa_pool_attention

        with jax.named_scope("attn"):
            att = gqa_pool_attention(q, pool, li, page_ids, live, active,
                                     self.n_kv_heads)
        return self._finish(lp, x, att, active)

    def chunk_finish(self, lp, x, q, cell, window, start):
        import jax

        from deeplearning4j_tpu.nn.gqa_attention import gqa_chunk_attention

        k, v = (a.astype(window[0].dtype) for a in cell)
        with jax.named_scope("attn"):
            att = gqa_chunk_attention(q, k, v, *window, start,
                                      self.n_kv_heads)
        return self._finish(lp, x, att, None)[0]

    def state_step(self, lp, x, state, si, active, positions):
        del positions               # a recurrence has no position
        from deeplearning4j_tpu.nn.short_conv import decode_mix

        out, state = decode_mix(lp, x, state, si, active, self.eps)
        x, counts = self._ffn(lp, x + out, active)
        return x, state, counts

    def state_chunk(self, lp, x, entry, n_state, positions):
        del positions
        from deeplearning4j_tpu.nn.short_conv import chunk_mix

        out, entry = chunk_mix(lp, x, entry, n_state, self.eps)
        return self._ffn(lp, x + out, None)[0], entry
