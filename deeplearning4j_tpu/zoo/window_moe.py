"""WindowMoETransformer: sliding-window attention layers with a per-slot
RING between full-attention layers with paged rows — the block of
Laguna-S-2.1 (`laguna`), whose published widths the benchmark serves
(benchmark/configs/laguna-s-2.1.json): a full layer, then three window
layers, over and over, grouped-query attention with a sigmoid gate a
head on every layer's output, pre-norm, a dense MLP in the leading
layers and an expert layer with a shared expert after, softmax routing,
an untied head.

    a = x + Attn(norm_in(x));  y = a + FFN(norm_pre_mlp(a))

`layer_kinds` names each layer's `Attn`: "full" (nn/gqa_attention.py
over the page pool, one K row and one V row a token in `param_dtype`;
YaRN's partial rotation, `full_rope`) or "window" (nn/window_attention.py
over a ring of `window` cells a slot; theta's rotation over the whole
head, `window_theta`). `heads` gives each layer's query heads (the
published block has 48 on the full layers and 72 on the window ones,
both on `n_kv_heads` K/V heads of `head_dim`); the gate `attn_gate`
[h, heads] is a leaf of every layer's. Everything else — the
feed-forward halves, the held experts, embedding, seeded weights,
counters — is LatentMoETransformer's, with what this block differs in
passed as data (`route_score="softmax"`, `n_shared`). To
engine/decode_program.py it describes the pool of its "full" layers
alone and, for the "window" layers, the second kind of state of the
contract there, which takes positions: the third model to describe a
state, and the first whose state is rows. Its decode step reads the
pool itself (`decode_from_pool`: each slot's live pages, no gathered
window).

One counter beside the expert layer's (`step_counters`):
`window_cells_live`, the ring cells the window layers' active rows
attended, min(t + 1, window) a row and layer.
"""

from __future__ import annotations

from typing import Optional, Sequence

from deeplearning4j_tpu.zoo.latent_moe import LatentMoETransformer
from deeplearning4j_tpu.zoo.short_conv_moe import ShortConvMoETransformer

KINDS = ("full", "window")


class WindowMoETransformer(LatentMoETransformer):
    def __init__(self, layer_kinds: Sequence[str] = ("full", "window",
                                                     "window", "window",
                                                     "full"),
                 heads: Optional[Sequence[int]] = None,
                 n_kv_heads: int = 2, head_dim: int = 16, window: int = 16,
                 window_theta: float = 1e4,
                 full_rope: Optional[dict] = None, **kw):
        """`full_rope`: the full layers' rotation as `rope_theta` and,
        where it is scaled, YaRN's `factor`,
        `original_max_position_embeddings`, `beta_fast`, `beta_slow`,
        `attention_factor` and `partial_rotary_factor` (the published
        `rope_parameters.full_attention` keys); None: theta 1e6 over
        the whole head."""
        kw.setdefault("sandwich_norm", False)
        kw.setdefault("route_score", "softmax")
        kinds = tuple(str(k) for k in layer_kinds)
        kw.setdefault("n_dense_layers", 1)
        kw["n_moe_layers"] = len(kinds) - int(kw["n_dense_layers"])
        rope = dict(full_rope or {"rope_theta": 1e6})
        kw["rope_theta"] = float(rope["rope_theta"])
        super().__init__(**kw)
        if not kinds or set(kinds) - set(KINDS) or kw["n_moe_layers"] < 0:
            raise ValueError(f"layer_kinds {kinds}: one of {KINDS} a layer, "
                             f"n_dense_layers of them at the least")
        heads = tuple(int(h) for h in heads) if heads is not None \
            else (self.n_heads,) * len(kinds)
        if len(heads) != len(kinds) or any(h % int(n_kv_heads)
                                           for h in heads) \
                or int(head_dim) % 2:
            raise ValueError(
                f"heads {heads} a layer on {n_kv_heads} K/V heads of "
                f"{head_dim}: whole groups, one count a layer, rotary pairs")
        self.layer_kinds, self.heads = kinds, heads
        self.n_kv_heads, self.head_dim = int(n_kv_heads), int(head_dim)
        self.window = int(window)
        self.window_theta = float(window_theta)
        self.full_rope = rope
        self._inv, self._factor = self._full_rotation(rope)

    def _full_rotation(self, rope: dict):
        """(inv, factor) of `rotary` for the full layers: YaRN's over the
        first `partial_rotary_factor` of each head where `rope` scales
        the rotation, theta's over the whole head (None, 1.0) where it
        does not."""
        from deeplearning4j_tpu.nn.latent_attention import (
            yarn_inverse_frequencies,
        )

        partial = float(rope.get("partial_rotary_factor", 1.0))
        if "factor" not in rope:
            if partial != 1.0:
                raise ValueError(f"a partial rotation without YaRN's "
                                 f"scaling is not served: {rope}")
            return None, 1.0
        return yarn_inverse_frequencies(
            int(self.head_dim * partial), float(rope["rope_theta"]),
            float(rope["factor"]),
            int(rope["original_max_position_embeddings"]),
            float(rope["beta_fast"]), float(rope["beta_slow"])), \
            float(rope["attention_factor"])

    def _mix_shapes(self, layer: int) -> dict:
        h, d, heads = self.hidden, self.head_dim, self.heads[layer]
        return {"norm_in": (h,), "wq": (h, heads * d),
                "wk": (h, self.n_kv_heads * d),
                "wv": (h, self.n_kv_heads * d), "attn_gate": (h, heads),
                "wo": (heads * d, h)}

    # ----------------------------------- what DecodeProgram builds from
    # the pool of token rows and its scatter and gather: LFM2's
    kv_page_axis = 2
    kv_shape = ShortConvMoETransformer.kv_shape
    write_cells = ShortConvMoETransformer.write_cells
    read_window = ShortConvMoETransformer.read_window

    @property
    def step_counters(self):
        return super().step_counters + ("window_cells_live",)

    @property
    def n_page_layers(self) -> int:
        """The full layers alone cache rows in the page pool."""
        return self.layer_kinds.count("full")

    @property
    def mix_kind(self):
        return tuple("state" if k == "window" else "pages"
                     for k in self.layer_kinds)

    @property
    def state_dtype(self):
        return self.param_dtype

    def state_shape(self, max_slots: int):
        from deeplearning4j_tpu.nn.window_attention import state_shape

        return state_shape(self.layer_kinds.count("window"), max_slots,
                           self.window, self.n_kv_heads * self.head_dim)

    def _heads(self, lp) -> int:
        return lp["attn_gate"].shape[1]

    def _project(self, lp, x, positions, full: bool):
        from deeplearning4j_tpu.nn.gqa_attention import gated_project

        if full:
            return gated_project(lp, x, positions, self._heads(lp),
                                 self.n_kv_heads, self.rope_theta, self.eps,
                                 self._inv, self._factor)
        return gated_project(lp, x, positions, self._heads(lp),
                             self.n_kv_heads, self.window_theta, self.eps)

    def project(self, lp, x, positions):
        import jax

        with jax.named_scope("qkv"):
            q, g, cell = self._project(lp, x, positions, True)
        return (q, g), cell

    def _gated_out(self, lp, x, att, g, active):
        """The gate on each head, then LatentMoETransformer's output
        projection and feed-forward half (`attn_out` around both)."""
        import jax

        from deeplearning4j_tpu.nn.gqa_attention import gate

        with jax.named_scope("attn_out"):
            att = gate(att, g)
        return self._finish(lp, x, att, active)

    def _with_cells(self, counts, cells):
        """The expert layer's counts (None in a dense layer) and this
        layer's live ring cells, as one vector of `step_counters`."""
        import jax.numpy as jnp

        if counts is None:
            counts = jnp.zeros(len(self.step_counters) - 1, jnp.int32)
        return jnp.concatenate(
            [counts, jnp.reshape(jnp.asarray(cells, jnp.int32), (1,))])

    def decode_from_pool(self, lp, x, q, pool, li, page_ids, live,
                         active):
        """A full layer of the decode step, its attention read straight
        from the pool: each active row's live pages alone."""
        import jax

        from deeplearning4j_tpu.nn.gqa_attention import gqa_pool_attention

        q, g = q
        with jax.named_scope("attn"):
            att = gqa_pool_attention(q, pool, li, page_ids, live, active,
                                     self.n_kv_heads)
        x, counts = self._gated_out(lp, x, att, g, active)
        return x, None if counts is None else self._with_cells(counts, 0)

    def chunk_finish(self, lp, x, q, cell, window, start):
        import jax

        from deeplearning4j_tpu.nn.gqa_attention import gqa_chunk_attention

        (q, g), (k, v) = q, (a.astype(window[0].dtype) for a in cell)
        with jax.named_scope("attn"):
            att = gqa_chunk_attention(q, k, v, *window, start,
                                      self.n_kv_heads)
        return self._gated_out(lp, x, att, g, None)[0]

    def _window_out(self, lp, x, att, g):
        """A window layer's heads gated, through `W_o`, onto the
        stream."""
        from deeplearning4j_tpu.nn.attention import mm
        from deeplearning4j_tpu.nn.gqa_attention import gate

        return x + self._post(lp, "norm_post_attn", mm(gate(att, g),
                                                       lp["wo"]))

    def state_step(self, lp, x, state, si, active, positions):
        """A window layer of the decode step: `state` is every window
        layer's ring, `si` this layer's index in it. Scopes `swa/*`,
        whose parts no other table knows (benchmark/swa_scopes.py)."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.nn import window_attention as wa

        with jax.named_scope("swa/proj"):
            q, g, (k, v) = self._project(lp, x, positions, False)
        with jax.named_scope("swa/ring_write"):
            state = wa.ring_write(state, si, k, v, positions, active)
        with jax.named_scope("swa/ring_read"):
            ring_k, ring_v = state[si, :, 0], state[si, :, 1]
        with jax.named_scope("swa/mix"):
            att = wa.window_decode_attention(q, ring_k, ring_v, positions,
                                             self.n_kv_heads)
        with jax.named_scope("swa/out"):
            x = self._window_out(lp, x, att, g)
            cells = jnp.sum(jnp.where(
                active, jnp.minimum(positions + 1, self.window), 0),
                dtype=jnp.int32)
        x, counts = self._ffn(lp, x, active)
        return x, state, self._with_cells(counts, cells)

    def state_chunk(self, lp, x, entry, n_state, positions):
        """A window layer over a chunk of one slot: `entry` [2, W, C]
        is the slot's ring of this layer as the chunk found it."""
        import jax

        from deeplearning4j_tpu.nn import window_attention as wa

        start = positions[0]
        with jax.named_scope("swa/proj"):
            q, g, cell = self._project(lp, x, positions, False)
            k, v = (a.astype(entry.dtype) for a in cell)
        with jax.named_scope("swa/mix"):
            att = wa.window_chunk_attention(q, k, v, entry[0], entry[1],
                                            start, self.n_kv_heads)
        with jax.named_scope("swa/out"):
            x = self._window_out(lp, x, att, g)
        with jax.named_scope("swa/ring_write"):
            entry = wa.absorb(entry, k, v, start, n_state)
        return self._ffn(lp, x, None)[0], entry
