"""LatentMoETransformer: a decoder of the kind deployed since 2024 —
multi-head latent attention over a low-rank cache, rotary positions,
RMSNorm before and after each sublayer ("sandwich"), a gated MLP in the
leading dense layers and a sparse-expert layer (routed experts beside a
shared one) in the rest, an untied head. The block of DeepSeek-V3 and
of openPangu-Ultra-MoE-718B (`pangu_ultra_moe`), whose published widths
the benchmark serves (benchmark/configs/pangu-ultra-moe-718b.json).

    a = x + norm_post_attn(MLA(norm_in(x)))
    y = a + norm_post_mlp(FFN(norm_pre_mlp(a)))

What varies between published blocks of this family is data here: a
query with no bottleneck (`q_lora_rank=None`: one matrix `wq`), keys
with no rotation (`rope_theta=None`), norms before each sublayer only
(`sandwich_norm=False`), a router with a selection bias
(`router_bias=True`) or an epsilon under its renormalisation
(`route_eps`) or softmax scores (`route_score="softmax"`), an expert layer with no shared expert (`n_shared=0`: the
layer then has no `sg`/`su`/`sd` leaves and nn/moe.py adds none), a
head tied to the embedding (`tie_embeddings=True`: no `head` leaf).
zoo/hybrid_delta.py puts linear-attention layers with a per-slot state
between such layers; zoo/short_conv_moe.py takes the feed-forward
halves, the seeded weights and the counters for a decoder of gated
short convolutions and grouped-query attention.

Like zoo/decoder.CausalTransformer it is served, not fit: a parameter
pytree, a JitCache, and the description engine/decode_program.py builds
its three programs from (the block at the end of the class). The
mathematics is nn/latent_attention.py and nn/moe.py.

The expert layer is told which experts it holds (`experts_held`): the
router scores all `n_experts`, a token keeps its `top_k`, and the layer
computes the part of the result the held experts give, plus the shared
expert — one chip's share of an expert-parallel deployment, run here
without its exchange. `param_dtype="bfloat16"` stores matrices,
embedding and the latent page pool in bfloat16 (norm gains stay
float32); every product then sums in float32 and norms, rotary,
softmax, router scores and the residual stream are float32.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import numpy as np

from deeplearning4j_tpu.nn.jit_cache import JitCache


class LatentMoETransformer:
    def __init__(self, vocab_size: int = 512, hidden: int = 64,
                 n_heads: int = 4, q_lora_rank: Optional[int] = 24,
                 kv_lora_rank: int = 16, qk_nope_dim: int = 16,
                 qk_rope_dim: int = 8, v_head_dim: int = 16,
                 dense_ff: int = 128, moe_ff: int = 32,
                 n_experts: int = 8, top_k: int = 2,
                 experts_held: Optional[Sequence[int]] = None,
                 n_shared: int = 1, routed_scale: float = 1.0,
                 n_dense_layers: int = 1, n_moe_layers: int = 2,
                 max_ctx: int = 128,
                 rope_theta: Optional[float] = 10000.0,
                 eps: float = 1e-5, seed: int = 123,
                 param_dtype: str = "float32",
                 sandwich_norm: bool = True, router_bias: bool = False,
                 route_eps: float = 0.0, tie_embeddings: bool = False,
                 route_score: str = "sigmoid"):
        if qk_rope_dim % 2:
            raise ValueError(f"rotary pairs need an even qk_rope_dim: "
                             f"{qk_rope_dim}")
        held = tuple(range(n_experts)) if experts_held is None \
            else tuple(int(e) for e in experts_held)
        if len(set(held)) != len(held) or not all(
                0 <= e < n_experts for e in held):
            raise ValueError(f"experts_held {held} are not distinct ids "
                             f"under n_experts {n_experts}")
        self.vocab_size, self.hidden = int(vocab_size), int(hidden)
        self.n_heads = int(n_heads)
        self.q_lora_rank = None if q_lora_rank is None \
            else int(q_lora_rank)
        self.kv_lora_rank = int(kv_lora_rank)
        self.qk_nope_dim, self.qk_rope_dim = int(qk_nope_dim), int(qk_rope_dim)
        self.v_head_dim = int(v_head_dim)
        self.dense_ff, self.moe_ff = int(dense_ff), int(moe_ff)
        self.n_experts, self.top_k = int(n_experts), int(top_k)
        self.experts_held = held
        self.n_shared = int(n_shared)
        self.routed_scale = float(routed_scale)
        self.n_dense_layers = int(n_dense_layers)
        self.n_moe_layers = int(n_moe_layers)
        self.n_layers = self.n_dense_layers + self.n_moe_layers
        self.max_ctx = int(max_ctx)
        self.rope_theta = None if rope_theta is None else float(rope_theta)
        self.eps = float(eps)
        self.sandwich_norm = bool(sandwich_norm)
        self.router_bias = bool(router_bias)
        self.route_eps = float(route_eps)
        self.route_score = str(route_score)
        self.tie_embeddings = bool(tie_embeddings)
        self.seed = int(seed)
        # matrices, embedding and page pool; "float32" or "bfloat16"
        self.param_dtype = str(param_dtype)
        # the precision-policy label DecodeProgram registers
        self.compute_dtype = None if self.param_dtype == "float32" \
            else self.param_dtype
        self.params = None
        self._jit_cache = JitCache()

    # ----------------------------------------------------------- shapes
    def _mix_shapes(self, layer: int) -> dict:
        """Leaf shapes of layer `layer`'s token-mixing half."""
        del layer                   # latent attention in every layer
        h, heads = self.hidden, self.n_heads
        d_q = heads * (self.qk_nope_dim + self.qk_rope_dim)
        query = {"wq": (h, d_q)} if self.q_lora_rank is None else {
            "wq_a": (h, self.q_lora_rank), "q_norm": (self.q_lora_rank,),
            "wq_b": (self.q_lora_rank, d_q)}
        return {
            "norm_in": (h,), **query,
            "wkv_a": (h, self.kv_lora_rank + self.qk_rope_dim),
            "kv_norm": (self.kv_lora_rank,),
            "wkv_b": (self.kv_lora_rank,
                      heads * (self.qk_nope_dim + self.v_head_dim)),
            "wo": (heads * self.v_head_dim, h)}

    def param_shapes(self) -> dict:
        """Leaf shapes; matrices are [in, out], the held experts
        stacked in the order of `experts_held`."""
        h = self.hidden
        f, e, fs = self.moe_ff, len(self.experts_held), \
            self.n_shared * self.moe_ff
        norms = {"norm_pre_mlp": (h,)}
        if self.sandwich_norm:
            norms.update(norm_post_attn=(h,), norm_post_mlp=(h,))
        dense = dict(norms, w_gate=(h, self.dense_ff),
                     w_up=(h, self.dense_ff), w_down=(self.dense_ff, h))
        moe = dict(norms, router=(h, self.n_experts), eg=(e, h, f),
                   eu=(e, h, f), ed=(e, f, h))
        if fs:
            moe.update(sg=(h, fs), su=(h, fs), sd=(fs, h))
        if self.router_bias:
            moe["router_bias"] = (self.n_experts,)
        top = {"tok_emb": (self.vocab_size, h), "final_norm": (h,)}
        if not self.tie_embeddings:
            top["head"] = (h, self.vocab_size)
        return dict(top, layers=[
            dict(self._mix_shapes(i),
                 **(dense if i < self.n_dense_layers else moe))
            for i in range(self.n_layers)])

    def init(self) -> "LatentMoETransformer":
        """Seeded weights: matrices normal / sqrt(fan_in) (every
        sublayer is normed on both sides, so only the ratio matters),
        gains 1 + 0.1 n so that no norm is the identity."""
        import jax
        import jax.numpy as jnp

        key = jax.random.PRNGKey(self.seed)

        def leaf(k, shape):
            n = jax.random.normal(k, shape, jnp.float32)
            if len(shape) == 1:
                return 1.0 + 0.1 * n
            return (n / math.sqrt(shape[-2])).astype(self.param_dtype)

        def tree(k, shapes):
            return {name: leaf(jax.random.fold_in(k, i), shape)
                    for i, (name, shape) in enumerate(sorted(shapes.items()))}

        shapes = self.param_shapes()
        layers = shapes.pop("layers")
        params = tree(key, shapes)
        params["layers"] = tuple(
            tree(jax.random.fold_in(key, 100 + i), layer)
            for i, layer in enumerate(layers))
        self.params = params
        return self

    def num_params(self) -> int:
        import jax

        if self.params is None:
            return 0
        return sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree_util.tree_leaves(self.params))

    # ----------------------------------- what DecodeProgram builds from
    # (the contract is in engine/decode_program.py's docstring)
    kv_page_axis = 1

    @property
    def kv_dtype(self):
        return self.param_dtype

    @property
    def step_counters(self):
        from deeplearning4j_tpu.nn.moe import COUNTERS

        return COUNTERS if self.n_moe_layers else ()

    @property
    def n_page_layers(self) -> int:
        """Layers that cache a row a token in the page pool."""
        return self.n_layers

    @property
    def _dims(self):
        return (self.n_heads, self.qk_nope_dim, self.qk_rope_dim,
                self.kv_lora_rank)

    @property
    def _scale(self) -> float:
        return 1.0 / math.sqrt(self.qk_nope_dim + self.qk_rope_dim)

    def kv_shape(self, n_pages: int, page_size: int):
        """One latent row a token a layer, the row innermost and in
        whole lane tiles (nn/latent_attention.py says why)."""
        from deeplearning4j_tpu.nn.latent_attention import row_width

        return (self.n_page_layers, n_pages, page_size,
                row_width(self.kv_lora_rank, self.qk_rope_dim))

    def embed(self, params, tokens, positions):
        import jax.numpy as jnp

        del positions               # rotary: they enter in `project`
        return params["tok_emb"][tokens].astype(jnp.float32)

    def project(self, lp, x, positions):
        from deeplearning4j_tpu.nn.latent_attention import latent_project

        return latent_project(lp, x, positions, self._dims,
                              self.rope_theta, self.eps)

    def write_cells(self, pool, li, cell, page, offset):
        return pool.at[li, page, offset].set(cell.astype(pool.dtype))

    def read_window(self, pool, li, page_ids):
        return pool[li, page_ids]

    def decode_finish(self, lp, x, q, window, live, active):
        import jax

        from deeplearning4j_tpu.nn import latent_attention as la

        with jax.named_scope("q_proj"):
            qa = la.absorb_query(lp, q, self._dims, self.v_head_dim)
        with jax.named_scope("attn"):
            att = la.latent_decode_attention(qa, window, live, self._scale)
        with jax.named_scope("attn_out"):
            att = la.unabsorb_output(lp, att, self._dims, self.v_head_dim)
        return self._finish(lp, x, att, active)

    def chunk_finish(self, lp, x, q, cell, window, start):
        import jax

        from deeplearning4j_tpu.nn.latent_attention import (
            latent_chunk_attention,
        )

        with jax.named_scope("attn"):
            att = latent_chunk_attention(
                lp, q, cell.astype(window.dtype), window, start,
                self._dims, self.v_head_dim, self._scale)
        return self._finish(lp, x, att, None)[0]

    def _finish(self, lp, x, att, active):
        """Merged heads -> the block's output: the output projection,
        then the feed-forward half."""
        import jax

        from deeplearning4j_tpu.nn.attention import mm

        with jax.named_scope("attn_out"):
            x = x + self._post(lp, "norm_post_attn", mm(att, lp["wo"]))
        return self._ffn(lp, x, active)

    def _post(self, lp, gain: str, y):
        """A sublayer's output through its own norm where the block
        has one after the sublayer (sandwich), as it is where not."""
        from deeplearning4j_tpu.nn.attention import rms_norm

        return rms_norm(y, lp[gain], self.eps) if gain in lp else y

    def _ffn(self, lp, x, active):
        """The block's feed-forward half on the stream after token
        mixing -> (x, counts): the gated MLP or the expert layer."""
        import jax

        from deeplearning4j_tpu.nn.attention import gated_mlp, rms_norm
        from deeplearning4j_tpu.nn.moe import expert_layer

        counts = None
        if "router" in lp:
            # the layer names its own scopes; its tail is the expert
            # layer's time too, so it carries one of them (`moe/*`)
            y, counts = expert_layer(
                lp, rms_norm(x, lp["norm_pre_mlp"], self.eps),
                self.experts_held, self.top_k, self.routed_scale, active,
                self.route_eps, self.route_score)
            with jax.named_scope("moe/shared"):
                x = x + self._post(lp, "norm_post_mlp", y)
        else:
            with jax.named_scope("mlp"):
                y = gated_mlp(rms_norm(x, lp["norm_pre_mlp"], self.eps),
                              lp["w_gate"], lp["w_up"], lp["w_down"])
                x = x + self._post(lp, "norm_post_mlp", y)
        return x, counts

    def head(self, params, x):
        import jax.numpy as jnp

        from deeplearning4j_tpu.nn.attention import mm, rms_norm

        xn = rms_norm(x, params["final_norm"], self.eps)
        if "head" in params:
            return mm(xn, params["head"])
        # tied: the embedding's rows as stored, no transpose made
        emb = params["tok_emb"]
        return jnp.einsum("...d,vd->...v", xn.astype(emb.dtype), emb,
                          preferred_element_type=jnp.float32)

