"""MambaMoETransformer: Mamba-2 state-space layers with a per-slot
STATE between grouped-query attention layers with paged rows — the
block of Granite-4.0-H (`granitemoehybrid`), whose published widths the
benchmark serves (benchmark/configs/granite-4.0-h-small.json): nine
Mamba-2 layers to one attention layer, pre-norm, an expert layer with a
shared expert after EVERY mixer, softmax routing, the head tied to the
embedding, and three multipliers on the stream:

    x_0 = m_emb E[id]
    a = x + m_res Mix(norm_in(x));  y = a + m_res FFN(norm_pre_mlp(a))
    logits = norm(x_L) E^T / m_logits

`layer_kinds` names each layer's `Mix`: "mamba" (nn/mamba2.py: a
matrix a head and the convolution's tail a slot, whatever the context)
or "attn" (nn/gqa_attention.py with no rotation and no norm a head,
scores times `attention_multiplier`; one K row and one V row a token in
the page pool, stored in `param_dtype`). Everything else — the
feed-forward halves, the held experts and the shared one, embedding,
seeded weights, counters — is LatentMoETransformer's, with what this
block differs in passed as data (`route_score="softmax"`, `n_shared`,
`tie_embeddings`, no dense layer). To engine/decode_program.py it
describes the pool of its "attn" layers alone and, for the "mamba"
layers, the second kind of state of the contract there: the fourth
model to describe one, and the one whose state a slot is the largest.
Its decode step reads the pool itself (`decode_from_pool`: each slot's
live pages, no gathered window).
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

from deeplearning4j_tpu.zoo.latent_moe import LatentMoETransformer
from deeplearning4j_tpu.zoo.short_conv_moe import ShortConvMoETransformer

KINDS = ("mamba", "attn")


class MambaMoETransformer(LatentMoETransformer):
    def __init__(self, layer_kinds: Sequence[str] = ("mamba", "mamba",
                                                     "attn", "mamba"),
                 n_kv_heads: int = 2, head_dim: int = 16,
                 ssm_heads: int = 4, ssm_head_dim: int = 16,
                 ssm_state: int = 16, conv_taps: int = 4,
                 embedding_multiplier: float = 1.0,
                 residual_multiplier: float = 1.0,
                 logits_scaling: float = 1.0,
                 attention_multiplier: Optional[float] = None, **kw):
        """`attention_multiplier`: the scores' scale (None:
        1/sqrt(head_dim))."""
        kw.setdefault("sandwich_norm", False)
        kw.setdefault("route_score", "softmax")
        kw.setdefault("tie_embeddings", True)
        kw.setdefault("rope_theta", None)
        kw.setdefault("n_dense_layers", 0)
        kinds = tuple(str(k) for k in layer_kinds)
        kw["n_moe_layers"] = len(kinds) - int(kw["n_dense_layers"])
        super().__init__(**kw)
        if not kinds or set(kinds) - set(KINDS) or kw["n_moe_layers"] < 0:
            raise ValueError(f"layer_kinds {kinds}: one of {KINDS} a layer, "
                             f"n_dense_layers of them at the least")
        if self.n_heads % int(n_kv_heads) or self.rope_theta is not None:
            raise ValueError(
                f"{self.n_heads} query heads on {n_kv_heads} K/V heads, no "
                f"rotation: whole groups, rope_theta None")
        self.layer_kinds = kinds
        self.n_kv_heads, self.head_dim = int(n_kv_heads), int(head_dim)
        self.ssm_heads, self.ssm_head_dim = int(ssm_heads), int(ssm_head_dim)
        self.ssm_state, self.conv_taps = int(ssm_state), int(conv_taps)
        self.embedding_multiplier = float(embedding_multiplier)
        self.residual_multiplier = float(residual_multiplier)
        self.logits_scaling = float(logits_scaling)
        self.attention_multiplier = None if attention_multiplier is None \
            else float(attention_multiplier)

    def _mix_shapes(self, layer: int) -> dict:
        h, d = self.hidden, self.head_dim
        if self.layer_kinds[layer] == "attn":
            return {"norm_in": (h,), "wq": (h, self.n_heads * d),
                    "wk": (h, self.n_kv_heads * d),
                    "wv": (h, self.n_kv_heads * d),
                    "wo": (self.n_heads * d, h)}
        heads, inner = self.ssm_heads, self.ssm_heads * self.ssm_head_dim
        channels = inner + 2 * self.ssm_state
        return {"norm_in": (h,), "w_in": (h, inner + channels + heads),
                "conv_w": (self.conv_taps, channels),
                "conv_b": (channels,), "dt_bias": (heads,),
                "A_log": (heads,), "D": (heads,), "norm_y": (inner,),
                "w_out": (inner, h)}

    def init(self) -> "MambaMoETransformer":
        """LatentMoETransformer's seeded weights, the Mamba-2 vectors
        moved from a gain's range to their own (`settle`)."""
        super().init()
        self.params["layers"] = tuple(
            settle(lp) for lp in self.params["layers"])
        return self

    # ----------------------------------- what DecodeProgram builds from
    # the pool of token rows and its scatter and gather: LFM2's
    kv_page_axis = 2
    kv_shape = ShortConvMoETransformer.kv_shape
    write_cells = ShortConvMoETransformer.write_cells
    read_window = ShortConvMoETransformer.read_window

    @property
    def n_page_layers(self) -> int:
        """The attention layers alone cache rows in the page pool."""
        return self.layer_kinds.count("attn")

    @property
    def mix_kind(self):
        return tuple("state" if k == "mamba" else "pages"
                     for k in self.layer_kinds)

    state_dtype = "float32"

    def state_shape(self, max_slots: int) -> dict:
        from deeplearning4j_tpu.nn.mamba2 import state_shapes

        return state_shapes(self.layer_kinds.count("mamba"), max_slots,
                            self.ssm_heads, self.ssm_head_dim,
                            self.ssm_state, self.conv_taps)

    def embed(self, params, tokens, positions):
        return super().embed(params, tokens, positions) \
            * self.embedding_multiplier

    def _post(self, lp, gain, y):
        """Every sublayer's output times the residual multiplier."""
        return super()._post(lp, gain, y) * self.residual_multiplier

    def head(self, params, x):
        return super().head(params, x) / self.logits_scaling

    def project(self, lp, x, positions):
        import jax

        from deeplearning4j_tpu.nn.gqa_attention import project

        with jax.named_scope("qkv"):
            return project(lp, x, positions, self.n_heads, self.n_kv_heads,
                           None, self.eps)

    def decode_from_pool(self, lp, x, q, pool, li, page_ids, live,
                         active):
        """The decode step's attention read straight from the pool:
        each active row's live pages alone."""
        import jax

        from deeplearning4j_tpu.nn.gqa_attention import gqa_pool_attention

        with jax.named_scope("attn"):
            att = gqa_pool_attention(q, pool, li, page_ids, live, active,
                                     self.n_kv_heads,
                                     self.attention_multiplier)
        return self._finish(lp, x, att, active)

    def chunk_finish(self, lp, x, q, cell, window, start):
        import jax

        from deeplearning4j_tpu.nn.gqa_attention import gqa_chunk_attention

        k, v = (a.astype(window[0].dtype) for a in cell)
        with jax.named_scope("attn"):
            att = gqa_chunk_attention(q, k, v, *window, start,
                                      self.n_kv_heads,
                                      self.attention_multiplier)
        return self._finish(lp, x, att, None)[0]

    def _mixed(self, lp, x, out):
        import jax

        with jax.named_scope("ssd/out"):
            return x + self._post(lp, "norm_post_attn", out)

    def state_step(self, lp, x, state, si, active, positions):
        """A Mamba-2 layer of the decode step: `state` is every such
        layer's, `si` this layer's index in it. Scopes `ssd/*`, whose
        parts no other table knows (benchmark/group_scopes.py)."""
        del positions               # a recurrence has no position
        from deeplearning4j_tpu.nn.mamba2 import decode_mix

        out, state = decode_mix(lp, x, state, si, active, self.ssm_heads,
                                self.ssm_state, self.eps)
        x, counts = self._ffn(lp, self._mixed(lp, x, out), active)
        return x, state, counts

    def state_chunk(self, lp, x, entry, n_state, positions):
        del positions
        from deeplearning4j_tpu.nn.mamba2 import chunk_mix

        out, entry = chunk_mix(lp, x, entry, n_state, self.ssm_heads,
                               self.ssm_state, self.eps)
        return self._ffn(lp, self._mixed(lp, x, out), None)[0], entry


# Mamba-2's own initial ranges (the reference implementation's
# `A_init_range` (1, 16), `dt_min` 1e-3, `dt_max` 0.1)
A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 1e-1)


def settle(lp: dict, conv_scale: float = 1.0) -> dict:
    """A layer's seeded leaves, 1 + 0.1 n where one-dimensional, with
    those of a Mamba-2 mixer that are no gains mapped to Mamba-2's own
    ranges through u = Phi(n), uniform on (0, 1) (n the same normal
    draw): A = exp(A_log) uniform on `A_RANGE`; the step at dt_bias
    alone, softplus(dt_bias), log-uniform on `DT_RANGE`; the
    convolution's bias 0.5 n; D stays 1 + 0.1 n; the taps times
    `conv_scale` (to order one a channel where the matrices' deviation
    is small)."""
    import jax.numpy as jnp
    from jax.scipy.special import ndtr

    if "A_log" not in lp:
        return lp
    out = dict(lp)
    u = {k: ndtr((lp[k] - 1.0) * 10.0) for k in ("A_log", "dt_bias")}
    lo, hi = A_RANGE
    out["A_log"] = jnp.log(lo + (hi - lo) * u["A_log"])
    lo, hi = (math.log(v) for v in DT_RANGE)
    dt = jnp.exp(lo + (hi - lo) * u["dt_bias"])
    out["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))       # softplus^-1
    out["conv_b"] = (lp["conv_b"] - 1.0) * 5.0
    if conv_scale != 1.0:
        out["conv_w"] = (lp["conv_w"].astype(jnp.float32)
                         * conv_scale).astype(lp["conv_w"].dtype)
    return out
