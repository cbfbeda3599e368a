"""HybridDeltaTransformer: linear-attention layers with a per-slot
STATE between latent-attention layers with paged rows — the block of
Kimi-Linear-48B-A3B (`kimi_linear`), whose published widths the
benchmark serves (benchmark/configs/kimi-linear-48b-a3b.json): three
Kimi Delta Attention layers to one latent-attention layer, pre-norm,
no rotation on any key, a dense MLP first and expert layers after.

    a = x + Mix(norm_in(x));  y = a + FFN(norm_pre_mlp(a))

`layer_kinds` names each layer's `Mix`: "kda" (nn/delta_attention.py:
a matrix a head and a short convolution's tail a slot, whatever the
context) or "mla" (nn/latent_attention.py: a row a token in the page
pool). Everything else — the feed-forward halves, the expert share,
embedding, head, seeded weights — is LatentMoETransformer's. To
engine/decode_program.py it describes the pool of its "mla" layers
alone and, for the "kda" layers, the second kind of state of the
contract there (`mix_kind`, `state_shape`, `state_step`,
`state_chunk`).
"""

from __future__ import annotations

from typing import Sequence

from deeplearning4j_tpu.zoo.latent_moe import LatentMoETransformer


class HybridDeltaTransformer(LatentMoETransformer):
    def __init__(self, layer_kinds: Sequence[str] = ("kda", "kda", "kda",
                                                     "mla"),
                 kda_heads: int = 4, kda_head_dim: int = 16,
                 conv_kernel: int = 4, gate_rank: int = 16, **kw):
        kw.setdefault("q_lora_rank", None)
        kw.setdefault("rope_theta", None)
        kw.setdefault("sandwich_norm", False)
        kw.setdefault("router_bias", True)
        kinds = tuple(str(k) for k in layer_kinds)
        kw.setdefault("n_dense_layers", 1)
        kw["n_moe_layers"] = len(kinds) - int(kw["n_dense_layers"])
        super().__init__(**kw)
        if not kinds or set(kinds) - {"kda", "mla"} or kw["n_moe_layers"] < 0:
            raise ValueError(f"layer_kinds {kinds}: one of 'kda', 'mla' a "
                             f"layer, n_dense_layers of them at the least")
        self.layer_kinds = kinds
        self.kda_heads, self.kda_head_dim = int(kda_heads), int(kda_head_dim)
        self.conv_kernel, self.gate_rank = int(conv_kernel), int(gate_rank)

    def _mix_shapes(self, layer: int) -> dict:
        if self.layer_kinds[layer] == "mla":
            return super()._mix_shapes(layer)
        h, heads, d = self.hidden, self.kda_heads, self.kda_head_dim
        c, r = heads * d, self.gate_rank
        return {"norm_in": (h,), "wq": (h, c), "wk": (h, c), "wv": (h, c),
                "conv_q": (self.conv_kernel, c),
                "conv_k": (self.conv_kernel, c),
                "conv_v": (self.conv_kernel, c),
                "wf_a": (h, r), "wf_b": (r, c), "dt_bias": (c,),
                "A_log": (heads,), "wb": (h, heads),
                "wg_a": (h, r), "wg_b": (r, c), "o_norm": (d,),
                "wo": (c, h)}

    def init(self) -> "HybridDeltaTransformer":
        """LatentMoETransformer's seeded weights, with the leaves that
        are no gains moved to where such a layer's lie: decays of a
        few per cent a token, a small selection bias."""
        super().init()
        self.params["layers"] = tuple(
            settle(lp) for lp in self.params["layers"])
        return self

    # ----------------------------------- what DecodeProgram builds from
    @property
    def n_page_layers(self) -> int:
        return self.layer_kinds.count("mla")

    @property
    def mix_kind(self):
        return tuple("state" if k == "kda" else "pages"
                     for k in self.layer_kinds)

    state_dtype = "float32"

    def state_shape(self, max_slots: int) -> dict:
        from deeplearning4j_tpu.nn.delta_attention import state_shapes

        return state_shapes(self.layer_kinds.count("kda"), max_slots,
                            self.kda_heads, self.kda_head_dim,
                            self.conv_kernel)

    def state_step(self, lp, x, state, si, active, positions):
        del positions               # a recurrence has no position
        from deeplearning4j_tpu.nn.delta_attention import decode_mix

        out, state = decode_mix(lp, x, state, si, active, self.kda_heads,
                                self.eps)
        x, counts = self._ffn(lp, x + out, active)
        return x, state, counts

    def state_chunk(self, lp, x, entry, n_state, positions):
        del positions
        from deeplearning4j_tpu.nn.delta_attention import chunk_mix

        out, entry = chunk_mix(lp, x, entry, n_state, self.kda_heads,
                               self.eps)
        return self._ffn(lp, x + out, None)[0], entry


def settle(lp: dict, conv_scale: float = 1.0) -> dict:
    """A layer's seeded leaves, 1 + 0.1 n where one-dimensional, with
    those that are no gains mapped to their own ranges (n the same
    normal draw): `A_log` = n (a decay rate of order one), `dt_bias` =
    n - 4 (softplus: steps of a few per cent, so a state remembers
    tens of tokens), `router_bias` = 0.1 n; the convolution's taps
    times `conv_scale` (to order one a channel where the matrices'
    deviation is small)."""
    out = dict(lp)
    for name, leaf in lp.items():
        if name == "A_log":
            out[name] = (leaf - 1.0) * 10.0
        elif name == "dt_bias":
            out[name] = (leaf - 1.0) * 10.0 - 4.0
        elif name == "router_bias":
            out[name] = leaf - 1.0
        elif name.startswith("conv_") and conv_scale != 1.0:
            out[name] = (leaf.astype("float32")
                         * conv_scale).astype(leaf.dtype)
    return out
