"""What Granite-4.0-H's model shares with the models served before it:
the expert layer (`nn/moe.py` with softmax routing over the chosen
experts, Granite's router, and the share one chip of four holds, in the
program and in the plain reference) and the pins that the programs of
LFM2, Laguna and Kimi-Linear trace as they did before
`nn/gqa_attention.py` took `theta=None` and `scale`. The model itself
is tested in tests/test_mamba_moe.py."""

import hashlib

import numpy as np
import pytest

from benchmark.reference import granite_hybrid as ref
from deeplearning4j_tpu.engine.decode_program import DecodeProgram

pytestmark = pytest.mark.serving

VOCAB = 64
LAYERS = ["mamba", "mamba", "attention", "mamba"]
# the reference's view of a toy model: hidden 64, 4 SSD heads of 16
# (expand 1), a state of 16, 4 taps; 4 query heads on 2 K/V heads of 16
CFG = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=16, shared_intermediate_size=32,
    num_experts_per_tok=3, num_hidden_layers=4, vocab_size=VOCAB,
    router_experts=16, experts_held=[0, 1, 2, 3], layer_types=LAYERS,
    mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16, mamba_d_conv=4,
    mamba_expand=1, mamba_n_groups=1, rms_norm_eps=1e-5,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    logits_scaling=0.5, attention_multiplier=0.125)

# --------------------------------------------------------- the expert layer
def test_softmax_routing_is_the_softmax_over_the_top_k_logits():
    """`route(score="softmax")` renormalised over the chosen experts is
    Granite's router: the softmax over the `top_k` largest logits alone
    (`GraniteMoeTopKGating`)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.moe import route

    rng = np.random.default_rng(3)
    x = rng.normal(size=(6, 12)).astype(np.float32)
    w = rng.normal(size=(12, 72)).astype(np.float32)
    ids, weights = route(jnp.asarray(x), jnp.asarray(w), 10, 1.0,
                         score="softmax")
    logits = x.astype(np.float64) @ w
    top = np.argsort(-logits, axis=-1)[:, :10]
    np.testing.assert_array_equal(np.asarray(ids), top)
    lt = np.take_along_axis(logits, top, -1)
    p = np.exp(lt - lt.max(-1, keepdims=True))
    np.testing.assert_allclose(np.asarray(weights),
                               p / p.sum(-1, keepdims=True), rtol=1e-5)


@pytest.mark.parametrize("side", ["reference", "program"])
def test_four_shares_of_the_experts_add_up_to_the_uncut_layer(side):
    """The deployment the cut stands for: 4 chips hold 4 of 16 experts
    each, every chip routes over all 16 and adds the shared expert; the
    routed parts summed, the shared expert counted once, are the layer
    with every expert held."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.attention import gated_mlp
    from deeplearning4j_tpu.nn.moe import expert_layer

    key = jax.random.PRNGKey(4)
    n = lambda i, shape, s=0.3: s * jax.random.normal(  # noqa: E731
        jax.random.fold_in(key, i), shape)
    h, f, e = 16, 8, 16
    lp = {"router": n(0, (h, e), 1.0), "eg": n(1, (e, h, f)),
          "eu": n(2, (e, h, f)), "ed": n(3, (e, f, h)),
          "sg": n(4, (h, 2 * f)), "su": n(5, (h, 2 * f)),
          "sd": n(6, (2 * f, h))}
    xn = n(7, (6, h), 1.0)
    cfg = dict(CFG, router_experts=e, hidden_size=h, intermediate_size=f,
               experts_held=list(range(e)), mamba_d_head=4)

    @jax.jit
    def share(lp, held):
        part = dict(lp, **{k: lp[k][held] for k in ("eg", "eu", "ed")})
        if side == "reference":
            return ref.expert_ffn(part, xn, cfg, held=held)
        return expert_layer(part, xn, held, 3, 1.0, score="softmax")[0]

    def layer(held):
        # the ids traced, so the four shares are one program
        return np.asarray(share(lp, jnp.asarray(held, jnp.int32)))

    whole = layer(list(range(e)))
    shared = np.asarray(gated_mlp(xn, lp["sg"], lp["su"], lp["sd"]))
    shares = [layer(list(range(c, c + 4))) for c in range(0, e, 4)]
    np.testing.assert_allclose(sum(shares) - 3 * shared, whole, atol=1e-5)
    assert float(np.max(np.abs(whole - shared))) > 0.01


# ------------------------------------- the programs served before this one
# sha256 of the jaxpr of each program DecodeProgram builds for the
# models served before this one, at their default toy widths (2 slots,
# pages of 8, 64 positions), as traced before nn/gqa_attention.py took
# `theta=None` and `scale`; the decode steps of LFM2 and Laguna as
# traced since their attention layers read the pool themselves
# (`decode_from_pool`), their chunks and copies as before
PINNED = {
    "lfm2": {"decode_step_s2": "52a8efbb34959834",
             "decode_prefill_c64": "981ea8729d54e41d",
             "decode_page_copy": "f04b43b58d614b59"},
    "laguna": {"decode_step_s2": "21ec41e712e39096",
               "decode_prefill_c64": "e575f1f34ee0c24a",
               "decode_page_copy": "ffd2ef1d19758708"},
    "kimi": {"decode_step_s2": "1951f0d36112d48a",
             "decode_prefill_c64": "d28be4469efcd596",
             "decode_page_copy": "6bb900b639f0cdc1"},
}


@pytest.mark.parametrize("which", sorted(PINNED))
def test_the_programs_of_the_models_served_before_trace_as_they_did(which):
    import jax

    from deeplearning4j_tpu.zoo import (
        HybridDeltaTransformer,
        ShortConvMoETransformer,
        WindowMoETransformer,
    )

    cls = {"lfm2": ShortConvMoETransformer, "laguna": WindowMoETransformer,
           "kimi": HybridDeltaTransformer}[which]
    # a jaxpr is a function of shapes and dtypes: the weights' shapes
    # alone, so no draw is compiled
    model = cls(max_ctx=64)
    model.params = jax.eval_shape(lambda: cls(max_ctx=64).init().params)
    prog = DecodeProgram(model, max_slots=2, page_size=8)
    got = {r.name: hashlib.sha256(str(jax.make_jaxpr(r.fn)(
        *r.example_args)).encode()).hexdigest()[:16]
        for r in prog.lint_records()}
    assert got == PINNED[which]
