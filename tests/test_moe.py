"""The expert layer's product (nn/moe.py): the hit list's kernel over
the experts some active row chose (nn/helpers/pallas_moe.py, interpret
mode here) against the dense product over every held expert, which
this file keeps as the reference (the layer as it was before PR 39),
the counter of what the layer read, and every program of the three
expert models on the kernel."""

import numpy as np
import pytest

H, F, N, TOP_K = 64, 32, 6, 2
N_ROUTED = 8
HELD = (0, 1, 2, 5)
# the toy models' layers: the period of tests/test_hybrid_decode.py and
# of tests/test_short_conv_decode.py, cut to three
KDA_KINDS = ("kda", "mla", "kda")
CONV_KINDS = ("conv", "attn", "conv")
# which experts each row chooses: the first N_ROUTED lanes of a row
# carry its router scores (`_layer`); rows 2 and 4 are inactive
ACTIVE = (True, True, False, True, False, True)
ROUTINGS = {
    "no_active_row": ([(0, 1)] * N, (False,) * N),
    "one_expert": ([(0, 3), (0, 4), (1, 2), (3, 0), (5, 6), (0, 7)],
                   ACTIVE),
    "every_expert": ([(0, 1), (2, 3), (1, 4), (5, 6), (0, 2), (1, 5)],
                     ACTIVE),
    "inactive_rows_alone": ([(0, 3), (1, 4), (2, 5), (3, 0), (5, 2),
                             (1, 0)], ACTIVE),
}


def _layer(routing, shared=True):
    """(lp, x): a toy expert layer whose products are exact in float32
    (small multiples of 1/16 summed over 64 lanes; a down matrix with
    one power of two a column), so that no dot's order of summation
    can differ between the two paths on the CPU: what is compared is
    the weighted sum, its order and its zeros. A row's first
    N_ROUTED lanes are its router scores (router = identity on them):
    4 and 3 on the experts it chooses, -4 elsewhere."""
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    e, f = len(HELD), F

    def ints(shape, lo, hi, scale):
        return rng.integers(lo, hi, shape).astype(np.float32) * scale

    x = ints((N, H), -3, 4, 0.5)
    x[:, :N_ROUTED] = -4.0
    for n, (a, b) in enumerate(routing):
        x[n, a], x[n, b] = 4.0, 3.0
    router = np.zeros((H, N_ROUTED), np.float32)
    router[np.arange(N_ROUTED), np.arange(N_ROUTED)] = 1.0
    ed = np.zeros((e, f, H), np.float32)
    for i in range(e):
        for j in range(H):
            ed[i, (3 * j + i) % f, j] = rng.choice([-1, 1]) * 2.0 ** int(
                rng.integers(-2, 2))
    lp = {"router": router, "eg": ints((e, H, f), -2, 3, 1 / 16),
          "eu": ints((e, H, f), -2, 3, 1 / 16), "ed": ed}
    if shared:
        lp.update(sg=ints((H, f), -2, 3, 1 / 16),
                  su=ints((H, f), -2, 3, 1 / 16),
                  sd=ed[0])
    return {k: jnp.asarray(v) for k, v in lp.items()}, jnp.asarray(x)


def _dense(xe, w, eg, eu, ed):
    """The reference product: every held expert over every row, a row's
    weight 0 for an expert it did not choose (nn/moe.py's einsums
    before PR 39)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    g = jnp.einsum("nh,ehf->enf", xe, eg, preferred_element_type=f32)
    u = jnp.einsum("nh,ehf->enf", xe, eu, preferred_element_type=f32)
    act = (jax.nn.silu(g) * u).astype(ed.dtype)
    ye = jnp.einsum("enf,efh->enh", act, ed, preferred_element_type=f32)
    return jnp.sum(ye * jnp.transpose(w)[:, :, None], axis=0)


def _dense_layer(lp, x, active):
    """The reference layer: nn/moe.py's router and shared expert around
    the reference product, traced as one program as the layer is, with
    its counters but what the layer read."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn import moe
    from deeplearning4j_tpu.nn.attention import gated_mlp

    @jax.jit
    def layer(lp, x, active):
        top_i, top_w = moe.route(x, lp["router"], TOP_K, 2.5)
        w = moe.held_weights(top_i, top_w, HELD)
        y = _dense(x.astype(lp["eg"].dtype), w, lp["eg"], lp["eu"],
                   lp["ed"])
        y = y + gated_mlp(x, lp["sg"], lp["su"], lp["sd"])
        return y, jnp.sum((w > 0) & active[:, None], axis=0)

    y, load = layer(lp, x, active)
    load = np.asarray(load)
    counts = [int(np.sum(active)) * TOP_K, int(load.sum()),
              int(load.max()), int((load > 0).sum())]
    return np.asarray(y), counts


def _run(lp, x, active, dense=False):
    import jax

    from deeplearning4j_tpu.nn import moe

    if dense:
        return _dense_layer(lp, x, np.asarray(active))
    fn = jax.jit(lambda lp, x, a: moe.expert_layer(
        lp, x, HELD, TOP_K, 2.5, a))
    y, counts = fn(lp, x, np.asarray(active))
    return np.asarray(y), np.asarray(counts)


def _bits(a):
    return np.asarray(a, np.float32).view(np.int32)


@pytest.mark.parametrize("name", sorted(ROUTINGS))
def test_the_hit_list_is_the_dense_layer_to_the_bit(name):
    """Float32 at toy widths, for every routing the kernel has a branch
    for: the active rows' results are the reference product's bit for
    bit; the counters agree, and what the layer read is the hit
    count, where the reference read every held expert."""
    from deeplearning4j_tpu.nn.attention import gated_mlp

    routing, active = ROUTINGS[name]
    lp, x = _layer(routing)
    y, counts = _run(lp, x, active)
    yd, cd = _run(lp, x, active, dense=True)
    rows = np.asarray(active)
    assert (_bits(y[rows]) == _bits(yd[rows])).all()
    assert counts[:4].tolist() == cd
    hit = {e for r, (a, b) in zip(active, routing) if r
           for e in (a, b) if e in HELD}
    assert counts[3] == counts[4] == len(hit)
    assert {"no_active_row": 0, "one_expert": 1, "every_expert": 4,
            "inactive_rows_alone": 2}[name] == len(hit)
    # an inactive row's routed experts add nothing: the shared expert's
    # result is all it gets
    shared = np.asarray(gated_mlp(x, lp["sg"], lp["su"], lp["sd"]))
    assert (_bits(y[~rows]) == _bits(shared[~rows])).all()


def test_random_weights_stay_within_rounding_of_the_dense_layer():
    """On weights drawn at random the two paths' dots may sum in another
    order on the CPU (the dense product is one dot over every expert):
    within a few float32 roundings, no more."""
    import jax

    lp, _ = _layer(ROUTINGS["every_expert"][0])
    key = jax.random.PRNGKey(3)
    lp = {k: jax.random.normal(jax.random.fold_in(key, i), v.shape) / 8
          for i, (k, v) in enumerate(sorted(lp.items()))}
    x = jax.random.normal(jax.random.fold_in(key, 99), (N, H))
    y, _ = _run(lp, x, ACTIVE)
    yd, _ = _run(lp, x, ACTIVE, dense=True)
    rows = np.asarray(ACTIVE)
    np.testing.assert_allclose(y[rows], yd[rows], rtol=1e-5, atol=1e-6)


def test_a_rows_result_is_its_own_whatever_the_others_route_to():
    """Row 0 on random weights beside three different sets of
    neighbours (each choosing other experts, or none active): its bits
    do not move. This is what the engine's byte identity with the
    sequential oracle rests on: the oracle runs the row alone."""
    import jax

    key = jax.random.PRNGKey(5)
    lp, _ = _layer(ROUTINGS["every_expert"][0])
    lp = {k: jax.random.normal(jax.random.fold_in(key, i), v.shape) / 8
          for i, (k, v) in enumerate(sorted(lp.items()))}
    row = jax.random.normal(jax.random.fold_in(key, 50), (1, H))
    got = []
    for i, active in enumerate([ACTIVE, (True,) * N,
                                (True,) + (False,) * (N - 1)]):
        others = jax.random.normal(jax.random.fold_in(key, 60 + i),
                                   (N - 1, H)) * (1 + i)
        y, _ = _run(lp, np.concatenate([row, others]), active)
        got.append(_bits(y[0]))
    assert all((g == got[0]).all() for g in got[1:])


# ------------------------------------------- the counter, in the engine
@pytest.mark.parametrize("slots", [2, 16])
def test_experts_read_come_back_with_the_steps(slots):
    """Two requests in two slots of the toy latent model, and in
    sixteen (the width of LFM2's step: 16 x 2 of 8 as 128 x 4 of 64):
    the layer read the experts the active rows hit, no more, whatever
    the inactive rows route to."""
    from deeplearning4j_tpu.engine.decode_program import DecodeProgram
    from deeplearning4j_tpu.serving.continuous import DecodeEngine
    from deeplearning4j_tpu.zoo.latent_moe import LatentMoETransformer

    m = LatentMoETransformer(experts_held=HELD, max_ctx=64, seed=3).init()
    eng = DecodeEngine(program=DecodeProgram(m, max_slots=slots,
                                             page_size=8))
    hs = [eng.submit(list(range(1 + i, 12 + i)), 6) for i in range(2)]
    while not all(h.done for h in hs) or eng._inflight is not None:
        eng.step_once()
    st = eng.stats()
    assert st["moe_experts_read"] == st["moe_experts_hit"] > 0
    # two rows of two choices a step in each of two expert layers
    assert st["moe_experts_read"] <= st["steps"] * 2 * TOP_K * 2


# ------------------------------------------ every program on the kernel
def _programs(model, slots):
    """{record name: (fn, example args)} of a program's step and chunk
    at every width the lint lists."""
    from deeplearning4j_tpu.engine.decode_program import DecodeProgram

    prog = DecodeProgram(model, max_slots=slots, page_size=8)
    return {r.name: (r.fn, r.example_args) for r in prog.lint_records()
            if not r.name.startswith("decode_page_copy")}


def _models():
    from deeplearning4j_tpu.zoo.hybrid_delta import HybridDeltaTransformer
    from deeplearning4j_tpu.zoo.latent_moe import LatentMoETransformer
    from deeplearning4j_tpu.zoo.short_conv_moe import (
        ShortConvMoETransformer,
    )

    small = dict(vocab_size=64, hidden=32, n_heads=4, dense_ff=64,
                 moe_ff=16, n_experts=8, top_k=2, experts_held=HELD,
                 max_ctx=64, seed=5)
    return {
        "latent": lambda: LatentMoETransformer(
            q_lora_rank=16, kv_lora_rank=16, qk_nope_dim=8,
            qk_rope_dim=8, v_head_dim=8, **small),
        "hybrid": lambda: HybridDeltaTransformer(
            kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8,
            kda_heads=2, kda_head_dim=8, gate_rank=8,
            layer_kinds=KDA_KINDS, **small),
        "conv": lambda: ShortConvMoETransformer(
            layer_kinds=CONV_KINDS, n_kv_heads=2,
            head_dim=8, **small),
    }


@pytest.mark.parametrize("kind", ["latent", "hybrid", "conv"])
def test_every_step_and_chunk_calls_the_kernel(kind):
    """At two slots and at sixteen (LFM2's shape, a row an expert and
    more at the mean), the step and the chunk at every ladder width
    read their experts through the kernel: there is no second path."""
    import jax

    make = _models()[kind]
    for slots in (2, 16):
        progs = _programs(make().init(), slots)
        assert progs and all(
            "pallas_call" in str(jax.make_jaxpr(fn)(*args))
            for fn, args in progs.values())


@pytest.mark.parametrize("kind", ["latent", "hybrid", "conv"])
def test_served_tokens_are_the_dense_products(kind, monkeypatch):
    """Sixteen slots, three requests served through the step and the
    chunk on the kernel, then again with the reference product in its
    place: the same tokens, and the same counters but what the layer
    read."""
    from deeplearning4j_tpu.engine.decode_program import DecodeProgram
    from deeplearning4j_tpu.nn.helpers import pallas_moe
    from deeplearning4j_tpu.serving.continuous import DecodeEngine

    def serve():
        eng = DecodeEngine(program=DecodeProgram(
            _models()[kind]().init(), max_slots=16, page_size=8))
        hs = [eng.submit([(7 * i + j) % 60 + 1 for j in range(5 + 7 * i)],
                         5) for i in range(3)]
        while not all(h.done for h in hs) or eng._inflight is not None:
            eng.step_once()
        st = eng.stats()
        return [h.tokens_so_far() for h in hs], st["moe_experts_hit"]

    got = serve()
    monkeypatch.setattr(pallas_moe, "grouped_experts",
                        lambda x, w, ids, n, eg, eu, ed:
                        _dense(x, w, eg, eu, ed))
    assert serve() == got
