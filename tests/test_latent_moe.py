"""The latent-attention, sparse-expert decoder (zoo/latent_moe.py,
nn/latent_attention.py, nn/moe.py) at a tiny preset of the block the
benchmark serves at its published widths: h 64, 4 heads of 16 + 8 / 16,
kv_lora_rank 16, q_lora_rank 24, 8 experts of width 32 with 2 a token
and 1 shared, 1 dense + 2 expert layers; seeded random weights.

The pins:
  * chunk prefill, then decode, through the paged latent pool agree
    with the plain reference's full forward pass on LOGITS
    (benchmark/reference/pangu_ultra_moe.py; float32 on the CPU);
  * the absorbed decode attention agrees with the expanded one;
  * DecodeEngine is BYTE-IDENTICAL to sequential_decode for this model
    under slot churn, shared prefixes and copy-on-write, on one compile;
  * the share test: the partial results of all shares of the experts,
    the shared expert counted once, add up to the uncut layer;
  * bfloat16 storage stays inside a stated tolerance of the float32
    reference where fp8 operands do not;
  * the expert layer's counters.
"""

import random

import numpy as np
import pytest

from benchmark.reference import pangu_ultra_moe as ref
from deeplearning4j_tpu.engine.decode_program import (
    SCRATCH_PAGE,
    DecodeProgram,
)
from deeplearning4j_tpu.serving.continuous import (
    DecodeEngine,
    sequential_decode,
)
from deeplearning4j_tpu.zoo.latent_moe import LatentMoETransformer

pytestmark = pytest.mark.serving

VOCAB, CTX, SLOTS, PAGE = 256, 64, 4, 8
TINY = dict(vocab_size=VOCAB, hidden=64, n_heads=4, q_lora_rank=24,
            kv_lora_rank=16, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
            dense_ff=128, moe_ff=32, n_experts=8, top_k=2, n_shared=1,
            routed_scale=2.5, n_dense_layers=1, n_moe_layers=2,
            max_ctx=CTX, rope_theta=10000.0, seed=5)


def config_of(m) -> dict:
    """The reference's configuration keys for a model."""
    return {"hidden_size": m.hidden, "num_attention_heads": m.n_heads,
            "q_lora_rank": m.q_lora_rank, "kv_lora_rank": m.kv_lora_rank,
            "qk_nope_head_dim": m.qk_nope_dim,
            "qk_rope_head_dim": m.qk_rope_dim, "v_head_dim": m.v_head_dim,
            "intermediate_size": m.dense_ff,
            "moe_intermediate_size": m.moe_ff,
            "num_experts_per_tok": m.top_k, "n_shared_experts": m.n_shared,
            "num_hidden_layers": m.n_layers,
            "first_k_dense_replace": m.n_dense_layers,
            "vocab_size": m.vocab_size,
            "experts_held": list(m.experts_held),
            "router_experts": m.n_experts, "rms_norm_eps": m.eps,
            "rope_theta": m.rope_theta,
            "routed_scaling_factor": m.routed_scale}


@pytest.fixture(scope="module")
def model():
    # the share of a chip that holds 4 of the 8 experts
    return LatentMoETransformer(experts_held=(0, 1, 2, 5), **TINY).init()


@pytest.fixture(scope="module")
def program(model):
    prog = DecodeProgram(model, max_slots=SLOTS, page_size=PAGE)
    prog.warmup(prog.init_kv())
    return prog


def paged_logits(prog, tokens, n_prompt, width=None):
    """Logits of positions n_prompt-1 .. len(tokens)-2 of one sequence
    through the paged pool: the prompt by the compiled chunk program,
    then one position at a time by the model's own layer functions in
    the decode step's order (project, write the cell, gather the
    window, finish), teacher-forced, with the logits kept where the
    compiled step keeps their argmax. Every window is `width` pages
    wide (None: the narrowest of the program's ladder that holds the
    live pages, as the engine takes it)."""
    import jax
    import jax.numpy as jnp

    m, ps, pps = prog.model, prog.page_size, prog.pages_per_slot
    table = list(range(1, pps + 1))
    kv = prog.init_kv()
    for start in prog.chunk_starts(n_prompt):
        pages = prog.block_pages(n_prompt, start)
        kv = prog.prefill_chunk(
            kv, tokens[start:min(n_prompt, start + prog.chunk_tokens)],
            start, prog.window_pages(table, start - 1, width),
            table[pages.start:pages.stop])

    @jax.jit
    def step(params, pool, tok, pos, page_ids, wp, wo):
        x = m.embed(params, tok, pos)
        live = jnp.minimum(pos + 1, prog.window)
        for li, lp in enumerate(params["layers"]):
            q, cell = m.project(lp, x, pos)
            pool = m.write_cells(pool, li, cell, wp, wo)
            x, _ = m.decode_finish(lp, x, q, m.read_window(pool, li, page_ids),
                                   live, page_ids[:, 0] != SCRATCH_PAGE)
        return pool, m.head(params, x)

    out = []
    for pos in range(n_prompt - 1, len(tokens) - 1):
        first = pos == n_prompt - 1     # the prefill wrote this cell
        kv, logits = step(
            m.params, kv, jnp.asarray([tokens[pos]], jnp.int32),
            jnp.asarray([pos], jnp.int32),
            jnp.asarray(prog.window_pages(table, pos, width))[None],
            jnp.asarray([SCRATCH_PAGE if first else table[pos // ps]],
                        jnp.int32),
            jnp.asarray([0 if first else pos % ps], jnp.int32))
        out.append(np.asarray(logits[0], np.float32))
    return np.stack(out)


def _sequence(seed, n_prompt=21, n_new=18):
    rng = np.random.default_rng(seed)
    return rng.integers(0, VOCAB, n_prompt + n_new).tolist(), n_prompt


# ================================================= against the reference
def test_model_has_the_references_shapes(model):
    import jax

    want = ref.param_shapes(config_of(model))
    got = jax.tree_util.tree_map(lambda a: tuple(a.shape), model.params)
    assert got == dict(want, layers=tuple(want["layers"]))
    assert model.num_params() == ref.n_params(config_of(model))


@pytest.mark.parametrize("seed", [0, 1])
def test_prefill_then_decode_through_the_pool_match_the_reference_logits(
        program, model, seed):
    """float32 on the CPU: both sides sum the same products in another
    order, so logits of spread 1 agree to a few 1e-5; 1e-3 leaves room
    and is a hundredth of what fp8 operands do (the bfloat16 test)."""
    import jax.numpy as jnp

    tokens, n_prompt = _sequence(seed)
    got = paged_logits(program, tokens, n_prompt)
    want = np.asarray(ref.logits_fn(
        model.params, jnp.asarray([tokens]), config_of(model)))[0]
    want = want[n_prompt - 1:len(tokens) - 1]
    assert np.std(want) > 0.5
    np.testing.assert_allclose(got, want, atol=1e-3, rtol=0)


@pytest.fixture(scope="module")
def wide_program():
    """A window past the floor of the width ladder: 1,024 positions in
    pages of 128, so the programs are compiled at 4 and at 8 pages."""
    m = LatentMoETransformer(experts_held=(0, 1, 2, 5),
                             **dict(TINY, max_ctx=1024)).init()
    prog = DecodeProgram(m, max_slots=2, page_size=128)
    assert prog.widths == (4, 8)
    prog.warmup(prog.init_kv())
    return prog


@pytest.mark.parametrize("seed,n_prompt", [(0, 300), (1, 700)])
def test_a_chunk_at_a_narrow_width_gives_the_full_widths_logits(
        wide_program, seed, n_prompt):
    """The chunk program expands as many pages of the window as it is
    handed: a prompt prefilled through the narrowest width that holds
    its prior pages (4 pages while they fit, then 8) and one through
    the whole window leave the same rows in the pool, so the logits
    decoded from them agree, with each other and with the reference,
    inside the tolerance of the test above. Both widths were
    dispatched, and neither traced twice."""
    import jax.numpy as jnp

    prog, m = wide_program, wide_program.model
    tokens = np.random.default_rng(seed).integers(
        0, VOCAB, n_prompt + 6).tolist()
    d0 = prog.trace_stats()["dispatches"]["chunk_by_width"]
    narrow = paged_logits(prog, tokens, n_prompt)
    d1 = prog.trace_stats()["dispatches"]["chunk_by_width"]
    full = paged_logits(prog, tokens, n_prompt, width=8)
    d2 = prog.trace_stats()["dispatches"]["chunk_by_width"]
    n_chunks = -(-n_prompt // 128)
    assert d1[4] - d0[4] == min(n_chunks, 5)     # 0..4 prior pages
    assert d1[8] - d0[8] == n_chunks - min(n_chunks, 5)
    assert (d2[4] - d1[4], d2[8] - d1[8]) == (0, n_chunks)
    want = np.asarray(ref.logits_fn(
        m.params, jnp.asarray([tokens]), config_of(m)))[0]
    want = want[n_prompt - 1:len(tokens) - 1]
    assert np.std(want) > 0.5
    np.testing.assert_allclose(narrow, full, atol=1e-3, rtol=0)
    np.testing.assert_allclose(narrow, want, atol=1e-3, rtol=0)
    assert set(prog.trace_stats()["trace_counts"].values()) == {1}


def test_served_tokens_are_the_references_best(program, model):
    """The compiled step's greedy tokens, judged as the benchmark's
    `correct` judges them: no served token's reference logit lies
    below the reference's best."""
    import jax.numpy as jnp

    prompt = _sequence(3)[0][:21]
    _, toks = sequential_decode(program, prompt, 20)
    gaps = np.asarray(ref.served_gaps(
        model.params, jnp.asarray([prompt + toks]), config_of(model)))
    assert gaps[0, len(prompt) - 1:].max() <= 1e-4


def test_absorbed_decode_attention_matches_the_expanded(model):
    """One layer, 24 positions: the last position's attention output
    by the chunk path (keys and values expanded from the latent rows)
    and by the decode path (`W_kvb` folded into query and output, the
    rows attended as stored)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn import latent_attention as la

    m, lp, t = model, model.params["layers"][1], 24
    x = jax.random.normal(jax.random.PRNGKey(2), (t, m.hidden))
    pos = jnp.arange(t)
    q, cell = m.project(lp, x, pos)
    empty = jnp.zeros((1, PAGE, cell.shape[-1]))
    expanded = la.latent_chunk_attention(
        lp, q, cell, empty, 0, m._dims, m.v_head_dim, m._scale)
    window = jnp.reshape(cell, (1, t // PAGE, PAGE, -1))
    last = (q[0][-1:], q[1][-1:])
    absorbed = la.unabsorb_output(lp, la.latent_decode_attention(
        la.absorb_query(lp, last, m._dims, m.v_head_dim), window,
        jnp.asarray([t]), m._scale), m._dims, m.v_head_dim)
    assert float(jnp.std(expanded[-1])) > 0.05
    np.testing.assert_allclose(np.asarray(absorbed[0]),
                               np.asarray(expanded[-1]), atol=2e-5, rtol=0)


# the reference's keys for a layer with no shared expert, every expert
# of 64 routed over (benchmark/reference/lfm2_moe.py reads these)
NO_SHARED_CFG = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=128, moe_intermediate_size=32, num_experts_per_tok=4,
    num_hidden_layers=2, num_dense_layers=1, vocab_size=VOCAB,
    conv_L_cache=3, router_experts=64, experts_held=list(range(64)),
    layer_types=["conv", "conv"], rope_parameters={"rope_theta": 1e6},
    norm_eps=1e-5, route_norm_eps=1e-6, routed_scaling_factor=1.0)


@pytest.mark.parametrize("shared", [True, False],
                         ids=["shared_expert", "no_shared_expert"])
def test_shares_of_the_experts_add_up_to_the_uncut_layer(shared):
    """The experts as 4 shares (8 as 4 of 2 beside a shared expert; 64
    as 4 of 16 with none, a selection bias and an epsilon under the
    renormalisation): every share routes over all and computes its own
    experts' terms, and the shared expert where the layer has one;
    their sum, the shared expert counted once, is the uncut
    reference's layer."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.attention import gated_mlp
    from deeplearning4j_tpu.nn.moe import expert_layer

    if shared:
        whole = LatentMoETransformer(**TINY).init()
        want_fn = lambda lp, xn: ref.expert_ffn(  # noqa: E731
            lp, xn, config_of(whole))
        shares = [(0, 1), (2, 3), (4, 5), (6, 7)]
    else:
        from benchmark.reference import lfm2_moe
        from deeplearning4j_tpu.zoo.hybrid_delta import settle

        whole = LatentMoETransformer(**dict(
            TINY, n_experts=64, top_k=4, n_shared=0, routed_scale=1.0,
            router_bias=True, route_eps=1e-6)).init()
        want_fn = lambda lp, xn: lfm2_moe.expert_ffn(  # noqa: E731
            lp, xn, NO_SHARED_CFG)
        shares = [tuple(range(i, i + 16)) for i in range(0, 64, 16)]
    lp = settle(whole.params["layers"][2]) if not shared \
        else whole.params["layers"][2]
    assert ("sg" in lp) == shared
    xn = jax.random.normal(jax.random.PRNGKey(4), (12, whole.hidden))
    want = want_fn(lp, xn)
    total = 0.0
    for held in shares:
        mine = dict(lp, **{k: lp[k][jnp.asarray(held)]
                           for k in ("eg", "eu", "ed")})
        y, counts = expert_layer(mine, xn, held, whole.top_k,
                                 whole.routed_scale,
                                 active=jnp.ones(12, bool),
                                 norm_eps=whole.route_eps)
        total = total + y
        assert int(counts[0]) == 12 * whole.top_k
    if shared:
        total = total - (len(shares) - 1) * gated_mlp(
            xn, lp["sg"], lp["su"], lp["sd"])
    assert float(jnp.std(want)) > 0.1
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5, rtol=0)
    # and a share alone is not the layer
    assert float(jnp.max(jnp.abs(y - want))) > 0.05
    # with every expert held the layer is the model's own: every routed
    # pair falls on a held expert
    every = tuple(range(whole.n_experts))
    y, counts = expert_layer(lp, xn, every, whole.top_k, whole.routed_scale,
                             active=jnp.ones(12, bool),
                             norm_eps=whole.route_eps)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5,
                               rtol=0)
    assert int(counts[0]) == int(counts[1]) == 12 * whole.top_k


def test_route_without_an_epsilon_is_the_program_it_was():
    """`norm_eps` is data: at 0 (what every model before LFM2-MoE
    passes) the weights are `scale * s / sum(s)` bit for bit and the
    traced program has no add under the division; at 1e-6 they are
    `scale * s / (sum(s) + 1e-6)`."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.moe import route

    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (9, 32))
    w = jax.random.normal(jax.random.fold_in(key, 1), (32, 16))
    bias = 0.1 * jax.random.normal(jax.random.fold_in(key, 2), (16,))

    def before(x, w, bias):
        scores = jax.nn.sigmoid(jnp.matmul(
            x, w, precision=jax.lax.Precision.HIGHEST))
        _, top_i = jax.lax.top_k(scores + bias, 3)
        top_s = jnp.take_along_axis(scores, top_i, axis=-1)
        return top_i, 2.5 * top_s / jnp.sum(top_s, axis=-1, keepdims=True)

    now = jax.jit(lambda x, w, b: route(x, w, 3, 2.5, b))
    for got, want in zip(now(x, w, bias), jax.jit(before)(x, w, bias)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    text = str(jax.make_jaxpr(lambda x, w, b: route(x, w, 3, 2.5, b))(
        x, w, bias))
    assert str(jax.make_jaxpr(before)(x, w, bias)) == text
    top_i, with_eps = route(x, w, 3, 2.5, bias, norm_eps=1e-6)
    top_s = jnp.take_along_axis(jax.nn.sigmoid(jnp.matmul(
        x, w, precision=jax.lax.Precision.HIGHEST)), top_i, axis=-1)
    np.testing.assert_allclose(
        np.asarray(with_eps), np.asarray(2.5 * top_s / (
            jnp.sum(top_s, axis=-1, keepdims=True) + 1e-6)), rtol=1e-6)
    assert float(jnp.max(jnp.abs(jnp.sum(with_eps, -1) - 2.5))) < 1e-4


# ==================================================== bfloat16 storage
def _median_error(prog, ref_params, cfg, control=None):
    """Median over positions of |logits - reference| / |reference|:
    a router near-tie that the rounding turns over moves one position
    by much and the median by nothing."""
    import jax.numpy as jnp

    errs = []
    for seed in (0, 1):
        tokens, n_prompt = _sequence(seed)
        want = np.asarray(ref.logits_fn(
            ref_params, jnp.asarray([tokens]), cfg))[0]
        if control is None:
            got = paged_logits(prog, tokens, n_prompt)
        else:
            got = np.asarray(ref.logits_fn(
                ref_params, jnp.asarray([tokens]), cfg, control))[0]
            got = got[n_prompt - 1:len(tokens) - 1]
        want = want[n_prompt - 1:len(tokens) - 1]
        errs += list(np.linalg.norm(got - want, axis=-1)
                     / np.linalg.norm(want, axis=-1))
    return float(np.median(errs))


def test_bfloat16_stays_inside_a_tolerance_that_fp8_operands_do_not():
    """Weights and pool in bfloat16, sums in float32: the logits sit
    within 3% of the float32 reference's on the same (bfloat16-valued)
    weights at the median position (measured 0.7%: 8 bits of mantissa
    through three layers; the reference's own path with bfloat16
    operands reads the same). The reference with every product's
    operands in float8 e4m3 (3 bits) reads 16% and is out."""
    import jax.numpy as jnp

    m = LatentMoETransformer(experts_held=(0, 1, 2, 5),
                             param_dtype="bfloat16", **TINY).init()
    prog = DecodeProgram(m, max_slots=1, page_size=PAGE)
    assert prog.init_kv().dtype == jnp.bfloat16
    assert m.params["layers"][1]["eg"].dtype == jnp.bfloat16
    assert m.params["layers"][1]["norm_in"].dtype == jnp.float32
    cfg = config_of(m)
    assert _median_error(prog, m.params, cfg) < 0.03
    assert _median_error(prog, m.params, cfg, control="fp8") > 0.06


# ============================================== engine against the oracle
def _requests(n, seed=0, max_prompt=20, max_new=12):
    rng = random.Random(seed)
    return [([rng.randrange(VOCAB)
              for _ in range(rng.randrange(2, max_prompt))],
             rng.randrange(2, max_new)) for _ in range(n)]


def _oracle(program, reqs):
    return [sequential_decode(program, p, mx)[1] for p, mx in reqs]


def _drive(program, reqs, stagger=2, **kwargs):
    eng = DecodeEngine(program=program, queue_limit=64, **kwargs)
    handles, i, steps = [], 0, 0
    while i < len(reqs) or any(not h.done for h in handles):
        if i < len(reqs) and steps % stagger == 0:
            handles.append(eng.submit(*reqs[i]))
            i += 1
        eng.step_once()
        steps += 1
        assert steps < 3000, "engine made no progress"
    return eng, [h.result(timeout_s=0) for h in handles]


def test_engine_is_byte_identical_to_the_oracle_under_slot_churn(program):
    """14 requests through 4 slots, joining every other step: every
    output equals the one-request-at-a-time oracle's, on the compiles
    of the warm-up."""
    reqs = _requests(14, seed=7)
    want = _oracle(program, reqs)
    before = dict(program.trace_stats()["trace_counts"])
    eng, got = _drive(program, reqs, max_prefills_per_step=2)
    assert got == want
    assert program.trace_stats()["trace_counts"] == before
    assert len(before) == 3 and all(v == 1 for v in before.values())
    assert eng.stats()["completed"] == 14


def test_shared_prefixes_and_copy_on_write_keep_byte_identity(program):
    """Twins that share 2 whole pages and half of a third, then
    diverge: the later ones map the trie's pages, copy the partial one
    on their first write, and still emit the oracle's tokens."""
    rng = random.Random(11)
    prefix = [rng.randrange(VOCAB) for _ in range(2 * PAGE + 4)]
    reqs = [(prefix + [rng.randrange(VOCAB) for _ in range(k)], 9)
            for k in (0, 0, 3, 5)] + _requests(3, seed=12)
    want = _oracle(program, reqs)
    eng, got = _drive(program, reqs, stagger=6, max_prefills_per_step=1)
    assert got == want
    st = eng.stats()
    assert st["prefix_hits"] > 0 and st["cow_copies"] > 0
    audit = eng._pool.audit()
    assert audit["leaked"] == 0 and not audit["double_freed"]


def test_ring_wrap_past_the_window_keeps_byte_identity(program):
    """Past max_ctx the latent ring slides like GPT-2's: rotary
    positions go on growing, the engine and the oracle read the same
    ring in the same order."""
    prompt = _requests(1, seed=13)[0][0]
    reqs = [(prompt, CTX + 9)]
    want = _oracle(program, reqs)
    eng, got = _drive(program, reqs)
    assert got == want and eng.stats()["ctx_wraps"] >= 1


def test_expert_counters_come_back_with_the_steps(model):
    """One request alone: every decode step routes top_k pairs in each
    of the two expert layers; those on the four held experts are
    counted apart, the most loaded expert of a layer has at most one
    of a single row, and an empty slot's row counts nothing."""
    prog = DecodeProgram(model, max_slots=2, page_size=PAGE)
    eng = DecodeEngine(program=prog)
    h = eng.submit(list(range(1, 12)), 10)
    while not h.done:
        eng.step_once()
    st = eng.stats()
    assert st["moe_assignments"] == st["steps"] * model.top_k * 2
    assert 0 < st["moe_assignments_held"] < st["moe_assignments"]
    assert st["moe_max_held_load"] <= st["steps"] * 2
    assert st["moe_experts_hit"] == st["moe_assignments_held"]
    assert prog.counters()["moe_assignments"] == st["moe_assignments"]


def test_gpt2_counts_nothing_and_keeps_three_outputs():
    from deeplearning4j_tpu.zoo.decoder import CausalTransformer

    gpt = CausalTransformer(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=1, max_ctx=32, seed=1).init()
    prog = DecodeProgram(gpt, max_slots=2, page_size=8)
    assert prog.counters() == {}
    assert "moe_assignments" not in DecodeEngine(program=prog).stats()
    assert prog.kv_shape == (1, 2, 9, 8, 32)
