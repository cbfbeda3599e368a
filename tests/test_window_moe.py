"""The third model with a per-slot state through `DecodeEngine`, and the
first whose state is rows: sliding-window attention layers over a RING
a slot (nn/window_attention.py) between full-attention layers over
paged K/V rows, both grouped-query with a sigmoid gate a head, per-layer
query head counts, YaRN's partial rotation on the full layers, softmax
routing over held experts beside a shared expert, an untied head —
served by the same engine, programs and oracle as every other model.
The engine against `sequential_decode` bitwise, both against the plain
reference (benchmark/reference/laguna.py) in LOGITS, the ring's window
row by row, and the pins that the programs of the models served before
it are the ones they were."""

import numpy as np
import pytest

from benchmark.reference import laguna as ref
from deeplearning4j_tpu.engine.decode_program import (
    SCRATCH_PAGE,
    DecodeProgram,
)
from deeplearning4j_tpu.nn import window_attention as wa
from deeplearning4j_tpu.resilience.faults import injector
from deeplearning4j_tpu.serving.continuous import (
    DecodeEngine,
    sequential_decode,
)
from deeplearning4j_tpu.zoo import WindowMoETransformer

pytestmark = pytest.mark.serving

VOCAB, CTX, SLOTS, PAGE, WINDOW = 64, 64, 3, 4, 16
LAYERS = ["full_attention", "sliding_attention", "sliding_attention",
          "sliding_attention", "full_attention"]
HEADS = [4, 6, 6, 6, 4]          # groups of 2 and 3, as 48 and 72 on 8
FULL_ROPE = {"rope_theta": 500000, "rope_type": "yarn", "factor": 8,
             "original_max_position_embeddings": 16, "beta_slow": 1,
             "beta_fast": 32, "attention_factor": 1.2,
             "partial_rotary_factor": 0.5}
# the reference's view of the toy model below
CFG = dict(
    hidden_size=64, num_key_value_heads=2, head_dim=8,
    intermediate_size=96, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, num_experts_per_tok=3,
    num_hidden_layers=5, vocab_size=VOCAB, sliding_window=WINDOW,
    router_experts=16, experts_held=[0, 1, 2, 3], layer_types=LAYERS,
    num_attention_heads_per_layer=HEADS,
    mlp_layer_types=["dense"] + ["sparse"] * 4, rms_norm_eps=1e-6,
    moe_routed_scaling_factor=2.5,
    rope_parameters={"full_attention": FULL_ROPE,
                     "sliding_attention": {"rope_type": "default",
                                           "rope_theta": 10000,
                                           "partial_rotary_factor": 1}})
# float32 on the CPU: program and reference differ by the order of
# their sums alone (logits of order one: 1e-4 is a thousand ulps)
LOGIT_TOL = 1e-4


def _model(max_ctx=CTX, window=WINDOW, **kw):
    return WindowMoETransformer(
        layer_kinds=[ref.KINDS[k] for k in LAYERS], heads=HEADS,
        n_kv_heads=2, head_dim=8, window=window, window_theta=10000,
        full_rope=FULL_ROPE, vocab_size=VOCAB, hidden=64, n_heads=4,
        dense_ff=96, moe_ff=16, n_experts=16, top_k=3,
        experts_held=[0, 1, 2, 3], n_shared=1, routed_scale=2.5,
        max_ctx=max_ctx, eps=1e-6, seed=5, **kw).init()


def _program(max_ctx=CTX, page=PAGE, window=WINDOW, **kw):
    prog = DecodeProgram(_model(max_ctx, window, **kw), max_slots=SLOTS,
                         page_size=page)
    prog.warmup(prog.init_kv())
    return prog


@pytest.fixture(scope="module")
def program():
    """A window of 64 positions in pages of 4: one chunk of 64 rows, so
    a prompt past 16 tokens wraps the ring of 16 inside its chunk."""
    prog = _program()
    assert (prog.chunk_tokens, prog.widths) == (64, (16,))
    return prog


@pytest.fixture(scope="module")
def blocks():
    """256 positions in pages of 4: chunks of 128 rows, each boundary
    where the ring of 16 wraps."""
    prog = _program(max_ctx=256)
    assert prog.chunk_tokens == 128
    return prog


@pytest.fixture(scope="module")
def wide():
    """A ring of 24 against chunks of 128 at pages of 8: a chunk
    boundary falls inside the ring (128 = 5 x 24 + 8)."""
    prog = _program(max_ctx=256, page=8, window=24)
    assert prog.chunk_tokens == 128
    return prog


def _cfg(window=WINDOW):
    return dict(CFG, sliding_window=window)


def _requests(n, seed, max_prompt=40, max_new=14):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, VOCAB, int(rng.integers(1, max_prompt))).tolist(),
             int(rng.integers(2, max_new))) for _ in range(n)]


def _oracle(program, reqs):
    return [sequential_decode(program, p, n)[1] for p, n in reqs]


def _drive(program, reqs, stagger=2, **kw):
    eng = DecodeEngine(program=program, queue_limit=64, **kw)
    handles, i, steps = [], 0, 0
    while i < len(reqs) or any(not h.done for h in handles):
        if i < len(reqs) and steps % stagger == 0:
            handles.append(eng.submit(*reqs[i]))
            i += 1
        eng.step_once()
        steps += 1
        assert steps < 3000, "engine made no progress"
    return eng, [h.result(timeout_s=0) for h in handles]


def paged_logits(prog, tokens, n_prompt):
    """Logits of positions n_prompt-1 .. len(tokens)-2 of one sequence
    through the pool and the rings: the prompt by the compiled chunk
    program (the rings told to absorb all but its last token), then one
    position at a time by the model's own layer functions in the decode
    step's order, teacher-forced."""
    import jax
    import jax.numpy as jnp

    m, ps, pps = prog.model, prog.page_size, prog.pages_per_slot
    table = list(range(1, pps + 1))
    kv, state = prog.init_kv(), prog.init_state()
    for start in prog.chunk_starts(n_prompt):
        pages = prog.block_pages(n_prompt, start)
        kv, state = prog.prefill_chunk(
            kv, tokens[start:start + prog.chunk_tokens], start,
            prog.window_pages(table, start - 1),
            table[pages.start:pages.stop],
            state=state, slot=0, n_state=prog.state_rows(n_prompt, start))

    @jax.jit
    def step(params, pool, state, tok, pos, page_ids, wp, wo):
        x = m.embed(params, tok, pos)
        live = jnp.minimum(pos + 1, prog.window)
        active = page_ids[:, 0] != SCRATCH_PAGE
        for lp, li in prog._layers(params):
            if li < 0:
                x, state, _ = m.state_step(lp, x, state, -1 - li, active,
                                           pos)
                continue
            q, cell = m.project(lp, x, pos)
            pool = m.write_cells(pool, li, cell, wp, wo)
            x, _ = m.decode_from_pool(lp, x, q, pool, li, page_ids,
                                      live, active)
        return pool, state, m.head(params, x)

    out = []
    for pos in range(n_prompt - 1, len(tokens) - 1):
        first = pos == n_prompt - 1     # the prefill wrote this cell
        ids = np.full((SLOTS, prog.widths[-1]), SCRATCH_PAGE, np.int32)
        ids[0] = prog.window_pages(table, pos, prog.widths[-1])
        one = lambda v: jnp.asarray([v] + [0] * (SLOTS - 1),  # noqa: E731
                                    jnp.int32)
        kv, state, logits = step(
            m.params, kv, state, one(tokens[pos]), one(pos),
            jnp.asarray(ids),
            one(SCRATCH_PAGE if first else table[pos // ps]),
            one(0 if first else pos % ps))
        out.append(np.asarray(logits[0], np.float32))
    return np.stack(out)


def _want(prog, tokens, n_prompt, window=WINDOW):
    import jax.numpy as jnp

    want = np.asarray(ref.logits_fn(prog.model.params,
                                    jnp.asarray([tokens]), _cfg(window)))[0]
    return want[n_prompt - 1:len(tokens) - 1]


def test_the_model_describes_rings_beside_a_pool_of_its_full_layers(
        program):
    model = program.model
    assert program.has_state
    assert model.mix_kind == ("pages", "state", "state", "state", "pages")
    # the pool holds the two full layers' K and V rows of 2 x 8; the
    # state the three window layers' rings of 16 cells of the same rows
    assert program.kv_shape == (2, 2, SLOTS * CTX // PAGE + 1, PAGE, 16)
    assert model.kv_page_axis == 2 and model.n_page_layers == 2
    state = program.init_state()
    assert state.shape == (3, SLOTS, 2, WINDOW, 16)
    assert str(state.dtype) == "float32"
    shapes = ref.param_shapes(CFG)
    assert set(model.params) == {"tok_emb", "final_norm", "head", "layers"}
    for lp, want in zip(model.params["layers"], shapes["layers"]):
        assert {k: tuple(v.shape) for k, v in lp.items()} \
            == {k: tuple(v) for k, v in want.items()}
    # a shared expert on every expert layer, no selection bias anywhere
    assert sum("sg" in lp for lp in model.params["layers"]) == 4
    assert not any("router_bias" in lp for lp in model.params["layers"])
    assert model.step_counters[-1] == "window_cells_live"


@pytest.mark.parametrize("n_prompt", [1, 2, PAGE + 1, WINDOW - 1, WINDOW,
                                      WINDOW + 1, 2 * WINDOW + 3, 45])
def test_prefill_then_decode_match_the_reference_in_logits(program,
                                                           n_prompt):
    """Prefill by one chunk of 64 rows, then decoding through the pool
    and the rings, against the reference's full forward pass: logits,
    with prompts that end before, at and past the ring's wrap inside
    the chunk (a prompt of one token absorbs no row in its chunk), over
    decodes that wrap it again."""
    tokens = np.random.default_rng(n_prompt).integers(
        0, VOCAB, n_prompt + 18).tolist()
    got = paged_logits(program, tokens, n_prompt)
    want = _want(program, tokens, n_prompt)
    assert float(np.std(want)) > 0.1
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("n_prompt", [127, 128, 129, 200])
def test_chunks_meet_at_the_rings_wrap(blocks, n_prompt):
    """Chunks of 128 rows over a ring of 16: a prompt that ends inside
    its first chunk, at its edge, one token past it and well into the
    second. The second chunk reads the ring the first one left (the
    last 16 of its absorbed rows), every prompt token but the last is
    absorbed once, and the engine is the oracle's bitwise."""
    tokens = np.random.default_rng(n_prompt).integers(
        0, VOCAB, n_prompt + 6).tolist()
    got = paged_logits(blocks, tokens, n_prompt)
    np.testing.assert_allclose(got, _want(blocks, tokens, n_prompt),
                               atol=LOGIT_TOL, rtol=0)
    prompt = tokens[:n_prompt]
    traces = dict(blocks.trace_stats()["trace_counts"])
    eng, (out,) = _drive(blocks, [(prompt, 6)])
    assert out == sequential_decode(blocks, prompt, 6)[1]
    st = eng.stats()
    assert st["trace_counts"] == traces and set(traces.values()) == {1}
    assert st["prefill_chunks"] == -(-n_prompt // 128)
    assert st["state_resets"] == 1
    assert st["state_rows"] == n_prompt - 1 + 6


@pytest.mark.parametrize("n_prompt", [100, 129, 200, 250])
def test_a_chunk_boundary_inside_the_ring(wide, n_prompt):
    """A ring of 24 against chunks of 128: the second chunk starts at
    cell 8 of the ring, so its rows read cells of both the first chunk's
    last pass round the ring and the pass before."""
    tokens = np.random.default_rng(n_prompt).integers(
        0, VOCAB, n_prompt + 5).tolist()
    got = paged_logits(wide, tokens, n_prompt)
    np.testing.assert_allclose(got, _want(wide, tokens, n_prompt, 24),
                               atol=LOGIT_TOL, rtol=0)


def _plain_band(q, k, v, window):
    """Causal attention over whole sequences with a query at t seeing
    keys t - window + 1 .. t: q [T, H, D], k, v [T, n_kv * D]."""
    t, h, d = q.shape
    kh = np.repeat(k.reshape(t, -1, d), h // (k.shape[1] // d), axis=1)
    vh = np.repeat(v.reshape(t, -1, d), h // (v.shape[1] // d), axis=1)
    s = np.einsum("thd,uhd->htu", q, kh) / np.sqrt(d)
    r = np.arange(t)
    band = (r[None, :] <= r[:, None]) & (r[None, :] > r[:, None] - window)
    s = np.where(band[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("htu,uhd->thd", p, vh).reshape(t, h * d)


def _ring_rows(q, k, v, window, chunk, n_prompt):
    """The same sequence through the ring: the first `n_prompt` rows by
    chunks of `chunk` that absorb all but the last prompt row, then one
    row at a time by the decode form. [T, H * D]."""
    import jax.numpy as jnp

    t, h, d = q.shape
    c = k.shape[1]
    entry = jnp.zeros((2, window, c), jnp.float32)
    out = []
    for start in range(0, n_prompt, chunk):
        sl = slice(start, start + chunk)
        qc, kc, vc = (np.zeros((chunk,) + a.shape[1:], np.float32)
                      for a in (q, k, v))
        n = len(q[sl])
        qc[:n], kc[:n], vc[:n] = q[sl], k[sl], v[sl]
        att = wa.window_chunk_attention(jnp.asarray(qc), jnp.asarray(kc),
                                        jnp.asarray(vc), entry[0], entry[1],
                                        start, 2)
        out.append(np.asarray(att)[:n])
        absorbed = max(0, min(chunk, n_prompt - 1 - start))
        entry = wa.absorb(entry, jnp.asarray(kc), jnp.asarray(vc), start,
                          absorbed)
    ring = jnp.broadcast_to(entry[None, None], (1, 1, 2, window, c))
    rows = out and [np.concatenate(out)[:n_prompt - 1]]
    for pos in range(n_prompt - 1, t):
        p = jnp.asarray([pos])
        ring = wa.ring_write(ring, 0, jnp.asarray(k[pos:pos + 1]),
                             jnp.asarray(v[pos:pos + 1]), p,
                             jnp.asarray([True]))
        rows.append(np.asarray(wa.window_decode_attention(
            jnp.asarray(q[pos:pos + 1]), ring[0, :, 0], ring[0, :, 1], p,
            2)))
    return np.concatenate(rows)


@pytest.mark.parametrize("window,chunk,n_prompt", [
    (8, 16, 30), (8, 8, 17), (12, 8, 25), (16, 32, 1), (5, 16, 40)])
def test_each_row_reads_exactly_its_window(window, chunk, n_prompt):
    """Rows t - window + 1 .. t and no other, whatever the chunk's
    length against the ring's and wherever its boundary falls: chunks
    then decoding through the ring are plain banded attention's rows;
    a key moved at t - window leaves row t as it was, bit for bit, and
    one moved at t - window + 1 does not."""
    rng = np.random.default_rng(window * 100 + chunk)
    t, h, d = 44, 6, 4
    q = rng.normal(size=(t, h, d)).astype(np.float32)
    k = rng.normal(size=(t, 2 * d)).astype(np.float32)
    v = rng.normal(size=(t, 2 * d)).astype(np.float32)
    got = _ring_rows(q, k, v, window, chunk, n_prompt)
    np.testing.assert_allclose(got, _plain_band(q, k, v, window),
                               atol=1e-5, rtol=0)
    row = t - 3
    for moved, same in ((row - window, True), (row - window + 1, False)):
        k2 = k.copy()
        k2[moved] += 1.0
        again = _ring_rows(q, k2, v, window, chunk, n_prompt)
        assert np.array_equal(again[row], got[row]) == same


def test_yarn_partial_rotary_is_the_published_formula():
    """The full layers' frequencies at the published parameters against
    an evaluation of YaRN's formula written out here (Peng et al. 2023,
    section 3.2, with Hugging Face's truncated bounds), and the rotation
    turns the first 64 lanes alone, cos and sin times the factor."""
    import math

    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.latent_attention import (
        rotary,
        yarn_inverse_frequencies,
    )

    dim, theta, s, orig = 64, 500000.0, 128.0, 8192
    got = yarn_inverse_frequencies(dim, theta, s, orig, 32, 1)
    # r(d) = orig / wavelength(d); gamma ramps 0 -> 1 between the
    # dimensions whose r is beta_fast = 32 and beta_slow = 1
    want = []
    bound = lambda b: dim * math.log(orig / (b * 2 * math.pi)) \
        / (2 * math.log(theta))  # noqa: E731
    lo, hi = math.floor(bound(32)), math.ceil(bound(1))
    for i in range(dim // 2):
        base = theta ** (-2 * i / dim)
        gamma = min(1.0, max(0.0, (i - lo) / (hi - lo)))
        want.append(base * (1 - gamma) + base / s * gamma)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    assert got[0] == 1.0 and got[-1] == pytest.approx(
        theta ** (-(dim - 2) / dim) / s)
    np.testing.assert_allclose(
        got, ref.yarn_frequencies(
            {"rope_theta": theta, "factor": s,
             "original_max_position_embeddings": orig, "beta_fast": 32,
             "beta_slow": 1}, dim), rtol=1e-12)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(3, 2, 128)),
                    jnp.float32)
    pos = jnp.asarray([0, 7, 9000])
    y = np.asarray(rotary(x, pos, theta, got, 1.4852030263919618))
    # lanes 64..127 pass; at position 0 the rotated ones are scaled
    np.testing.assert_array_equal(y[..., 64:], np.asarray(x)[..., 64:])
    np.testing.assert_allclose(y[0, :, :64], 1.4852030263919618
                               * np.asarray(x)[0, :, :64], rtol=1e-6)
    ang = 9000 * np.asarray(got, np.float32)
    x1, x2 = np.asarray(x)[2, :, :32], np.asarray(x)[2, :, 32:64]
    np.testing.assert_allclose(
        y[2, :, :32], 1.4852030263919618 * (x1 * np.cos(ang)
                                            - x2 * np.sin(ang)),
        atol=2e-5)


def test_one_model_holds_two_head_counts_and_gates_each_head(program):
    """The full layers' 4 query heads (a group of 2) and the window
    layers' 6 (a group of 3) on the same 2 K/V heads, and the gate: a
    head's D outputs times the sigmoid of its own logit."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.gqa_attention import gate

    layers = program.model.params["layers"]
    assert [lp["wq"].shape[1] // 8 for lp in layers] == HEADS
    assert [lp["attn_gate"].shape[1] for lp in layers] == HEADS
    att = jnp.arange(2 * 6 * 8, dtype=jnp.float32).reshape(2, 48)
    logits = jnp.asarray(np.random.default_rng(1).normal(size=(2, 6)),
                         jnp.float32)
    sig = 1 / (1 + np.exp(-np.asarray(logits)))
    np.testing.assert_allclose(
        np.asarray(gate(att, logits)),
        (np.asarray(att).reshape(2, 6, 8) * sig[..., None]).reshape(2, 48),
        rtol=1e-6)


def test_a_dropped_gate_or_a_ring_never_written_would_show(program):
    """The witnesses for the logits tests: with the gate left out of
    every layer, or the rings' decode writes left out, the logits leave
    the reference's by far more than the tolerance."""
    from deeplearning4j_tpu.nn import gqa_attention

    tokens = np.random.default_rng(0).integers(0, VOCAB, 40).tolist()
    want = _want(program, tokens, 20)
    np.testing.assert_allclose(paged_logits(program, tokens, 20), want,
                               atol=LOGIT_TOL, rtol=0)
    real_gate = gqa_attention.gate
    gqa_attention.gate = lambda att, logits: att
    try:
        bad = paged_logits(program, tokens, 20)
    finally:
        gqa_attention.gate = real_gate
    assert float(np.max(np.abs(bad - want))) > 100 * LOGIT_TOL
    real_write = wa.ring_write
    wa.ring_write = lambda ring, *a: ring
    try:
        bad = paged_logits(program, tokens, 20)
    finally:
        wa.ring_write = real_write
    assert float(np.max(np.abs(bad - want))) > 100 * LOGIT_TOL


def test_softmax_routing_is_the_renormalised_top_k():
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.moe import route

    rng = np.random.default_rng(3)
    x = rng.normal(size=(5, 12)).astype(np.float32)
    w = rng.normal(size=(12, 16)).astype(np.float32)
    ids, weights = route(jnp.asarray(x), jnp.asarray(w), 3, 2.5,
                         score="softmax")
    logits = x.astype(np.float64) @ w
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    top = np.argsort(-p, axis=-1)[:, :3]
    np.testing.assert_array_equal(np.asarray(ids), top)
    pt = np.take_along_axis(p, top, -1)
    np.testing.assert_allclose(np.asarray(weights),
                               2.5 * pt / pt.sum(-1, keepdims=True),
                               rtol=1e-5)
    with pytest.raises(ValueError):
        route(jnp.asarray(x), jnp.asarray(w), 3, 1.0, score="relu")


@pytest.mark.parametrize("side", ["reference", "program"])
def test_eight_shares_of_the_experts_add_up_to_the_uncut_layer(side):
    """The deployment the cut stands for: 8 chips hold 2 of 16 experts
    each, every chip routes over all 16 and adds the shared expert; the
    routed parts summed, the shared expert counted once, are the layer
    with every expert held."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.moe import expert_layer

    key = jax.random.PRNGKey(4)
    n = lambda i, shape, s=0.3: s * jax.random.normal(  # noqa: E731
        jax.random.fold_in(key, i), shape)
    h, f, e = 16, 8, 16
    lp = {"router": n(0, (h, e), 1.0), "eg": n(1, (e, h, f)),
          "eu": n(2, (e, h, f)), "ed": n(3, (e, f, h)), "sg": n(4, (h, f)),
          "su": n(5, (h, f)), "sd": n(6, (f, h)),
          "norm_pre_mlp": jnp.ones(h)}
    xn = n(7, (6, h), 1.0)
    cfg = dict(CFG, router_experts=e, hidden_size=h,
               moe_intermediate_size=f, experts_held=list(range(e)))

    def layer(held):
        part = dict(lp, **{k: lp[k][jnp.asarray(held, jnp.int32)]
                           for k in ("eg", "eu", "ed")})
        if side == "reference":
            return np.asarray(ref.expert_ffn(part, xn, cfg, held=held))
        return np.asarray(expert_layer(part, xn, held, 3, 2.5,
                                       score="softmax")[0])

    whole = layer(list(range(e)))
    if side == "reference":
        shared = np.asarray(ref._mlp(ref._mm(None), xn, lp["sg"], lp["su"],
                                     lp["sd"]))
    else:
        from deeplearning4j_tpu.nn.attention import gated_mlp

        shared = np.asarray(gated_mlp(xn, lp["sg"], lp["su"], lp["sd"]))
    shares = [layer(list(range(c, c + 2))) for c in range(0, e, 2)]
    np.testing.assert_allclose(sum(shares) - 7 * shared, whole, atol=1e-5)
    assert float(np.max(np.abs(whole - shared))) > 0.01


def test_engine_matches_the_oracle_bitwise_under_churn(program):
    """Staggered joins and leaves over 3 slots: every request's stream
    is its solo decode's, so no operation mixes slots' rings; the
    state's counters count as for any state, and the rings' live cells
    are min(t + 1, 16) a decode row and window layer."""
    reqs = _requests(12, seed=1)
    oracle = _oracle(program, reqs)
    before = program.counters()["window_cells_live"]
    eng, got = _drive(program, reqs)
    assert got == oracle
    st = eng.stats()
    assert st["completed"] == len(reqs)
    assert st["state_resets"] == len(reqs)
    chunk_rows = sum(len(p) - 1 for p, _ in reqs)
    assert st["state_rows"] == chunk_rows + st["tokens_total"]
    assert st["state_bytes"] == 4 * 3 * SLOTS * 2 * WINDOW * 16
    cells = sum(min(len(p) + j, WINDOW) for p, n in reqs for j in range(n))
    assert program.counters()["window_cells_live"] - before == 3 * cells


@pytest.mark.chaos
def test_eviction_replay_gives_the_same_stream(program):
    """A forced eviction re-prefills from token 0 (the chunk at 0
    starts the new slot's rings from zero) and force-feeds the emitted
    stream through the decode step, which writes the rings over it:
    byte-identical to the never-evicted oracle."""
    reqs = _requests(8, seed=4)
    oracle = _oracle(program, reqs)
    inj = injector()
    inj.inject("serving.slot_evict", mode="raise", at_hit=6, times=1)
    inj.inject("serving.slot_evict", mode="raise", at_hit=14, times=2)
    eng, got = _drive(program, reqs)
    assert got == oracle
    st = eng.stats()
    assert st["evictions"] == 3
    assert st["state_resets"] == len(reqs) + 3


def test_a_chunk_at_zero_resets_a_poisoned_ring(program):
    """The reset is a select, not a product: a slot whose rings are NaN
    decodes the oracle's stream after a chunk at position 0."""
    import jax.numpy as jnp

    eng = DecodeEngine(program=program)
    eng.state = jnp.full_like(eng.state, jnp.nan)
    prompt = list(range(1, 30))
    h = eng.submit(prompt, 6)
    while not h.done:
        eng.step_once()
    assert h.result(timeout_s=0) == sequential_decode(program, prompt, 6)[1]
    assert bool(jnp.all(jnp.isnan(eng.state[:, 1:])))


def test_the_trie_is_off_whatever_prefix_cache_says(program):
    """A cached page would bring a prefix's full-layer rows back without
    the rings at its end: no trie is built, and the streams are the
    oracle's."""
    shared = list(range(3, 3 + 4 * PAGE))
    reqs = [(shared + [7, 8, i], 5) for i in range(4)]
    eng, got = _drive(program, reqs, prefix_cache=True)
    assert got == _oracle(program, reqs)
    st = eng.stats()
    assert st["prefix_cache"] is False
    assert st["prefix_hits"] == 0 and st["trie_blocks"] == 0
    assert st["state_resets"] == 4


def test_full_layers_wrap_and_the_rings_slide_on(program):
    """Past `max_ctx` the full layers' window slides (the pool's ring
    recycles the slot's oldest page) while the window layers' rings go
    on as they always do; engine and oracle agree bitwise through it."""
    prompt = list(range(2, 2 + 40))
    eng, (out,) = _drive(program, [(prompt, 40)])
    assert out == sequential_decode(program, prompt, 40)[1]
    assert eng.stats()["ctx_wraps"] > 0


def test_no_compile_after_warmup(program):
    before = program.model._jit_cache.trace_counts()
    reqs = _requests(6, seed=8)
    _oracle(program, reqs)
    _drive(program, reqs, stagger=1)
    assert program.model._jit_cache.trace_counts() == before
    assert before[str(program.decode_key())] == 1
    assert before[str(program.chunk_key())] == 1


def test_bfloat16_storage_keeps_the_pool_and_the_rings_in_bfloat16():
    """`param_dtype="bfloat16"`: matrices, embedding, head, the K/V pool
    and the rings are bfloat16, gains float32, and the engine still
    equals its oracle bitwise."""
    model = _model(param_dtype="bfloat16")
    prog = DecodeProgram(model, max_slots=2, page_size=PAGE)
    assert str(prog.init_kv().dtype) == "bfloat16"
    assert str(prog.init_state().dtype) == "bfloat16"
    assert str(model.params["head"].dtype) == "bfloat16"
    assert str(model.params["layers"][1]["norm_in"].dtype) == "float32"
    reqs = _requests(3, seed=9)
    _, got = _drive(prog, reqs)
    assert got == _oracle(prog, reqs)


def test_served_tokens_are_the_references_first_choice(program):
    import jax.numpy as jnp

    for prompt, n in _requests(4, seed=2):
        out = sequential_decode(program, prompt, n)[1]
        gaps = np.asarray(ref.served_gaps(
            program.model.params, jnp.asarray([prompt + out], jnp.int32),
            CFG))[0, len(prompt) - 1:]
        assert gaps.max() <= LOGIT_TOL


def test_the_reference_takes_long_sequences_a_block_at_a_time():
    """The reference's blocked attention (queries 512 at a time) is its
    whole-sequence form's: the logits of 600 positions by blocks equal
    those of one block."""
    import jax.numpy as jnp

    model = _model(max_ctx=1024)
    tokens = jnp.asarray(np.random.default_rng(5).integers(
        0, VOCAB, (1, 600)), jnp.int32)
    blocked = np.asarray(ref.logits_fn(model.params, tokens, CFG))
    saved = ref.QUERY_BLOCK
    ref.QUERY_BLOCK = 1024
    try:
        whole = np.asarray(ref.logits_fn(model.params, tokens, CFG))
    finally:
        ref.QUERY_BLOCK = saved
    np.testing.assert_allclose(blocked, whole, atol=LOGIT_TOL, rtol=0)


# ------------------------------------- the programs served before this one
def _old_rotary(x, positions, theta):
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv
    ang = jnp.reshape(ang, ang.shape[:1] + (1,) * (x.ndim - 2)
                      + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _old_project(lp, x, positions, n_heads, n_kv, theta, eps):
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.attention import merge_heads, mm, rms_norm

    u = rms_norm(x, lp["norm_in"], eps)
    n = x.shape[0]
    q = jnp.reshape(mm(u, lp["wq"]), (n, n_heads, -1))
    k = jnp.reshape(mm(u, lp["wk"]), (n, n_kv, -1))
    q = _old_rotary(rms_norm(q, lp["q_norm"], eps), positions, theta)
    k = _old_rotary(rms_norm(k, lp["k_norm"], eps), positions, theta)
    return q, (merge_heads(k), mm(u, lp["wv"]))


def _old_route(x, router_w, top_k, scale, bias=None, norm_eps=0.0):
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    if bias is None:
        top_s, top_i = jax.lax.top_k(scores, top_k)
    else:
        _, top_i = jax.lax.top_k(scores + bias, top_k)
        top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    top_w = scale * top_s
    total = jnp.sum(top_s, axis=-1, keepdims=True)
    if norm_eps:
        total = total + norm_eps
    return top_i, top_w / total


def _jaxpr(fn, *args):
    import jax

    return str(jax.make_jaxpr(fn)(*args))


def test_lfm2s_attention_traces_to_the_operations_it_had():
    """GQA with a norm a head, no gate and theta's rotation over the
    whole head: `project` and `rotary` trace to the very operations of
    the functions they were before the gate, the partial rotation and
    the optional norm came in as data."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn import gqa_attention as gqa
    from deeplearning4j_tpu.nn.latent_attention import rotary

    key = jax.random.PRNGKey(0)
    lp = {name: jax.random.normal(jax.random.fold_in(key, i), shape)
          for i, (name, shape) in enumerate(
              [("norm_in", (32,)), ("wq", (32, 64)), ("wk", (32, 16)),
               ("wv", (32, 16)), ("q_norm", (8,)), ("k_norm", (8,))])}
    x = jax.random.normal(key, (3, 32))
    pos = jnp.asarray([0, 5, 900])
    new = lambda lp, x, p: gqa.project(lp, x, p, 8, 2, 1e6, 1e-5)  # noqa
    old = lambda lp, x, p: _old_project(lp, x, p, 8, 2, 1e6, 1e-5)  # noqa
    assert _jaxpr(new, lp, x, pos) == _jaxpr(old, lp, x, pos)
    q = jax.random.normal(key, (3, 4, 8))
    assert _jaxpr(lambda a, p: rotary(a, p, 1e4), q, pos) \
        == _jaxpr(lambda a, p: _old_rotary(a, p, 1e4), q, pos)


@pytest.mark.parametrize("bias,eps", [(False, 0.0), (True, 1e-6)])
def test_sigmoid_routing_traces_to_the_operations_it_had(bias, eps):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.moe import route

    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (4, 16))
    w = jax.random.normal(jax.random.fold_in(key, 1), (16, 8))
    b = jnp.linspace(-0.1, 0.1, 8) if bias else None
    assert _jaxpr(lambda x, w: route(x, w, 2, 2.5, b, eps), x, w) \
        == _jaxpr(lambda x, w: _old_route(x, w, 2, 2.5, b, eps), x, w)


@pytest.mark.parametrize("which", ["kimi", "lfm2"])
def test_state_layers_of_the_recurrences_ignore_the_positions(which):
    """The contract hands every state layer its rows' positions; a
    recurrence's layer (Kimi Delta Attention's, the short convolution's)
    traces to the same operations whatever they are."""
    import jax
    import jax.numpy as jnp

    if which == "kimi":
        from deeplearning4j_tpu.zoo import HybridDeltaTransformer

        model = HybridDeltaTransformer(max_ctx=64).init()
    else:
        from deeplearning4j_tpu.zoo import ShortConvMoETransformer

        model = ShortConvMoETransformer(max_ctx=64).init()
    prog = DecodeProgram(model, max_slots=2, page_size=8)
    state = prog.init_state()
    lp, si = next((lp, -1 - li) for lp, li in prog._layers(model.params)
                  if li < 0)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, model.hidden))
    active = jnp.asarray([True, False])
    step = lambda x, s, p: model.state_step(  # noqa: E731
        lp, x, s, si, active, p)
    a, b = jnp.asarray([3, 9]), jnp.asarray([700, 1])
    assert _jaxpr(step, x, state, a) == _jaxpr(step, x, state, b)
    for u, w in zip(jax.tree_util.tree_leaves(step(x, state, a)),
                    jax.tree_util.tree_leaves(step(x, state, b))):
        assert np.array_equal(np.asarray(u), np.asarray(w))
    entry = jax.tree.map(lambda a: a[si, 0], state)
    t = prog.chunk_tokens
    xc = jax.random.normal(jax.random.PRNGKey(3), (t, model.hidden))
    one = model.state_chunk(lp, xc, entry, 5, jnp.arange(t))
    two = model.state_chunk(lp, xc, entry, 5, 4096 + jnp.arange(t))
    for u, w in zip(jax.tree_util.tree_leaves(one),
                    jax.tree_util.tree_leaves(two)):
        assert np.array_equal(np.asarray(u), np.asarray(w))
