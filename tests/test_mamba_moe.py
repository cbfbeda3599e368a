"""The fourth model with a per-slot state through `DecodeEngine`: Mamba-2
state-space layers (nn/mamba2.py: a matrix a head and a convolution's
tail a slot) between grouped-query attention layers with no rotation
over paged K/V rows, an expert layer with a shared expert after every
mixer, softmax routing, three multipliers on the stream, a tied head —
Granite-4.0-H's block, served by the same engine, programs and oracle
as every other model. The mixer's chunked form against its step and
the reference's sequential scan; the engine against `sequential_decode`
bitwise and both against the plain reference
(benchmark/reference/granite_hybrid.py) in LOGITS; the storage
precision. The expert layer's share and the pins of the programs of
the models served before it are in tests/test_mamba_moe_shared.py."""

import functools

import numpy as np
import pytest

from benchmark.reference import granite_hybrid as ref
from deeplearning4j_tpu.engine.decode_program import (
    SCRATCH_PAGE,
    DecodeProgram,
)
from deeplearning4j_tpu.nn import mamba2
from deeplearning4j_tpu.serving.continuous import (
    DecodeEngine,
    sequential_decode,
)
from deeplearning4j_tpu.zoo import MambaMoETransformer

pytestmark = pytest.mark.serving

VOCAB, CTX, SLOTS, PAGE = 64, 256, 3, 8
LAYERS = ["mamba", "mamba", "attention", "mamba"]
# the reference's view of the toy model below: hidden 64, 4 SSD heads of
# 16 (expand 1), a state of 16, 4 taps; 4 query heads on 2 K/V heads of 16
CFG = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=16, shared_intermediate_size=32,
    num_experts_per_tok=3, num_hidden_layers=4, vocab_size=VOCAB,
    router_experts=16, experts_held=[0, 1, 2, 3], layer_types=LAYERS,
    mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16, mamba_d_conv=4,
    mamba_expand=1, mamba_n_groups=1, rms_norm_eps=1e-5,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    logits_scaling=0.5, attention_multiplier=0.125)
# float32 on the CPU: program and reference differ by the order of
# their sums alone (logits of order one: 1e-4 is a thousand ulps)
LOGIT_TOL = 1e-4
REF_LEN = 200       # the reference's sequence length in this file


def _model(max_ctx=CTX, **kw):
    return MambaMoETransformer(
        layer_kinds=[ref.KINDS[k] for k in LAYERS], n_kv_heads=2,
        head_dim=16, ssm_heads=4, ssm_head_dim=16, ssm_state=16,
        conv_taps=4, embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=0.5, attention_multiplier=0.125, vocab_size=VOCAB,
        hidden=64, n_heads=4, moe_ff=16, n_experts=16, top_k=3,
        experts_held=[0, 1, 2, 3], n_shared=2, max_ctx=max_ctx, eps=1e-5,
        seed=5, **kw).init()


@pytest.fixture(scope="module")
def program():
    """256 positions in pages of 8: chunks of 128 rows, so a prompt of
    more than 128 tokens takes two chunks, the second from the state
    the first left."""
    prog = DecodeProgram(_model(), max_slots=SLOTS, page_size=PAGE)
    prog.warmup(prog.init_kv())
    assert (prog.chunk_tokens, prog.widths) == (128, (32,))
    return prog


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


def test_the_model_describes_a_state_beside_a_pool_of_its_attention_layer(
        program):
    model = program.model
    assert program.has_state
    assert model.mix_kind == ("state", "state", "pages", "state")
    # the pool holds the attention layer's K and V rows of 2 x 16; the
    # state a matrix of 16 x 16 a head, the four heads side by side in
    # one row of 64 lanes, and 3 rows of 64 + 32 channels
    assert program.kv_shape == (1, 2, SLOTS * CTX // PAGE + 1, PAGE, 32)
    state = program.init_state()
    assert {k: v.shape for k, v in state.items()} == {
        "s": (3, SLOTS, 1, 16, 64), "tail": (3, SLOTS, 3, 96)}
    assert {str(v.dtype) for v in state.values()} == {"float32"}
    shapes = ref.param_shapes(CFG)
    assert set(model.params) == {"tok_emb", "final_norm", "layers"}
    for lp, want in zip(model.params["layers"], shapes["layers"]):
        assert {k: tuple(v.shape) for k, v in lp.items()} \
            == {k: tuple(v) for k, v in want.items()}
    # every layer an expert layer with the shared expert, no bias
    assert all("sg" in lp and "router_bias" not in lp
               for lp in model.params["layers"])
    # the Mamba-2 vectors in their own ranges
    lp = model.params["layers"][0]
    a = np.exp(np.asarray(lp["A_log"]))
    step = np.log1p(np.exp(np.asarray(lp["dt_bias"])))
    assert np.all((a >= 1) & (a <= 16)) and np.all(
        (step >= 1e-3 * 0.999) & (step <= 0.1 * 1.001))


# ------------------------------------------------------- the mixer alone
def _mixer_case(seed=0, t=12, prefix=5):
    import jax

    model = _model()
    lp = model.params["layers"][0]
    x = 2.0 * jax.random.normal(jax.random.PRNGKey(seed),
                                (prefix + t, model.hidden))
    return model, lp, x


@functools.lru_cache(maxsize=None)
def _jitted(name):
    """One compile a function and shape for the whole file: the
    mixer's two forms, the reference's mixer and its forward pass (by
    `control`)."""
    import jax

    if name == "chunk_mix":
        return jax.jit(mamba2.chunk_mix, static_argnums=(4, 5, 6))
    if name == "decode_mix":
        return jax.jit(mamba2.decode_mix, static_argnums=(3, 5, 6, 7))
    if name == "mamba_mix":
        return jax.jit(lambda lp, u: ref.mamba_mix(lp, u, CFG))
    control = None if name == "logits" else name
    return jax.jit(lambda p, t: ref.logits_fn(p, t, CFG, control))


def _reference_out(model, lp, x):
    u = ref._rms(x, lp["norm_in"], model.eps)[None]
    return np.asarray(_jitted("mamba_mix")(lp, u))[0]


def _entry(model):
    import jax.numpy as jnp

    shapes = mamba2.state_shapes(1, 1, 4, 16, 16, 4)
    return {k: jnp.zeros(v[2:], jnp.float32) for k, v in shapes.items()}


@pytest.mark.parametrize("split", [1, 3, "whole"])
@pytest.mark.parametrize("carried", [False, True])
def test_the_chunked_form_is_the_step_and_the_sequential_scan(split,
                                                               carried):
    """A sequence through `chunk_mix` in chunks of 1, 3 or all of it,
    from a zero state or from the state a first chunk of 5 tokens left,
    against the one-token `decode_mix` a row at a time and the
    reference's sequential scan over the whole sequence."""
    import jax.numpy as jnp

    model, lp, x = _mixer_case()
    prefix = 5 if carried else 0
    x = x if carried else x[5:]
    want = _reference_out(model, lp, x)
    entry = _entry(model)
    got = []
    bounds = [0, prefix] if carried else [0]
    size = len(x) - prefix if split == "whole" else split
    bounds += list(range(bounds[-1] + size, len(x), size)) + [len(x)]
    bounds = sorted(set(bounds))
    for a, b in zip(bounds, bounds[1:]):
        out, entry = _jitted("chunk_mix")(lp, x[a:b], entry, b - a, 4,
                                          16, model.eps)
        got.append(np.asarray(out))
    np.testing.assert_allclose(np.concatenate(got), want, atol=1e-4,
                               rtol=1e-4)
    state = {k: v[None, None] for k, v in _entry(model).items()}
    steps = []
    for r in range(len(x)):
        out, state = _jitted("decode_mix")(lp, x[r:r + 1], state, 0,
                                           jnp.asarray([True]), 4, 16,
                                           model.eps)
        steps.append(np.asarray(out))
    np.testing.assert_allclose(np.concatenate(steps), want, atol=1e-4,
                               rtol=1e-4)
    # both forms leave the same state behind
    for k in ("s", "tail"):
        np.testing.assert_allclose(np.asarray(state[k][0, 0]),
                                   np.asarray(entry[k]), atol=1e-5,
                                   rtol=1e-5)
    assert float(np.std(want)) > 0.1


def test_a_chunk_absorbs_its_first_rows_alone_and_a_masked_row_none():
    """`n_state` of a padded chunk: the entry after the chunk is the one
    after its first rows, whatever the rows past them hold; a step row
    `active` does not mark keeps its entry."""
    import jax
    import jax.numpy as jnp

    # the lengths the chunked form's cases compiled: 5 rows and 12
    model, lp, x = _mixer_case(seed=3, t=12, prefix=0)
    chunk = _jitted("chunk_mix")
    _, short = chunk(lp, x[:5], _entry(model), 5, 4, 16, model.eps)
    noisy = x.at[5:].set(jax.random.normal(jax.random.PRNGKey(9),
                                           (7, model.hidden)) * 50.0)
    _, padded = chunk(lp, noisy, _entry(model), 5, 4, 16, model.eps)
    for k in ("s", "tail"):
        np.testing.assert_allclose(np.asarray(padded[k]),
                                   np.asarray(short[k]), atol=1e-6)
    state = {k: jnp.stack([v, v + 1.0])[None]
             for k, v in short.items()}
    _, after = _jitted("decode_mix")(lp, x[:2], state, 0,
                                     jnp.asarray([False, True]), 4, 16,
                                     model.eps)
    for k in ("s", "tail"):
        np.testing.assert_array_equal(np.asarray(after[k][0, 0]),
                                      np.asarray(state[k][0, 0]))
        assert not np.array_equal(np.asarray(after[k][0, 1]),
                                  np.asarray(state[k][0, 1]))


@pytest.mark.parametrize("budget", [None, 64 * 1024])
def test_the_kernel_is_the_update_and_the_read_and_keeps_inactive_rows(
        budget, monkeypatch):
    """`ssd_step` on rows of heads against the recurrence written out in
    numpy: the layer it is given advances, every other layer and every
    inactive row's matrices stay bit for bit, whether a grid step holds
    every row of heads or (a small budget) one at a time."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.helpers import pallas_ssd

    if budget:
        monkeypatch.setattr(pallas_ssd, "_BLOCK_BUDGET", budget)
    rng = np.random.default_rng(7)
    layers, slots, groups, n, w = 2, 3, 4, 16, 128
    f = lambda *shape: rng.normal(size=shape).astype(np.float32)  # noqa: E731
    state, dx, b, c = f(layers, slots, groups, n, w), f(slots, groups, w), \
        f(slots, n), f(slots, n)
    decay = rng.uniform(0.5, 1.0, (slots, groups, w)).astype(np.float32)
    active = np.asarray([True, False, True])
    y, after = pallas_ssd.ssd_step(jnp.asarray(state), 1, jnp.asarray(decay),
                                   jnp.asarray(dx), jnp.asarray(b),
                                   jnp.asarray(c), jnp.asarray(active))
    new = decay[:, :, None] * state[1] + b[:, None, :, None] * dx[:, :, None]
    np.testing.assert_allclose(np.asarray(y),
                               np.sum(new * c[:, None, :, None], axis=2),
                               rtol=1e-5, atol=1e-5)
    after = np.asarray(after)
    np.testing.assert_allclose(after[1, active], new[active], rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_array_equal(after[1, ~active], state[1, ~active])
    np.testing.assert_array_equal(after[0], state[0])


def test_rows_of_heads_turn_to_heads_and_back():
    """Two heads of 64 a row of 128 lanes at the published widths; the
    turn is a permutation and its own inverse's."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.helpers.pallas_ssd import heads_per_row

    assert heads_per_row(128, 64) == 2 and heads_per_row(4, 16) == 4
    assert heads_per_row(6, 16) == 2 and heads_per_row(3, 64) == 1
    assert mamba2.state_shapes(9, 96, 128, 64, 128, 4) == {
        "s": (9, 96, 64, 128, 128), "tail": (9, 96, 3, 8448)}
    s = jnp.arange(4 * 16 * 8, dtype=jnp.float32).reshape(4, 16, 8)
    rows = mamba2._to_rows(s, 2)
    assert rows.shape == (2, 8, 32)
    # head 1's channel p of state n is row 0, sublane n, lane 16 + p
    assert float(rows[0, 3, 16 + 5]) == float(s[1, 5, 3])
    np.testing.assert_array_equal(np.asarray(mamba2._to_heads(rows, 16)),
                                  np.asarray(s))


# -------------------------------------------------------- through the pool
def paged_logits(prog, tokens, n_prompt):
    """Logits of positions n_prompt-1 .. len(tokens)-2 of one sequence
    through the pool and the state: the prompt by the compiled chunk
    program (the state told to absorb all but its last token), then one
    position at a time by the model's own layer functions in the decode
    step's order, teacher-forced."""
    import jax.numpy as jnp

    m, ps = prog.model, prog.page_size
    table = list(range(1, prog.pages_per_slot + 1))
    kv, state = prog.init_kv(), prog.init_state()
    for start in prog.chunk_starts(n_prompt):
        pages = prog.block_pages(n_prompt, start)
        kv, state = prog.prefill_chunk(
            kv, tokens[start:start + prog.chunk_tokens], start,
            prog.window_pages(table, start - 1),
            table[pages.start:pages.stop],
            state=state, slot=0, n_state=prog.state_rows(n_prompt, start))
    step = _teacher_step(prog)
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


@functools.lru_cache(maxsize=None)
def _teacher_step(prog):
    """One decode position by the model's own layer functions in the
    decode step's order, compiled once a program."""
    import jax
    import jax.numpy as jnp

    m = prog.model

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

    return step


@pytest.mark.parametrize("n_prompt", [1, 2, PAGE + 1, 128, 129, 150])
def test_prefill_then_decode_match_the_reference_in_logits(program,
                                                           n_prompt):
    """Prefill by chunks of 128 rows (one or two: the second from the
    state the first left), then decoding through the pool and the
    state, against the reference's full forward pass: logits."""
    import jax.numpy as jnp

    tokens = np.random.default_rng(n_prompt).integers(
        0, VOCAB, n_prompt + 10).tolist()
    got = paged_logits(program, tokens, n_prompt)
    # one length for every case (a causal forward: the padding's tokens
    # come after every position compared), so one compile
    padded = np.zeros((1, REF_LEN), np.int32)
    padded[0, :len(tokens)] = tokens
    want = np.asarray(_jitted("logits")(program.model.params,
                                        jnp.asarray(padded)))[0]
    want = want[n_prompt - 1:len(tokens) - 1]
    assert float(np.std(want)) > 0.1
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


def test_engine_matches_the_oracle_bitwise_under_churn(program):
    """Staggered joins and leaves over 3 slots: every request's stream
    is its solo decode's, so no operation mixes slots' states; the
    state's counters count as for any state."""
    reqs = _requests(10, seed=1)
    eng, got = _drive(program, reqs)
    assert got == _oracle(program, reqs)
    st = eng.stats()
    assert st["completed"] == len(reqs)
    assert st["state_resets"] == len(reqs)
    assert st["state_bytes"] == 4 * 3 * SLOTS * (4 * 16 * 16 + 3 * 96)


def test_a_chunk_at_zero_resets_a_poisoned_state(program):
    """The reset is a select, not a product: a slot whose state is NaN
    decodes the oracle's stream after a chunk at position 0."""
    import jax
    import jax.numpy as jnp

    eng = DecodeEngine(program=program)
    eng.state = jax.tree.map(lambda a: jnp.full_like(a, jnp.nan), eng.state)
    prompt = list(range(1, 30))
    h = eng.submit(prompt, 6)
    while not h.done:
        eng.step_once()
    assert h.result(timeout_s=0) == sequential_decode(program, prompt, 6)[1]
    assert bool(jnp.all(jnp.isnan(eng.state["s"][:, 1:])))


def test_no_compile_after_warmup_and_the_trie_is_off(program):
    before = program.model._jit_cache.trace_counts()
    reqs = [(list(range(3, 3 + 4 * PAGE)) + [i], 4) for i in range(4)]
    eng, got = _drive(program, reqs, stagger=1, prefix_cache=True)
    assert got == _oracle(program, reqs)
    assert program.model._jit_cache.trace_counts() == before
    assert eng.stats()["prefix_cache"] is False


def test_bfloat16_storage_keeps_the_state_in_float32():
    """`param_dtype="bfloat16"`: matrices, taps, embedding and the K/V
    pool are bfloat16, gains and the Mamba-2 vectors float32, the state
    float32, and the engine still equals its oracle bitwise."""
    model = _model(max_ctx=64, param_dtype="bfloat16")
    prog = DecodeProgram(model, max_slots=2, page_size=PAGE)
    assert str(prog.init_kv().dtype) == "bfloat16"
    assert {str(v.dtype) for v in prog.init_state().values()} \
        == {"float32"}
    lp = model.params["layers"][0]
    assert str(lp["w_in"].dtype) == "bfloat16"
    assert str(lp["A_log"].dtype) == str(lp["dt_bias"].dtype) == "float32"
    reqs = _requests(3, seed=9)
    _, got = _drive(prog, reqs)
    assert got == _oracle(prog, reqs)


def test_a_bfloat16_state_forgets_what_the_float32_one_keeps():
    """The reason the state is float32: a slow head's decay of 0.999 a
    token rounds back to 1 in bfloat16, and the reference run with the
    state alone in bfloat16 drifts from the float32 one over a few
    hundred tokens by far more than float32's rounding."""
    import jax.numpy as jnp

    model = _model()
    tokens = jnp.asarray(np.random.default_rng(4).integers(
        0, VOCAB, (1, REF_LEN)), jnp.int32)
    f32 = np.asarray(_jitted("logits")(model.params, tokens))
    bf = np.asarray(_jitted("state_bfloat16")(model.params, tokens))
    assert np.max(np.abs(bf - f32)) > 10 * LOGIT_TOL
    assert float(jnp.asarray(0.999, jnp.bfloat16)) == 1.0
