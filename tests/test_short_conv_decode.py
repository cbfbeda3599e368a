"""The second model with a per-slot state through `DecodeEngine`: gated
short-convolution layers (nn/short_conv.py) between grouped-query
attention layers over paged K/V rows (nn/gqa_attention.py), an expert
layer with every expert held and no shared expert, a tied head —
served by the same engine, programs and oracle as every other model.
The engine against `sequential_decode` bitwise, both against the plain
reference's full forward pass in LOGITS, and the rules a state forces
on the engine, which this state (two rows a layer a slot, no recurrence
matrix, a state layer in the leading dense position) keeps as Kimi
Delta Attention's does."""

import numpy as np
import pytest

from benchmark.reference import lfm2_moe as ref
from deeplearning4j_tpu.engine.decode_program import (
    SCRATCH_PAGE,
    DecodeProgram,
)
from deeplearning4j_tpu.resilience.faults import injector
from deeplearning4j_tpu.serving.continuous import (
    DecodeEngine,
    sequential_decode,
)
from deeplearning4j_tpu.zoo import ShortConvMoETransformer

pytestmark = pytest.mark.serving

VOCAB, CTX, SLOTS, PAGE = 64, 64, 3, 8
KINDS = ("conv", "attn", "conv", "conv", "conv", "attn", "conv")
# the reference's view of the toy model below
CFG = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=64, moe_intermediate_size=16, num_experts_per_tok=2,
    num_hidden_layers=7, num_dense_layers=1, vocab_size=VOCAB,
    conv_L_cache=3, router_experts=8, experts_held=list(range(8)),
    layer_types=["conv" if k == "conv" else "full_attention"
                 for k in KINDS],
    rope_parameters={"rope_theta": 1e6}, norm_eps=1e-5,
    route_norm_eps=1e-6, routed_scaling_factor=1.0)
# float32 on the CPU: program and reference differ by the order of
# their sums alone (logits of order one: 1e-4 is a thousand ulps)
LOGIT_TOL = 1e-4


def _model(max_ctx=CTX, **kw):
    return ShortConvMoETransformer(
        layer_kinds=KINDS, n_kv_heads=2, head_dim=8, vocab_size=VOCAB,
        hidden=32, n_heads=4, dense_ff=64, moe_ff=16, n_experts=8, top_k=2,
        max_ctx=max_ctx, seed=5, **kw).init()


@pytest.fixture(scope="module")
def program():
    prog = DecodeProgram(_model(), max_slots=SLOTS, page_size=PAGE)
    prog.warmup(prog.init_kv())
    return prog


@pytest.fixture(scope="module")
def blocks():
    """A window of two chunks: 256 positions in pages of 8, so a chunk
    is 16 pages (128 tokens) and a prompt past 128 has a second chunk
    with the first one's pages as its prior window, as on the chip.
    `program`'s window is one chunk."""
    prog = DecodeProgram(_model(max_ctx=256), max_slots=SLOTS,
                         page_size=PAGE)
    assert (prog.chunk_pages, prog.chunk_tokens) == (16, 128)
    prog.warmup(prog.init_kv())
    return prog


def _requests(n, seed, max_prompt=26, max_new=12):
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
    through the pool and the tails: the prompt by the compiled chunk
    program (the tails told to absorb all but its last token), then one
    position at a time by the model's own layer functions in the decode
    step's order, teacher-forced, with the logits kept where the
    compiled step keeps their argmax."""
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


def test_the_model_describes_tails_beside_a_pool_of_its_attention_layers(
        program):
    model = program.model
    assert program.has_state
    assert model.mix_kind == ("state", "pages", "state", "state", "state",
                              "pages", "state")
    # the pool holds the two attention layers' K and V rows of 2 x 8;
    # the state the five others' two rows of `B * z`
    assert program.kv_shape == (2, 2, SLOTS * CTX // PAGE + 1, PAGE, 16)
    assert model.kv_page_axis == 2 and model.n_page_layers == 2
    state = program.init_state()
    assert state.shape == (5, SLOTS, 2, 32)
    assert str(state.dtype) == "float32"
    shapes = ref.param_shapes(CFG)
    assert set(model.params) == {"tok_emb", "final_norm", "layers"}
    for lp, want in zip(model.params["layers"], shapes["layers"]):
        assert {k: tuple(v.shape) for k, v in lp.items()} \
            == {k: tuple(v) for k, v in want.items()}
    # no shared expert anywhere, a selection bias on every router
    assert not any("sg" in lp for lp in model.params["layers"])
    assert sum("router_bias" in lp for lp in model.params["layers"]) == 6


@pytest.mark.parametrize("n_prompt", [1, 2, 3, PAGE - 1, PAGE, PAGE + 1,
                                      2 * PAGE + 1, 23])
def test_prefill_then_decode_match_the_reference_in_logits(program,
                                                           n_prompt):
    """Prefill by chunks, then decoding through the pool and the tails,
    against the reference's full forward pass: logits, at every
    alignment of the prompt's end to a page (a prompt of one token runs
    no state rows in its chunk), over decodes long enough to cross a
    page."""
    import jax.numpy as jnp

    tokens = np.random.default_rng(n_prompt).integers(
        0, VOCAB, n_prompt + 12).tolist()
    got = paged_logits(program, tokens, n_prompt)
    want = np.asarray(ref.logits_fn(
        program.model.params, jnp.asarray([tokens]), CFG))[0]
    want = want[n_prompt - 1:len(tokens) - 1]
    assert float(np.std(want)) > 0.1
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("n_prompt", [127, 128, 129, 200])
def test_chunks_of_several_pages_absorb_each_prompt_row_once(blocks,
                                                             n_prompt):
    """A prompt that ends inside its first chunk of 16 pages, at its
    edge, one token past it and well into the second: the tails absorb
    every prompt token but the last exactly once (the logits are the
    reference's, which a row absorbed twice or a pad row absorbed once
    would leave), the second chunk attends the first one's pages, and
    the engine is the oracle's bitwise."""
    import jax.numpy as jnp

    tokens = np.random.default_rng(n_prompt).integers(
        0, VOCAB, n_prompt + 6).tolist()
    got = paged_logits(blocks, tokens, n_prompt)
    want = np.asarray(ref.logits_fn(
        blocks.model.params, jnp.asarray([tokens]), CFG))[0]
    np.testing.assert_allclose(got, want[n_prompt - 1:len(tokens) - 1],
                               atol=LOGIT_TOL, rtol=0)
    prompt = tokens[:n_prompt]
    traces = dict(blocks.trace_stats()["trace_counts"])
    eng, (out,) = _drive(blocks, [(prompt, 6)])
    assert out == sequential_decode(blocks, prompt, 6)[1]
    st = eng.stats()
    assert st["trace_counts"] == traces and set(traces.values()) == {1}
    assert st["prefill_chunks"] == -(-n_prompt // 128)
    assert st["prefill_pages"] == -(-n_prompt // PAGE)
    assert st["prefill_pages"] * PAGE + st["prefill_rows_padded"] \
        == st["prefill_chunks"] * 128
    assert st["state_resets"] == 1
    assert st["state_rows"] == n_prompt - 1 + 6


def test_a_wrong_tail_would_show(program):
    """The witness for the test above: with the tails' update left out
    of the decode steps (the state the chunks left, never advanced) the
    logits leave the reference's by far more than the tolerance."""
    import jax.numpy as jnp

    tokens = np.random.default_rng(0).integers(0, VOCAB, 21).tolist()
    want = np.asarray(ref.logits_fn(
        program.model.params, jnp.asarray([tokens]), CFG))[0][8:20]
    good = paged_logits(program, tokens, 9)
    np.testing.assert_allclose(good, want, atol=LOGIT_TOL, rtol=0)
    model = program.model
    real = model.state_step

    def frozen(lp, x, state, si, active, positions):
        x, _, counts = real(lp, x, state, si, active, positions)
        return x, state, counts

    model.state_step = frozen
    try:
        bad = paged_logits(program, tokens, 9)
    finally:
        del model.state_step
    assert float(np.max(np.abs(bad - want))) > 100 * LOGIT_TOL


def test_engine_matches_the_oracle_bitwise_under_churn(program):
    """Staggered joins and leaves over 3 slots: every request's stream
    is its solo decode's, so no operation mixes slots' tails; the
    state's counters count as for any state."""
    reqs = _requests(12, seed=1)
    oracle = _oracle(program, reqs)
    eng, got = _drive(program, reqs)
    assert got == oracle
    st = eng.stats()
    assert st["completed"] == len(reqs)
    # each placement began its slot's tails anew, once
    assert st["state_resets"] == len(reqs)
    chunk_rows = sum(len(p) - 1 for p, _ in reqs)
    assert st["state_rows"] == chunk_rows + st["tokens_total"]
    assert st["state_bytes"] == 4 * 5 * SLOTS * 2 * 32
    # every expert is held: every routed pair falls on a held expert
    assert st["moe_assignments"] == st["moe_assignments_held"] > 0


def test_served_tokens_are_the_references_first_choice(program):
    import jax.numpy as jnp

    for prompt, n in _requests(4, seed=2):
        out = sequential_decode(program, prompt, n)[1]
        gaps = np.asarray(ref.served_gaps(
            program.model.params, jnp.asarray([prompt + out], jnp.int32),
            CFG))[0, len(prompt) - 1:]
        assert gaps.max() <= LOGIT_TOL


@pytest.mark.chaos
def test_eviction_replay_gives_the_same_stream(program):
    """A forced eviction re-prefills from token 0 (the chunk at 0
    starts the new slot's tails from zero) and force-feeds the emitted
    stream through the decode step, which advances the tails over it:
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


def test_a_chunk_at_zero_resets_a_poisoned_tail(program):
    """The reset is a select, not a product: a slot whose tails are NaN
    decodes the oracle's stream after a chunk at position 0."""
    import jax.numpy as jnp

    eng = DecodeEngine(program=program)
    eng.state = jnp.full_like(eng.state, jnp.nan)
    prompt = list(range(1, 12))
    h = eng.submit(prompt, 6)
    while not h.done:
        eng.step_once()
    assert h.result(timeout_s=0) == sequential_decode(program, prompt, 6)[1]
    # the slots no request touched still hold what they held
    assert bool(jnp.all(jnp.isnan(eng.state[:, 1:])))


def test_the_trie_is_off_whatever_prefix_cache_says(program):
    """A cached page would bring a prefix's K and V rows back without
    the tails at its end: no trie is built, shared prefixes are filled
    page for page, and the streams are the oracle's."""
    shared = list(range(3, 3 + 2 * PAGE))
    reqs = [(shared + [7, 8, i], 5) for i in range(4)]
    eng, got = _drive(program, reqs, prefix_cache=True)
    assert got == _oracle(program, reqs)
    st = eng.stats()
    assert st["prefix_cache"] is False
    assert st["prefix_hits"] == 0 and st["trie_blocks"] == 0
    assert st["cow_copies"] == 0
    assert st["prefill_chunks"] == 4 and st["prefill_pages"] == 4 * 3
    assert st["state_resets"] == 4


def test_ring_wrap_rotates_on_and_leaves_the_tails_whole(program):
    """Past `max_ctx` the attention layers' window slides (the ring
    recycles the slot's oldest page) and the rotation takes the logical
    position, which grows on; the tails have no window. Engine and
    oracle agree bitwise through the wrap."""
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


def test_bfloat16_storage_keeps_the_pool_and_the_head_in_bfloat16():
    """`param_dtype="bfloat16"`: matrices, embedding (the tied head) and
    the K/V pool are bfloat16, gains and the tails float32, and the
    engine still equals its oracle bitwise."""
    model = _model(param_dtype="bfloat16")
    prog = DecodeProgram(model, max_slots=2, page_size=PAGE)
    assert str(prog.init_kv().dtype) == "bfloat16"
    assert str(prog.init_state().dtype) == "float32"
    assert str(model.params["tok_emb"].dtype) == "bfloat16"
    assert str(model.params["layers"][1]["q_norm"].dtype) == "float32"
    reqs = _requests(3, seed=9)
    _, got = _drive(prog, reqs)
    assert got == _oracle(prog, reqs)
