"""A model with a per-slot state through `DecodeEngine`: linear-
attention layers (nn/delta_attention.py) between latent-attention
layers, served by the same engine, programs and oracle as every other
model. The engine against `sequential_decode` bitwise, both against
the plain reference's full forward pass, and the rules a state forces
on the engine: the last prompt token, the reset, the trie."""

import numpy as np
import pytest

from benchmark.reference import kimi_linear as ref
from deeplearning4j_tpu.engine.decode_program import DecodeProgram
from deeplearning4j_tpu.resilience.faults import injector
from deeplearning4j_tpu.serving.continuous import (
    DecodeEngine,
    sequential_decode,
)
from deeplearning4j_tpu.zoo.hybrid_delta import HybridDeltaTransformer

pytestmark = pytest.mark.serving

VOCAB, CTX, SLOTS, PAGE = 64, 64, 3, 8
KINDS = ("kda", "kda", "kda", "mla", "kda", "mla")
# the reference's view of the toy model below
CFG = dict(
    hidden_size=32, num_attention_heads=2, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    intermediate_size=64, moe_intermediate_size=16,
    num_experts_per_token=2, num_shared_experts=1, num_hidden_layers=6,
    first_k_dense_replace=1, vocab_size=VOCAB, experts_held=[0, 1, 2, 5],
    router_experts=8, routed_scaling_factor=2.446, rms_norm_eps=1e-5,
    linear_attn_config=dict(num_heads=2, head_dim=8,
                            short_conv_kernel_size=4,
                            kda_layers=[1, 2, 3, 5],
                            full_attn_layers=[4, 6]))
# float32 on the CPU: program and reference differ by the order of
# their sums alone, so a served token is the reference's first choice
# or within rounding of it (logits of order one: 1e-4 is a thousand
# ulps; a near-tie closer than that would read as a gap under it)
GAP_TOL = 1e-4


def _model(max_ctx=CTX, **kw):
    return HybridDeltaTransformer(
        layer_kinds=KINDS, vocab_size=VOCAB, hidden=32, n_heads=2,
        kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8,
        dense_ff=64, moe_ff=16, n_experts=8, top_k=2,
        experts_held=(0, 1, 2, 5), max_ctx=max_ctx, kda_heads=2,
        kda_head_dim=8, gate_rank=8, routed_scale=2.446, seed=5,
        **kw).init()


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


def _gaps(program, prompt, out):
    import jax.numpy as jnp

    tokens = jnp.asarray([prompt + out], jnp.int32)
    gaps = ref.served_gaps(program.model.params, tokens, CFG)
    return np.asarray(gaps)[0, len(prompt) - 1:]


def test_the_model_describes_a_state_beside_a_pool_of_its_page_layers(
        program):
    model = program.model
    assert program.has_state
    assert model.mix_kind == ("state",) * 3 + ("pages", "state", "pages")
    # the pool holds the two latent layers only; the state the four others
    assert program.kv_shape == (2, SLOTS * CTX // PAGE + 1, PAGE, 20)
    state = program.init_state()
    assert state["s"].shape == (4, SLOTS, 2, 8, 8)
    assert state["tail"].shape == (4, SLOTS, 3, 3 * 16)
    assert str(state["s"].dtype) == "float32"
    shapes = ref.param_shapes(CFG)
    for lp, want in zip(model.params["layers"], shapes["layers"]):
        assert {k: tuple(v.shape) for k, v in lp.items()} \
            == {k: tuple(v) for k, v in want.items()}


def test_engine_matches_the_oracle_bitwise_under_churn(program):
    """Staggered joins and leaves over 3 slots: every request's stream
    is its solo decode's, so no operation mixes slots' states."""
    reqs = _requests(12, seed=1)
    oracle = _oracle(program, reqs)
    eng, got = _drive(program, reqs)
    assert got == oracle
    st = eng.stats()
    assert st["completed"] == len(reqs)
    # each placement began its slot's state anew, once
    assert st["state_resets"] == len(reqs)
    chunk_rows = sum(len(p) - 1 for p, _ in reqs)
    assert st["state_rows"] == chunk_rows + st["tokens_total"]
    assert st["state_bytes"] == 4 * (4 * SLOTS * (2 * 8 * 8 + 3 * 48))


@pytest.mark.parametrize("n_prompt", [1, PAGE - 1, PAGE, PAGE + 1,
                                      2 * PAGE, 2 * PAGE + 1, 23])
def test_prefill_then_decode_is_the_reference_forward_pass(program,
                                                           n_prompt):
    """The last-token rule: a prompt's chunks absorb all but its last
    token, the first-token step absorbs that one, at every alignment
    of the prompt's end to a page (a prompt of one token runs no state
    rows in its chunk). Every served token is the reference's first
    choice to `GAP_TOL`, over decodes long enough to cross a page."""
    prompt = np.random.default_rng(n_prompt).integers(
        0, VOCAB, n_prompt).tolist()
    eng, (out,) = _drive(program, [(prompt, 12)])
    assert out == sequential_decode(program, prompt, 12)[1]
    assert _gaps(program, prompt, out).max() <= GAP_TOL
    assert eng.stats()["state_rows"] == n_prompt - 1 + 12


@pytest.mark.parametrize("n_prompt", [127, 128, 129, 200])
def test_chunks_of_several_pages_absorb_each_prompt_row_once(blocks,
                                                             n_prompt):
    """A prompt that ends inside its first chunk of 16 pages, at its
    edge, one token past it and well into the second: the state
    absorbs every prompt token but the last exactly once (every served
    token is the reference's first choice, which a row absorbed twice
    or a pad row absorbed once would leave), the second chunk starts
    from the state the first left and attends its pages, and the
    engine is the oracle's bitwise."""
    prompt = np.random.default_rng(n_prompt).integers(
        0, VOCAB, n_prompt).tolist()
    traces = dict(blocks.trace_stats()["trace_counts"])
    eng, (out,) = _drive(blocks, [(prompt, 6)])
    assert out == sequential_decode(blocks, prompt, 6)[1]
    assert _gaps(blocks, prompt, out).max() <= GAP_TOL
    st = eng.stats()
    assert st["trace_counts"] == traces and set(traces.values()) == {1}
    assert st["prefill_chunks"] == -(-n_prompt // 128)
    assert st["prefill_pages"] == -(-n_prompt // PAGE)
    assert st["prefill_pages"] * PAGE + st["prefill_rows_padded"] \
        == st["prefill_chunks"] * 128
    assert st["state_resets"] == 1
    assert st["state_rows"] == n_prompt - 1 + 6


def test_a_token_absorbed_twice_would_show(program):
    """The witness for the test above: feed the oracle's first token
    step the prompt's last token with the chunks having absorbed it
    too (a prompt one token longer, cut back), and the stream leaves
    the reference's."""
    prompt = np.random.default_rng(3).integers(0, VOCAB, 11).tolist()
    _, twice = sequential_decode(program, prompt + prompt[-1:], 8)
    assert _gaps(program, prompt, twice).max() > GAP_TOL


@pytest.mark.chaos
def test_eviction_replay_gives_the_same_stream(program):
    """A forced eviction re-prefills from token 0 (the chunk at 0
    starts the new slot's state from zero) and force-feeds the emitted
    stream through the decode step, which advances the state over it:
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


@pytest.mark.chaos
def test_quarantine_replay_gives_the_same_stream(program):
    """A poison verdict retires the slot; the request replays on a
    healthy one from a zero state. The retired slot's entry is never
    read again, and would not survive a chunk at 0 if it were."""
    reqs = _requests(6, seed=6)
    oracle = _oracle(program, reqs)
    injector().inject("decode.nonfinite", mode="raise", at_hit=5, times=1)
    eng, got = _drive(program, reqs)
    assert got == oracle
    st = eng.stats()
    assert st["quarantines"] == 1 and st["quarantined_slots"] == 1


def test_a_chunk_at_zero_resets_a_poisoned_state(program):
    """The reset is a select, not a product: a slot whose state is NaN
    decodes the oracle's stream after a chunk at position 0."""
    import jax.numpy as jnp

    eng = DecodeEngine(program=program)
    eng.state = {k: jnp.full_like(v, jnp.nan) for k, v in eng.state.items()}
    prompt = list(range(1, 12))
    h = eng.submit(prompt, 6)
    while not h.done:
        eng.step_once()
    assert h.result(timeout_s=0) == sequential_decode(program, prompt, 6)[1]
    # the slots no request touched still hold what they held
    assert bool(jnp.all(jnp.isnan(eng.state["s"][:, 1:])))


def test_the_trie_is_off_whatever_prefix_cache_says(program):
    """A cached page would bring a prefix's rows back without the
    state at its end: no trie is built, shared prefixes are filled
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
    # a model without state keeps its trie
    from deeplearning4j_tpu.zoo.decoder import CausalTransformer

    plain = DecodeEngine(model=CausalTransformer(
        vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=1, max_ctx=CTX,
        seed=3).init(), max_slots=2, page_size=PAGE)
    assert plain.stats()["prefix_cache"] is True
    assert plain.state is None and plain.stats()["state_bytes"] == 0


def test_ring_wrap_slides_the_window_and_leaves_the_state_whole(program):
    """Past `max_ctx` the latent layers' window slides (the ring
    recycles the slot's oldest page); the state has no window. Engine
    and oracle agree bitwise through the wrap."""
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


def test_four_shares_partial_sums_add_up_to_the_uncut_expert_layer():
    """The deployment's arithmetic: four chips hold two of the eight
    routed experts each and every one the shared expert; their partial
    sums, the shared expert counted once, are the reference's expert
    layer with all eight held. The program's layer (nn/moe.py) gives
    each share."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.attention import gated_mlp
    from deeplearning4j_tpu.nn.moe import expert_layer

    cfg = dict(CFG, experts_held=list(range(8)))
    model = _model()
    lp = dict(model.params["layers"][1])
    key = jax.random.PRNGKey(0)
    full = {n: jax.random.normal(jax.random.fold_in(key, i), (8,) + s[1:])
            / np.sqrt(s[-2]) for i, (n, s) in enumerate(
                (n, lp[n].shape) for n in ("eg", "eu", "ed"))}
    x = jax.random.normal(jax.random.fold_in(key, 9), (5, 32))
    whole = ref.expert_ffn(dict(lp, **full), x, cfg)
    shared = gated_mlp(x, lp["sg"], lp["su"], lp["sd"])
    total = jnp.zeros_like(whole)
    for share in ([0, 1], [2, 3], [4, 5], [6, 7]):
        part = dict(lp, **{n: w[jnp.asarray(share)]
                           for n, w in full.items()})
        y, _ = expert_layer(part, x, tuple(share), 2, 2.446)
        np.testing.assert_allclose(
            y, ref.expert_ffn(part, x, cfg, held=share), atol=1e-5)
        total = total + y - shared
    np.testing.assert_allclose(total + shared, whole, atol=1e-5)
