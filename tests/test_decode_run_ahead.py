"""The engine's run-ahead of one step (serving/continuous.py
`step_once`): step n+1 is dispatched before step n is fetched, and
takes step n's tokens from the device (engine/decode_program.py
`step`'s `prev`/`take`).

What is pinned, for each of the three toy models the decode tests
build (a plain decoder, latent attention with sparse experts, a hybrid
with a per-slot state):
  * the streams are the sequential oracle's byte for byte under churn,
    whether a request ends by `eos_id` mid-stream (nothing after the
    EOS; the overrun row is counted) or by `max_new_tokens` (no row
    overruns: the host knew at dispatch);
  * a cancel, a deadline, a forced eviction and a poison verdict that
    land while a step is in flight: the row of that step is credited
    to nobody placed on the slot since, and the old stream is whole;
  * the order itself, which a CPU cannot time: a program whose outputs
    record when they are first read;
  * the drain, and `stop()` / a restart with a step in flight.
"""

import time

import numpy as np
import pytest

from deeplearning4j_tpu.engine.decode_program import DecodeProgram
from deeplearning4j_tpu.resilience.errors import ShutdownError
from deeplearning4j_tpu.resilience.faults import injector
from deeplearning4j_tpu.serving.continuous import (
    DecodeEngine,
    sequential_decode,
)

pytestmark = pytest.mark.serving

VOCAB, CTX, SLOTS, PAGE = 64, 64, 4, 8
MODELS = ("decoder", "latent_moe", "hybrid_delta")


def _build(kind):
    if kind == "decoder":
        from deeplearning4j_tpu.zoo.decoder import CausalTransformer

        return CausalTransformer(vocab_size=VOCAB, d_model=32, n_heads=4,
                                 n_layers=2, max_ctx=CTX, seed=3)
    latent = dict(vocab_size=VOCAB, n_heads=2, kv_lora_rank=16,
                  qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8, dense_ff=64,
                  moe_ff=16, n_experts=8, top_k=2,
                  experts_held=(0, 1, 2, 5), max_ctx=CTX, seed=5)
    if kind == "latent_moe":
        from deeplearning4j_tpu.zoo.latent_moe import LatentMoETransformer

        return LatentMoETransformer(
            hidden=32, q_lora_rank=12, n_shared=1, routed_scale=2.5,
            n_dense_layers=1, n_moe_layers=2, rope_theta=10000.0, **latent)
    from deeplearning4j_tpu.zoo.hybrid_delta import HybridDeltaTransformer

    return HybridDeltaTransformer(
        layer_kinds=("kda", "kda", "mla", "kda"), hidden=32, kda_heads=2,
        kda_head_dim=8, gate_rank=8, routed_scale=2.446, **latent)


@pytest.fixture(scope="module", params=MODELS)
def program(request):
    prog = DecodeProgram(_build(request.param).init(), max_slots=SLOTS,
                         page_size=PAGE)
    prog.warmup(prog.init_kv())
    return prog


def _requests(n, seed, max_prompt=20, max_new=12):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, VOCAB, int(rng.integers(2, max_prompt))).tolist(),
             int(rng.integers(4, max_new))) for _ in range(n)]


def _oracle(program, prompt, n, eos=None):
    return sequential_decode(program, prompt, n, eos_id=eos)[1]


def _dispatched(program) -> int:
    """Decode steps the program has run so far, whoever asked."""
    return program.trace_stats()["dispatches"]["step"]


def _finish(eng, handles):
    """Step until every stream is done and nothing is in flight."""
    calls = 0
    while any(not h.done for h in handles) or eng._inflight is not None:
        eng.step_once()
        calls += 1
        assert calls < 3000, "engine made no progress"


def _drive(eng, reqs, stagger=2):
    """Submit `(prompt, max_new, eos)` one every `stagger` calls, then
    step to the end."""
    handles = []
    for prompt, n, eos in reqs:
        handles.append(eng.submit(prompt, n, eos_id=eos))
        for _ in range(stagger):
            eng.step_once()
    _finish(eng, handles)
    return handles


def _until_in_flight(eng, handle, tokens):
    """Step until `handle` has `tokens` tokens and a step is in flight
    with its next one."""
    calls = 0
    while len(handle.tokens_so_far()) < tokens or eng._inflight is None:
        eng.step_once()
        calls += 1
        assert calls < 200
    assert not handle.done


# ====================================================== (a) the streams
def test_streams_match_the_oracle_under_churn_with_eos(program):
    """Requests that end by `eos_id` mid-stream beside requests that
    end by length: every stream is the oracle's, nothing is emitted
    after an EOS, and each EOS cost exactly one overrun row (the step
    after it had gone out with the slot's row in it)."""
    reqs = []
    for k, (prompt, n) in enumerate(_requests(12, seed=11)):
        eos = None
        if k % 2 == 0:
            # a token of the request's own stream, before its end
            free = _oracle(program, prompt, n)
            eos = free[len(free) // 2]
        reqs.append((prompt, n, eos))
    want = [_oracle(program, *r) for r in reqs]
    eng = DecodeEngine(program=program, queue_limit=64)
    before = _dispatched(program)
    handles = _drive(eng, reqs)
    n_eos = 0
    for h, (_, n, eos), w in zip(handles, reqs, want):
        got = h.result(timeout_s=0)
        assert got == w
        if eos is not None and got[-1] == eos:
            assert h.finish_reason == "eos" and eos not in got[:-1]
            # an EOS on the last token the budget allowed overran nothing
            n_eos += len(got) < n
        else:
            assert h.finish_reason == "length" and len(got) == n
    st = eng.stats()
    assert n_eos >= 4
    assert st["rows_discarded"] == n_eos
    assert st["tokens_total"] == sum(len(w) for w in want)
    # every step dispatched was harvested: nothing is left in flight
    assert st["steps"] == _dispatched(program) - before
    assert eng._pool.audit()["leaked"] == 0


def test_a_finish_by_length_overruns_nothing(program):
    """`max_new_tokens` is known at dispatch: the slot sits out the
    step after its last emitting one, no row is thrown away, and all
    but the first step of an unbroken run were dispatched ahead."""
    reqs = [(p, n, None) for p, n in _requests(9, seed=12)]
    want = [_oracle(program, *r) for r in reqs]
    eng = DecodeEngine(program=program, queue_limit=64)
    handles = _drive(eng, reqs, stagger=1)
    assert [h.result(timeout_s=0) for h in handles] == want
    st = eng.stats()
    assert st["rows_discarded"] == 0
    assert st["tokens_total"] == sum(n for _, n, _ in reqs)
    # one drain at the end of the one unbroken run
    assert st["steps_ahead"] == st["steps"] - 1
    assert st["trace_counts"] == program.trace_stats()["trace_counts"]


# ================================= (b) what lands while a step flies
def _cancel(eng, a):
    a.cancel()
    return "cancelled"


def _deadline(eng, a):
    a._deadline = time.monotonic() - 1.0
    return "deadline"


def _evict(eng, a):
    injector().inject("serving.slot_evict", mode="raise", at_hit=1)
    return None


def _nonfinite(eng, a):
    injector().inject("decode.nonfinite", mode="raise", at_hit=1)
    return None


@pytest.mark.chaos
@pytest.mark.parametrize("land", [_cancel, _deadline, _evict, _nonfinite],
                         ids=["cancel", "deadline", "evict", "nonfinite"])
def test_an_event_in_flight_credits_nobody_else(program, land):
    """A holds slot 0 with a step in flight when the event lands; B is
    waiting. The next call frees the slot (or, for the poison verdict,
    quarantines it at the harvest) and places a request before the
    step in flight is harvested: that step's row is thrown away, B's
    stream starts with B's own first token, and A's stream is a prefix
    of the oracle's (cancel, deadline) or the whole of it after replay
    (eviction, quarantine), no token twice."""
    (pa, _), (pb, nb) = _requests(2, seed=13, max_prompt=PAGE)
    na = 10
    want_a, want_b = _oracle(program, pa, na), _oracle(program, pb, nb)
    eng = DecodeEngine(program=program, queue_limit=64)
    a = eng.submit(pa, na, deadline_s=600.0)
    _until_in_flight(eng, a, tokens=3)
    had = a.tokens_so_far()
    gen = int(eng._slot_gen[0])
    discarded = eng.stats()["rows_discarded"]
    reason = land(eng, a)
    b = eng.submit(pb, nb)
    eng.step_once()
    # the slot changed hands (or was written off) before the harvest
    assert int(eng._slot_gen[0]) > gen
    if reason is not None:
        assert a.done and a.finish_reason == reason
        assert a.result(timeout_s=0) == had == want_a[:len(had)]
        assert eng._slot_req[0] is b
    else:
        assert not a.done and a.tokens_so_far() == had
    # nobody was credited the token that was in flight for A
    assert b.tokens_so_far() == []
    _finish(eng, [a, b])
    assert b.result(timeout_s=0) == want_b
    st = eng.stats()
    if reason is None:
        assert a.result(timeout_s=0) == want_a
        assert st["replays"] == 1
        assert a.evictions + a.poison_strikes == 1
    assert st["rows_discarded"] >= discarded + 1
    assert st["quarantines"] == (land is _nonfinite)
    assert st["evictions"] == (land is _evict)
    audit = eng._pool.audit()
    assert audit["leaked"] == 0 and not audit["double_freed"]


# ====================================================== (c) the order
class _Late:
    """A step's output that records when it is first read."""

    def __init__(self, value, log, step):
        self.value, self.log, self.step = value, log, step

    def copy_to_host_async(self):
        pass

    def __array__(self, dtype=None, copy=None):
        self.log.append(("read", self.step))
        return np.asarray(self.value, dtype)


class _Recording:
    """A DecodeProgram that logs every decode dispatch and every first
    read of a step's tokens, and is the wrapped program otherwise."""

    def __init__(self, program):
        self._program, self.log, self._n = program, [], 0

    def __getattr__(self, name):
        return getattr(self._program, name)

    def step(self, *args):
        *args, prev, take = args
        self._n += 1
        self.log.append(("dispatch", self._n))
        if isinstance(prev, _Late):
            prev = prev.value       # stays on the device: not a read
        kv, nxt, ok, *state = self._program.step(*args, prev, take)
        return (kv, _Late(nxt, self.log, self._n),
                _Late(ok, [], self._n), *state)


def test_step_n_plus_1_is_dispatched_before_step_n_is_read(program):
    rec = _Recording(program)
    reqs = [(p, n, None) for p, n in _requests(5, seed=14)]
    want = [_oracle(program, *r) for r in reqs]
    eng = DecodeEngine(program=rec, queue_limit=64)
    handles = _drive(eng, reqs, stagger=1)
    assert [h.result(timeout_s=0) for h in handles] == want
    steps = eng.stats()["steps"]
    assert steps == rec._n >= 10
    # dispatch 1, dispatch 2, read 1, dispatch 3, read 2, ... and the
    # last step, with no successor, read by the drain
    assert rec.log == [("dispatch", 1)] + [
        ev for n in range(1, steps)
        for ev in (("dispatch", n + 1), ("read", n))] + [("read", steps)]
    assert eng.stats()["steps_ahead"] == steps - 1


# ============================================= (d) drain, stop, restart
def test_the_drain_harvests_the_last_step_and_then_there_is_nothing(
        program):
    prompt, n = [5, 6, 7], 3
    want = _oracle(program, prompt, n)
    eng = DecodeEngine(program=program)
    before = _dispatched(program)
    h = eng.submit(prompt, n)
    # the call that admits it dispatches the first-token step; two
    # more dispatches, each harvesting the step before
    for k in range(n):
        assert eng.step_once()
        assert len(h.tokens_so_far()) == k and eng._inflight is not None
    assert not h.done
    # every emitting step is out: nothing to dispatch, one to harvest
    assert eng.step_once()
    assert h.done and h.result(timeout_s=0) == want
    assert eng._inflight is None
    assert eng.step_once() is False
    st = eng.stats()
    assert st["steps"] == _dispatched(program) - before == n
    assert st["steps_ahead"] == n - 1 and st["rows_discarded"] == 0


def test_stop_with_a_step_in_flight_leaves_the_pool_usable(program):
    prompt, n = list(range(1, 12)), 8
    want = _oracle(program, prompt, n)
    eng = DecodeEngine(program=program)
    h = eng.submit(prompt, n)
    _until_in_flight(eng, h, tokens=2)
    had = h.tokens_so_far()
    eng.stop()
    assert eng._inflight is None
    with pytest.raises(ShutdownError):
        h.result(timeout_s=0)
    assert h.tokens_so_far() == had == want[:len(had)]
    # the pool is the dropped step's output: another engine's to use
    again = DecodeEngine(program=program)
    again.kv, again.state = eng.kv, eng.state
    g = again.submit(prompt, n)
    _finish(again, [g])
    assert g.result(timeout_s=0) == want
    assert again.stats()["rows_discarded"] == 0


def test_a_restart_drops_the_step_in_flight_and_replays(program):
    prompt, n = list(range(3, 20)), 9
    want = _oracle(program, prompt, n)
    eng = DecodeEngine(program=program, watchdog_timeout_s=None)
    h = eng.submit(prompt, n)
    _until_in_flight(eng, h, tokens=3)
    with eng._cond:
        eng._running = True     # as under `start()`, with no loop thread
    eng._spawn_loop = lambda epoch: None    # and none after: stepped here
    try:
        eng._restart_engine("drill")
        assert eng._inflight is None and not eng._take.any()
        assert not h.done and h.tokens_so_far() == want[:3]
        _finish(eng, [h])
        assert h.result(timeout_s=0) == want
    finally:
        eng.stop()
    st = eng.stats()
    assert h.replays == 1 and st["engine_restarts"] == 1
    # the dropped step's rows were nobody's to discard
    assert st["rows_discarded"] == 0
