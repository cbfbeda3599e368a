"""The step timeline (observability/perf.py, serving/continuous.py,
observability/tracing.py) and the named scopes of the compiled programs.

The pins:
  * every engine step leaves ONE record in the process's timeline, its
    phases cover its wall time, its number is `stats()["steps"]`, and
    it exists with `tracer=None`; the ring stays bounded and counts
    what it pushes out;
  * a record's `work` says what its call dispatched, field by field
    over a hand-worked schedule, its sums are the engine's counters,
    and on a device that runs in order it lies between the record's
    `harvest` mark and the next record's;
  * a request leaves one record at its first token, its three clocks in
    order, and what its first token waited for;
  * marks and scopes are metadata: tokens are bitwise the sequential
    oracle's;
  * the lowered decode, chunk and train-step programs carry the scope
    names a device trace is summed by;
  * `clock_offset` recovers a known offset between two clocks, from
    records of five fields or six, with the trace cut at its head or
    running on past the slice, and reports a wide spread as such;
    `phases_over` names a gap's phases;
  * a record costs microseconds.
"""

import re
import time
from collections import deque
from types import SimpleNamespace

import pytest

from deeplearning4j_tpu.engine.decode_program import DecodeProgram
from deeplearning4j_tpu.observability import perf
from deeplearning4j_tpu.observability.perf import (
    TIMELINE_CAPACITY,
    StepPhaseProfiler,
    get_timeline,
)
from deeplearning4j_tpu.observability.tracing import (
    Tracer,
    clock_offset,
    phases_over,
)
from deeplearning4j_tpu.serving.continuous import (
    DecodeEngine,
    sequential_decode,
)
from deeplearning4j_tpu.zoo.decoder import CausalTransformer

pytestmark = pytest.mark.obs

VOCAB, CTX, SLOTS, PAGE = 64, 64, 4, 8
ENGINE_PHASES = {"between_steps", "sweep", "admit", "prepare_cells",
                 "tables", "dispatch", "fetch", "harvest", "emit",
                 "journal"}


@pytest.fixture(scope="module")
def program():
    model = CausalTransformer(vocab_size=VOCAB, d_model=32, n_heads=4,
                              n_layers=2, max_ctx=CTX, seed=5).init()
    prog = DecodeProgram(model, max_slots=SLOTS, page_size=PAGE)
    prog.warmup(prog.init_kv())
    return prog


def _drive(eng, handles, max_steps=500):
    steps = 0
    while any(not h.done for h in handles):
        eng.step_once()
        steps += 1
        assert steps < max_steps, "engine made no progress"


def _records(owner):
    mine = [r for r in list(get_timeline()) if r[0] == owner]
    return ([r for r in mine if r[1] != "request"],
            [r for r in mine if r[1] == "request"])


PROMPTS = [([3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5], 9), ([2, 7, 1, 8], 12),
           ([1, 6, 1, 8, 0, 3, 3, 9, 8, 8, 7, 4, 9, 8, 9, 4, 8, 4], 7)]


# ================================================== the engine's records
def test_every_engine_step_leaves_one_record_that_covers_it(program):
    eng = DecodeEngine(program=program, model_name="covers")
    assert eng.tracer is None       # the records need no tracer
    handles = [eng.submit(p, n) for p, n in PROMPTS]
    _drive(eng, handles)
    steps, _ = _records("decode/covers")
    assert [r[1] for r in steps] == list(range(1, eng.stats()["steps"] + 1))
    for _, _, t_begin, marks, t_end, work in steps:
        assert len(work) == len(perf.WORK_FIELDS)
        assert {m[0] for m in marks} <= ENGINE_PHASES
        times = [t for _, t in marks]
        assert times == sorted(times) and t_begin <= times[0]
        covered = t_end - times[0]
        assert covered >= 0.95 * (t_end - t_begin)
    # the caller's turn is the later step's `between_steps`
    assert all(r[3][0][0] == "between_steps" for r in steps[1:])
    assert all(b[2] >= a[4] for a, b in zip(steps, steps[1:]))
    # the operator's view: the same phases, summed
    phases = eng.stats()["phases"]
    assert phases["steps"] == len(steps)
    assert phases["coverage"] >= 0.95
    assert {"fetch", "tables", "between_steps"} <= set(phases["phases"])


def test_request_record_has_its_clocks_in_order(program):
    eng = DecodeEngine(program=program, model_name="requests",
                       max_prefills_per_step=1)
    first = eng.submit(*PROMPTS[0])
    while eng.stats()["steps"] < 1:     # a step counts once harvested
        eng.step_once()
    late = eng.submit(*PROMPTS[1])      # submitted at a later step
    _drive(eng, [first, late])
    _, requests = _records("decode/requests")
    assert len(requests) == 2           # one a request, at its first token
    for _, _, at_step, t_submit, t_placed, t_first, *made_of in requests:
        assert t_submit <= t_placed <= t_first
    assert requests[0][2] == 0 and requests[1][2] >= 1
    assert requests[0][3:6] == (first.t_submit, first.t_placed,
                                first.t_first_token)
    # what each first token waited for: prompt tokens, pages the trie
    # mapped, chunks dispatched (one a prompt where the chunk is the
    # window; `test_a_records_work_...` has a request the trie serves)
    assert [r[6:] for r in requests] == [(len(PROMPTS[0][0]), 0, 1),
                                         (len(PROMPTS[1][0]), 0, 1)]


# ========================================= what a step's call dispatched
def _work(ahead=1, width=CTX // PAGE, rows=1, live=2, copies=0, chunks=(),
          earlier=()):
    return (ahead, width, rows, live, copies, tuple(chunks), tuple(earlier))


A_PROMPT = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5]    # a page and three tokens
C_PROMPT = A_PROMPT[:PAGE] + [7, 7, 7, 7, 7]    # A's first page, then its own


def _schedule(eng):
    """A alone for three calls, B joins, both run out (a drain), then C
    on an idle engine, whose first page the trie holds."""
    a = eng.submit(A_PROMPT, 6)
    for _ in range(3):
        eng.step_once()
    b = eng.submit([2, 7, 1, 8], 3)
    while not (a.done and b.done) or eng._inflight is not None:
        eng.step_once()
    assert not eng.step_once()          # idle: nothing to do, no record
    c = eng.submit(C_PROMPT, 2)
    while not c.done or eng._inflight is not None:
        eng.step_once()


def test_a_records_work_says_what_its_call_dispatched(program):
    eng = DecodeEngine(program=program, model_name="work")
    s0 = eng.stats()
    _schedule(eng)
    steps, requests = _records("decode/work")
    w = CTX // PAGE     # the toy program's one window width, in pages
    assert [r[5] for r in steps] == [
        # call 1 placed A, ran its chunk (two pages, 11 tokens) and
        # dispatched A's first-token step on an idle device: no harvest,
        # no record, so it rides in record 1's `earlier`, not ahead.
        # Call 2 copied A's last prompt page (the trie holds it too)
        # and dispatched a step over the one in flight
        _work(copies=1, earlier=[_work(ahead=0, chunks=[(w, 2, 11)])]),
        _work(),                            # a step alone
        # B joins: its chunk (one page, four tokens) beside a step of
        # two rows, three live pages
        _work(rows=2, live=3, chunks=[(w, 1, 4)]),
        _work(rows=2, live=3, copies=1),    # B's first private write
        _work(rows=2, live=3),
        # A's and B's last emitting steps are in flight or harvested:
        # the call finds no row to dispatch and drains
        _work(ahead=0, width=0, rows=0, live=0),
        # C on an idle engine: the trie maps its first page, the chunk
        # fills the second (13 tokens run, the first page's rows parked
        # in scratch); the first dispatch after a drain is not ahead
        _work(copies=1, earlier=[_work(ahead=0, chunks=[(w, 1, 13)])]),
        _work(ahead=0, width=0, rows=0, live=0),
    ]
    assert [r[6:] for r in requests] == [(11, 0, 1), (4, 0, 1), (13, 1, 1)]
    # the records' sums are the engine's counters
    s1 = eng.stats()
    calls = [c for r in steps for c in (*r[5][6], r[5])]
    assert sum(len(c[5]) for c in calls) \
        == s1["prefill_chunks"] - s0["prefill_chunks"] == 3
    assert sum(c[4] for c in calls) \
        == s1["cow_copies"] - s0["cow_copies"] == 3
    assert sum(c[3] for c in calls) \
        == s1["kv_pages_live"] - s0["kv_pages_live"]
    assert sum(1 for c in calls if c[1] == w) \
        == (s1["dispatches"]["step_by_width"][w]
            - s0["dispatches"]["step_by_width"][w]) == 8
    assert sum(c[0] for c in calls) \
        == s1["steps_ahead"] - s0["steps_ahead"] == 6


def test_a_records_work_lies_between_its_harvest_and_the_next(
        program, monkeypatch):
    """The pairing rule, on a clock of the test's own and a device that
    runs what it is handed in order, each program at a cost of its
    kind: where a call ran ahead, the time from its record's `harvest`
    mark to the next record's is the cost of exactly the work the
    record lists."""
    cost = {"step": 0.020, "chunk": 0.007, "copy": 0.001}
    clock = SimpleNamespace(now=100.0)

    def read():                     # every read is a little host work
        clock.now += 1e-5
        return clock.now

    device = SimpleNamespace(free_at=0.0, step_ends=deque())

    def runs(kind, real):
        def handed_over(*args, **kwargs):
            device.free_at = max(clock.now, device.free_at) + cost[kind]
            if kind == "step":
                device.step_ends.append(device.free_at)
            return real(*args, **kwargs)
        return handed_over

    monkeypatch.setattr(perf, "time", SimpleNamespace(perf_counter=read))
    monkeypatch.setattr(program, "step", runs("step", program.step))
    monkeypatch.setattr(program, "prefill_chunk",
                        runs("chunk", program.prefill_chunk))
    monkeypatch.setattr(program, "copy_page",
                        runs("copy", program.copy_page))
    eng = DecodeEngine(program=program, model_name="paired")
    mark = eng._phases.mark

    def fetched(phase):             # `fetch` ends when the step does
        if phase == "harvest":
            clock.now = max(clock.now, device.step_ends.popleft())
        mark(phase)

    eng._phases.mark = fetched
    _schedule(eng)
    steps, _ = _records("decode/paired")
    at = [dict(r[3])["harvest"] for r in steps]
    checked = 0
    for (k, rec), nxt in zip(enumerate(steps), steps[1:]):
        ahead, width, _, _, copies, chunks, _ = rec[5]
        if not ahead or nxt[5][6]:
            continue        # a drain, or a call without a record between
        assert at[k + 1] - at[k] == pytest.approx(
            cost["step"] + copies * cost["copy"]
            + len(chunks) * cost["chunk"], abs=1e-9)
        checked += 1
    assert checked == 6     # records 1-5 and 7: all but the two drains


def test_marks_and_scopes_leave_the_tokens_bitwise(program):
    eng = DecodeEngine(program=program, model_name="bitwise",
                       tracer=Tracer())
    handles = [eng.submit(p, n) for p, n in PROMPTS]
    _drive(eng, handles)
    for (prompt, n), h in zip(PROMPTS, handles):
        assert h.result(timeout_s=0) == sequential_decode(
            program, prompt, n)[1]
    # with a tracer the same marks are `phase:<name>` spans: the step
    # track under the request spans
    names = {s["name"] for s in eng.tracer.spans()}
    assert {"phase:fetch", "phase:tables", "phase:between_steps",
            "prefill_chunk_dispatch", "token"} <= names


def test_the_ring_stays_bounded():
    """The ring holds a run (a 51 s window at a cycle of 3 ms with its
    requests, the warm-up and the traced slice before it) and counts
    every record it pushes out."""
    assert TIMELINE_CAPACITY == 32768
    ring = get_timeline()
    held, dropped = len(ring), perf.timeline_dropped()
    pp = StepPhaseProfiler(owner="decode/bounded", emit_metrics=False)
    for i in range(TIMELINE_CAPACITY + 50):
        pp.begin_step(since_last="between_steps")
        pp.mark("fetch")
        pp.end_step(step=i)
    assert len(ring) == ring.maxlen == TIMELINE_CAPACITY
    assert ring[-1][1] == TIMELINE_CAPACITY + 49
    assert ring[-1][5] is None          # a caller that passes no work
    assert perf.timeline_dropped() - dropped == held + 50
    perf.record_request("decode/bounded", 1, 0.0, 1.0, 2.0)
    assert len(ring) == TIMELINE_CAPACITY and ring[-1][1] == "request"
    assert ring[-1][6:] == (None, None, None)
    assert perf.timeline_dropped() - dropped == held + 51


def test_a_record_costs_microseconds():
    """10,000 stub steps with the engine's ten phases: under 5 us a
    record, the best of five rounds. Read on this thread's CPU clock:
    a thread another test left running, or five other workers, make
    this loop wait (3.3 us became 9.6 beside two spinning threads) and
    cannot make a record cost more."""
    names = sorted(ENGINE_PHASES - {"between_steps"})
    pp = StepPhaseProfiler(owner="decode/stub", emit_metrics=False)
    best = float("inf")
    for _ in range(5):
        t0 = time.thread_time()
        for i in range(10_000):
            pp.begin_step(since_last="between_steps")
            for n in names:
                pp.mark(n)
            pp.end_step(step=i)
        best = min(best, (time.thread_time() - t0) / 10_000)
    assert best < 5e-6, f"{best * 1e6:.2f} us a record"


def test_the_default_profiler_never_syncs():
    pp = StepPhaseProfiler()
    assert pp.sync_every == 0 and not pp.should_sync(0)
    pp.begin_step(3)
    pp.mark("dispatch")
    pp.sync(object())       # would raise in block_until_ready if it ran
    pp.mark("host_sync")
    pp.end_step()
    assert "device_compute" not in pp.report()["phases"]
    assert get_timeline()[-1][:2] == ("train", 3)
    assert get_timeline()[-1][5] is None    # a fit step dispatches no work


# ================================================ the programs' scopes
def _scoped(text: str, scope: str) -> bool:
    """`scope` as one whole element of an operation's name stack in
    lowered text (`"jit(decode_fn)/kv_read"`,
    `jvp(conv/stem_conv))/mul`)."""
    return re.search(r'["/(]' + re.escape(scope) + r'["/)]', text) \
        is not None


def test_decode_and_chunk_programs_carry_their_scopes(program):
    import jax

    texts = {}
    for rec in program.lint_records():
        texts[rec.name] = jax.jit(rec.fn).lower(
            *rec.example_args).as_text(debug_info=True)
    step = texts[f"decode_step_s{SLOTS}"]
    chunk = texts[f"decode_prefill_c{program.chunk_tokens}"]
    for scope in ("embed", "qkv", "kv_write", "kv_read", "attn", "mlp"):
        assert _scoped(step, scope), scope
        assert _scoped(chunk, scope), scope
    assert _scoped(step, "head") and not _scoped(chunk, "head")
    assert _scoped(texts["decode_page_copy"], "kv_copy")


def test_compiled_operations_keep_their_scopes_under_the_cache_settings():
    """What a device trace shows is the compiled operation's `op_name`.
    `place_compile_cache()` cuts locations to one frame for the cache
    key's sake; that must leave the scope in the name (turning
    tracebacks off whole took it out: PERF.md, PR 26)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.jit_cache import place_compile_cache

    place_compile_cache()
    assert jax.config.jax_compilation_cache_include_metadata_in_key

    def gather(x):
        with jax.named_scope("kv_read"):
            return x[jnp.arange(4)] * 2.0

    hlo = jax.jit(gather).lower(jnp.ones((8, 8))).compile().as_text()
    names = re.findall(r'op_name="([^"]+)"', hlo)
    assert any("/kv_read/" in n for n in names), names


@pytest.mark.parametrize("helpers", ["none", "fused"])
def test_train_step_carries_vertex_scopes(helpers):
    import jax
    import jax.numpy as jnp

    from tests.test_helpers import _mini_resnet

    net = _mini_resnet(helpers)
    fn = net._build_train_step(False)
    x = jnp.zeros((4, 16, 16, 3), jnp.float32)
    y = jnp.zeros((4, 5), jnp.float32)
    text = fn.lower(net.params, net.updater_states, net.states,
                    jnp.int32(0), {"input": x}, [y], None, None,
                    jax.random.PRNGKey(0), None,
                    jnp.float32(1.0)).as_text(debug_info=True)
    for scope in ("conv/stem_conv", "bn/stem_bn", "pool/pool", "loss",
                  "updater"):
        assert _scoped(text, scope), scope
    # the backward operations inherit the vertex's scope
    assert "transpose(jvp(conv/b1b_conv))" in text
    if helpers == "none":       # the fused tier folds these into convs
        assert _scoped(text, "add/b1_add")
        assert _scoped(text, "act/b1_out")


# ===================================================== one clock
def _synthetic(offset_ns, jitter_ns=(0.0,) * 8, step_s=0.5, fields=6,
               busy_s=(0.4,) * 8):
    """Eight steps whose `fetch` ends `offset_ns` (+ jitter) after the
    matching device execution ends, on another clock. Records of
    `fields` fields: five as they were before `work`, or six."""
    records, runs = [], []
    t = 100.0
    for k, j in enumerate(jitter_ns):
        step_s = busy_s[k] + 0.1
        dev_end = (t + step_s) * 1e9            # the device's clock
        host_end = (dev_end + offset_ns + j) * 1e-9
        marks = [("between_steps", host_end - step_s - 0.004),
                 ("sweep", host_end - step_s - 0.001),
                 ("dispatch", host_end - step_s),
                 ("fetch", host_end - step_s + 0.001),
                 ("harvest", host_end), ("emit", host_end + 0.002)]
        records.append((("decode/syn", k + 1, marks[0][1], marks,
                         host_end + 0.003) + (None,))[:fields])
        runs.append((dev_end - busy_s[k] * 1e9, dev_end))
        t += step_s + 0.007
    return records, runs


@pytest.mark.parametrize("fields", [5, 6])
def test_clock_offset_recovers_a_known_offset(fields):
    records, runs = _synthetic(1.25e6 + 3e9, fields=fields)
    got = clock_offset(records, runs, "fetch")
    assert got["n"] == 8 and got["shift"] == 0
    assert got["offset_ns"] == pytest.approx(1.25e6 + 3e9, abs=2e3)
    assert got["spread_ns"] < 2e3
    # the profiler's start cut the first execution off: pairs count
    # from the slice's end
    assert clock_offset(records, runs[1:], "fetch")["offset_ns"] \
        == pytest.approx(1.25e6 + 3e9, abs=2e3)
    assert clock_offset(records, [], "fetch")["n"] == 0


def test_clock_offset_reports_a_wide_spread_as_such():
    jitter = (0.0, 2e6, -1e6, 4e6, 0.0, 3e6, -2e6, 1e6)
    records, runs = _synthetic(5e6, jitter)
    got = clock_offset(records, runs, "fetch")
    assert got["spread_ns"] == pytest.approx(6e6, rel=1e-3)
    assert got["spread_ns"] > 0.5e6     # a reader must not join on this


# turns of their own lengths, as a slice with chunks and copies in it
UNEVEN = (0.4, 0.31, 0.47, 0.36, 0.52, 0.33, 0.44, 0.39)


@pytest.mark.parametrize("head, tail, busy", [
    (0, 0, UNEVEN), (1, 0, UNEVEN), (0, 1, UNEVEN), (1, 1, UNEVEN),
    (1, 1, (0.4,) * 8)])
def test_clock_offset_survives_a_step_in_flight(head, tail, busy):
    """With a step always in flight the profiler's start cuts the
    execution it finds running (`head`: a sliver of the slice's first
    step) and its stop the one after the slice's last record (`tail`:
    a sliver past the records). Neither is paired; on turns that are
    all alike too, which cannot tell one pairing from another."""
    records, runs = _synthetic(1.25e6 + 3e9, busy_s=busy)
    if head:        # its end in place, its start lost
        runs[0] = (runs[0][1] - 0.03e9, runs[0][1])
    if tail:        # the step in flight when the profiler stopped
        runs.append((runs[-1][1] + 0.107e9, runs[-1][1] + 0.157e9))
    got = clock_offset(records, runs, "fetch")
    assert got["shift"] == 0
    assert got["offset_ns"] == pytest.approx(1.25e6 + 3e9, abs=2e3)
    assert got["spread_ns"] < 2e3
    assert got["n"] == 8 - head


def test_clock_offset_searches_for_no_other_pairing():
    """A trace that runs on by a WHOLE execution past the slice's last
    record (no measured join does: PERF.md, PR 38) is paired a turn
    off: on turns of their own lengths that reads as a spread no
    reader may join on, and on turns all alike as an offset a turn
    off, as it did before."""
    records, runs = _synthetic(1.25e6 + 3e9, busy_s=UNEVEN)
    got = clock_offset(records[:-1], runs, "fetch")
    assert (got["shift"], got["n"]) == (0, 7)
    assert got["spread_ns"] > 0.5e6
    records, runs = _synthetic(1.25e6 + 3e9)
    got = clock_offset(records[:-1], runs, "fetch")
    assert got["spread_ns"] < 2e3
    assert got["offset_ns"] == pytest.approx(1.25e6 + 3e9 - 0.507e9,
                                             abs=2e3)


@pytest.mark.parametrize("fields", [5, 6])
def test_phases_over_names_the_phases_of_a_gap(fields):
    records, runs = _synthetic(2e6, fields=fields)
    off = clock_offset(records, runs, "fetch")["offset_ns"]
    # the device idles from each execution's end to the next's start
    gaps = [(a[1], b[0]) for a, b in zip(runs, runs[1:])]
    got = phases_over(records, gaps, off)
    assert "(no record)" not in got
    assert set(got) == {"harvest", "emit", "between_steps", "sweep",
                        "dispatch", "fetch"}
    assert sum(got.values()) == pytest.approx(
        sum(e - s for s, e in gaps) * 1e-9, rel=1e-6)
    # a gap no record covers is said to be so
    assert phases_over(records, [(0.0, 1e6)], off) == {
        "(no record)": pytest.approx(1e-3)}


def test_clock_offset_reads_a_tracers_phase_spans():
    tr = Tracer()
    pp = StepPhaseProfiler(tracer=tr, owner="decode/spans",
                           emit_metrics=False)
    ends = []
    for i in range(3):
        pp.begin_step()
        pp.mark("fetch")
        time.sleep(0.001)
        ends.append(time.perf_counter())
        pp.mark("harvest")
        pp.end_step(step=i)
    runs = [(0.0, e * 1e9 - 7e6) for e in ends]
    got = clock_offset(tr, runs, "fetch")
    assert got["n"] == 3
    assert got["offset_ns"] == pytest.approx(7e6, abs=0.2e6)
