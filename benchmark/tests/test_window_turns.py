"""The readers of the window's own turns (`window_step_ms.serve`,
`window_chunk_ms.serve`), of its request records
(`fill_ms_per_chunk.serve`), of the ring's losses
(`records_dropped.serve`) and of the clock join's health
(`clock_join_spread_ms.serve`), over synthetic records: a step record's
`work` says what its call dispatched, and the time from its `harvest`
mark to the next record's is the device's time for it; a request record
says what its first token waited for."""

import json
import os

import pytest

from benchmark import run, timeline, window_turns
from deeplearning4j_tpu.observability import perf, tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
STEP = {16: 0.004, 32: 0.006, 64: 0.015}    # a decode step alone, by width
CHUNK = {32: 0.002, 64: 0.003}      # what a chunk adds, by its width
COPY = 0.0005


def work(width=32, chunks=(), copies=0, ahead=1, earlier=()):
    return (ahead, width, 30, 8 * width, copies,
            tuple((w, 4, 100) for w in chunks), tuple(earlier))


def took(w) -> float:
    return (STEP.get(w[1], 0.0) + w[4] * COPY
            + sum(CHUNK[c[0]] for c in w[5]))


def window_of(works, fields=6):
    """Records of consecutive steps: each `harvest` mark follows the one
    before by the device's time for the work of the record before."""
    records, t = [], 50.0
    for k, w in enumerate(works):
        marks = [("between_steps", t - 0.004), ("dispatch", t - 0.003),
                 ("fetch", t - 0.002), ("harvest", t), ("emit", t + 0.0002)]
        records.append((("decode/syn", 1000 + k, marks[0][1], marks,
                         t + 0.0004, w))[:fields])
        t += took(w) if w[0] else 0.050     # a drain idles the device
    return records


def facts_of(records):
    calls = [w for r in records if len(r) > 5 and r[5]
             for w in (*r[5][6], r[5])]
    return {"timeline": {"window": records},
            "delta": {"steps": len(records),
                      "prefill_chunks": sum(len(w[5]) for w in calls),
                      "cow_copies": sum(w[4] for w in calls),
                      "kv_pages_live": sum(w[3] for w in calls)}}


def mixed():
    """106 turns at 32 pages (40 alone, 35 beside a chunk of 32 pages, 31
    beside one of 64) and 25 at 64, too few to be the window's steady
    width, dealt in turn; copies, two chunks, a drain and a call without
    a record in between."""
    kinds = ([work()] * 40 + [work(chunks=[32])] * 35
             + [work(chunks=[64])] * 31 + [work(64)] * 25)
    works = [kinds[(i * 37) % len(kinds)] for i in range(len(kinds))]
    works[10:10] = [work(copies=1)] * 12 + [work(chunks=[32, 32])] * 3
    # a drain, then a record that carries the first dispatch after it:
    # neither the drain's turn nor the turn that ends in that record counts
    works[60:60] = [work(0, ahead=0),
                    work(earlier=[work(ahead=0, chunks=[32])])]
    return works


def test_a_step_alone_and_what_a_chunk_adds_are_medians_of_their_turns(
        capsys):
    facts = facts_of(window_of(mixed()))
    assert run.read_layer_metric("window_step_ms.serve", facts) \
        == pytest.approx(6.0, abs=1e-6)
    # 35 turns at 2 ms over the step and 30 at 3: the median of the 65
    assert run.read_layer_metric("window_chunk_ms.serve", facts) \
        == pytest.approx(2.0, abs=1e-6)
    err = capsys.readouterr().err
    # the table a reader of the log takes the other widths from (the
    # window's last record, a chunk of 64 pages beside its step, has no
    # turn)
    assert "  64  0  0:     25   15.0000    30.00     512.0" in err
    assert "  32  1  0:     65    8.0000" in err
    assert "  32  0  1:     12    6.5000" in err
    assert "  32  2  0:      3   10.0000" in err
    assert "    32:     35    2.0000    4.00    100.0" in err
    assert "    64:     30    3.0000" in err
    assert "steady width of 32 pages: 6.0000 ms" in err
    # the records' sums beside the window's counters, `earlier` counted
    assert "chunks 73 (counted 73), copies 12 (12)" in err
    assert "{32: 123, 64: 25} (148 harvested), not ahead 1" in err


@pytest.mark.parametrize("name", ["window_step_ms.serve",
                                  "window_chunk_ms.serve"])
@pytest.mark.parametrize("case", ["few turns", "five fields", "no work",
                                  "no window"])
def test_a_reader_of_turns_says_nothing_where_it_has_too_little(name, case):
    # 29 turns of each kind: one under what a median wants
    works = {"few turns": [work()] * 29 + [work(chunks=[32])] * 29
             + [work()]}.get(case, [work()] * 80 + [work(chunks=[32])] * 80)
    records = window_of(works, fields=5 if case == "five fields" else 6)
    if case == "no work":       # a caller of `end_step` that passed none
        records = [r[:5] + (None,) for r in records]
    if case == "no window":
        records = []
    assert run.read_layer_metric(name, facts_of(records)) is None


@pytest.mark.parametrize("wide, stalled, step, chunk", [
    (404, 0, 6.0, 2.0),     # the 32-page phase a little shorter than ...
    (404, 370, 6.0, 2.0),   # ... and much shorter: a stall took its turns
    (29, 0, 4.0, 3.0),      # too few at 32 pages to be the steady state
])
def test_the_steady_width_is_no_vote_between_widths(wide, stalled, step,
                                                    chunk):
    """`lfm2-moe-chat-closed128`: the deal fixes the 16-page phase's
    turns and the 32-page phase has what is left of the window; the
    metric reads the 32-page step however the count falls, down to
    `MIN_TURNS` turns alone."""
    works = ([work(16)] * 466 + [work(16, chunks=[64])] * 329
             + [work()] * (wide - stalled) + [work(chunks=[32])] * 438)
    facts = facts_of(window_of(works + [work()]))
    assert run.read_layer_metric("window_step_ms.serve", facts) \
        == pytest.approx(step, abs=1e-6)
    assert run.read_layer_metric("window_chunk_ms.serve", facts) \
        == pytest.approx(chunk, abs=1e-6)


def test_the_turns_of_a_program_without_work_are_none(monkeypatch):
    """The parent under this benchmark's files: no `perf.WORK_FIELDS`."""
    monkeypatch.setattr(window_turns, "WORK_FIELDS", None)
    facts = facts_of(window_of([work()] * 80))
    assert window_turns.turns(facts) is None
    assert run.read_layer_metric("window_step_ms.serve", facts) is None


def test_every_turn_a_step_and_a_chunk_gives_neither():
    """`gpt2m-unshared-prefix-closed32`: no turn holds a step alone, so
    the host's clock cannot part the two."""
    facts = facts_of(window_of([work(64, chunks=[32])] * 200))
    assert run.read_layer_metric("window_step_ms.serve", facts) is None
    assert run.read_layer_metric("window_chunk_ms.serve", facts) is None


def test_the_turns_are_read_once_a_run():
    facts = facts_of(window_of(mixed()))
    run.read_layer_metric("window_chunk_ms.serve", facts)
    found = facts["window_turns"]
    run.read_layer_metric("window_step_ms.serve", facts)
    assert facts["window_turns"] is found
    # the drain's turn (not ahead, and it ends in a record that carries
    # a call without a record) and the window's last record are none
    assert len(found) == len(mixed()) - 2


def request(k, chunks, mapped=0, wait=0.001, turn=0.008, fields=9):
    """A request record: placed `wait` after its submission, its first
    token a `turn` a chunk and one step of 6 ms after that."""
    t = 50.0 + 0.01 * k
    placed = t + wait
    return (("decode/syn", "request", 1000 + k, t, placed,
             placed + chunks * turn + 0.006, 100 * chunks + 7 + 16 * mapped,
             mapped, chunks))[:fields]


def requests_facts(monkeypatch, records):
    monkeypatch.setattr(timeline, "split_records",
                        lambda plane: ([], list(records)))
    return {"mix": {"warmup_steps": 1000}, "delta": {"steps": 500}}


def test_filling_a_chunk_is_the_median_over_the_windows_requests(
        monkeypatch, capsys):
    # 40 requests of one chunk (8 + 6 ms each), 30 of three (8 + 2 a
    # chunk), 5 the trie served whole (no chunk: in the table, not in
    # the median), and two submitted outside the window
    records = ([request(k, 1) for k in range(40)]
               + [request(40 + k, 3, wait=0.020) for k in range(30)]
               + [request(70 + k, 0, mapped=4) for k in range(5)]
               + [request(-3, 9), request(600, 9)])
    got = run.read_layer_metric("fill_ms_per_chunk.serve",
                                requests_facts(monkeypatch, records))
    assert got == pytest.approx(14.0, abs=1e-6)
    err = capsys.readouterr().err
    assert "the window's 75 requests" in err
    assert "      1    0:     40     15.000      1.000     14.000    107.0" \
        in err
    assert "      3    0:     30     50.000     20.000     30.000    307.0" \
        in err
    assert "      0    4:      5      7.000      1.000      6.000     71.0" \
        in err


@pytest.mark.parametrize("case", ["few requests", "six fields",
                                  "no count", "no requests"])
def test_filling_a_chunk_says_nothing_where_it_has_too_little(
        monkeypatch, case):
    records = [request(k, 2) for k in range(29 if case == "few requests"
                                            else 80)]
    records += [request(100 + k, 0, mapped=4) for k in range(10)]
    if case == "six fields":    # a program before PR 38
        records = [r[:6] for r in records]
    if case == "no count":      # a caller of `record_request` without
        records = [r[:6] + (None, None, None) for r in records]
    if case == "no requests":
        records = []
    assert run.read_layer_metric(
        "fill_ms_per_chunk.serve",
        requests_facts(monkeypatch, records)) is None


def test_records_dropped_reads_the_rings_count(monkeypatch):
    monkeypatch.setattr(perf, "timeline_dropped", lambda: 7)
    assert run.read_layer_metric("records_dropped.serve", {}) == 7
    monkeypatch.delattr(perf, "timeline_dropped")   # a program before it
    assert run.read_layer_metric("records_dropped.serve", {}) is None


@pytest.mark.parametrize("clock, value", [
    ({"offset_ns": 3e9, "spread_ns": 2.3e5, "n": 8, "shift": 1}, 0.23),
    ({"offset_ns": 3e9, "spread_ns": 2.09e7, "n": 8}, 20.9),    # no shift told
    ({"offset_ns": 0.0, "spread_ns": float("inf"), "n": 0, "shift": 0},
     None),
    (None, None),
])
def test_clock_join_spread_is_the_joins_own(clock, value):
    facts = {"timeline": {"clock": clock} if clock else {}}
    got = run.read_layer_metric("clock_join_spread_ms.serve", facts)
    assert got == (pytest.approx(value) if value is not None else None)


def test_the_recorded_pair_gives_the_offset_it_gave():
    """The slice recorded on the chip (five records of five fields, five
    executions): the pairing that survives a step in flight keeps
    shift 0 there and returns the median it returned before."""
    with open(os.path.join(DATA, "tpu_scoped.records.json")) as f:
        records = [(r[0], r[1], r[2], [tuple(m) for m in r[3]], r[4])
                   for r in json.load(f)["records"]]
    trace = timeline.read_trace(os.path.join(DATA, "tpu_scoped.xplane.pb"))
    runs = [(m[0], m[1]) for m in trace["modules"]]
    ends = [t1 * 1e9 for r in records
            for name, _, t1 in perf.phase_spans(r[3], r[4])
            if name == "fetch"]
    diffs = sorted(h - d[1] for h, d in zip(ends, runs))
    got = tracing.clock_offset(records, runs, "fetch")
    assert (got["shift"], got["n"]) == (0, 5)
    assert got["offset_ns"] == diffs[2]
    assert got["spread_ns"] == diffs[-1] - diffs[0]
