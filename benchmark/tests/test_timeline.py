"""The timeline's reductions, on a small trace recorded on the chip with
scopes and step records (`record_scoped_trace.py`: five executions of
one jitted program under `conv/c1`, `bn/b1` and `kv_read`, each in a
profiler step with `dispatch`, `fetch` and `harvest`)."""

import json
import os

import pytest

from benchmark import timeline
from deeplearning4j_tpu.observability import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PROGRAM = "jit_scoped_step"


@pytest.fixture(scope="module")
def trace():
    return timeline.read_trace(os.path.join(DATA, "tpu_scoped.xplane.pb"))


@pytest.fixture(scope="module")
def records():
    with open(os.path.join(DATA, "tpu_scoped.records.json")) as f:
        doc = json.load(f)
    return [(r[0], r[1], r[2], [tuple(m) for m in r[3]], r[4])
            for r in doc["records"]]


def test_the_wire_reader_finds_modules_operations_and_scopes(trace):
    assert [m[2] for m in trace["modules"]] == [PROGRAM] * 5
    # 104.43 us an execution, as the trace's XLA Modules line has them
    assert trace["modules"][0][1] - trace["modules"][0][0] \
        == pytest.approx(104426.2, rel=1e-4)
    assert trace["profile_start_ns"] == 1790790361540110009
    tf_ops = {o[2] for o in trace["ops"]}
    assert "jit(scoped_step)/conv/c1/dot_general:" in tf_ops
    assert "jit(scoped_step)/kv_read/gather:" in tf_ops
    assert "" in tf_ops         # the compiler's own copies carry none


def test_scope_sums_add_up_to_the_programs_device_time(trace):
    prog = timeline.device_by_scope(trace)[PROGRAM]
    assert prog["n"] == 5
    assert set(prog["scopes"]) == {"conv/c1", "bn/b1", "kv_read",
                                   timeline.UNSCOPED}
    assert sum(prog["scopes"].values()) == pytest.approx(
        prog["seconds"], rel=0.01)
    # the matmul is most of it; the gather and the batch-norm follow
    assert prog["scopes"]["conv/c1"] / prog["seconds"] \
        == pytest.approx(0.851, abs=0.005)
    assert prog["scopes"]["kv_read"] > prog["scopes"]["bn/b1"] \
        > prog["scopes"][timeline.UNSCOPED]


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(step_fn)/transpose(jvp(conv/s2b0_a_conv))/mul:",
     "conv/s2b0_a_conv"),
    ("jit(step_fn)/jvp(conv/s2b0_a_conv)/bn/stats/reduce_sum:", "bn/stats"),
    ("jit(step_fn)/transpose(jvp(conv/c))/other/bias_grad/reduce_sum:",
     "other/bias_grad"),
    ("jit(step_fn)/transpose(jvp(loss))/div:", "loss"),
    ("jit(step_fn)/updater/add:", "updater"),
    ("jit(decode_fn)/kv_read/gather:", "kv_read"),
    ("jit(step_fn)/jvp()/convert_element_type:", timeline.UNSCOPED),
    ("pool:", timeline.UNSCOPED),
    ("", timeline.UNSCOPED),
])
def test_scope_of_an_operation_is_its_innermost(tf_op, scope):
    assert timeline.scope_of(tf_op) == scope


def test_the_recorded_steps_align_with_the_trace(trace, records):
    runs = [(m[0], m[1]) for m in trace["modules"]]
    clock = tracing.clock_offset(records, runs, "fetch")
    assert clock["n"] == 5
    assert clock["spread_ns"] < timeline.JOIN_SPREAD_NS
    gaps = timeline.idle_gaps(trace, {PROGRAM})
    by_phase = tracing.phases_over(records, gaps, clock["offset_ns"])
    idle = sum(e - s for s, e in gaps) * 1e-9
    assert sum(by_phase.values()) == pytest.approx(idle, rel=1e-6)
    # the recorder slept in `harvest` and between two steps
    assert by_phase["between_steps"] > by_phase["harvest"] > 0
    assert by_phase.get("(no record)", 0.0) < 0.01 * idle


def test_a_gap_table_over_synthetic_records_names_every_gap(trace):
    """A step record around each execution, on a clock 3 s and 1.5 ms
    off the trace's: every idle gap falls under a named phase."""
    off = 3e9 + 1.5e6
    runs = [(m[0], m[1]) for m in trace["modules"]]
    recs, prev_end = [], (runs[0][0] + off) * 1e-9 - 0.002
    for k, (start, end) in enumerate(runs):
        h0, h1 = (start + off) * 1e-9, (end + off) * 1e-9
        marks = [("between_steps", prev_end), ("tables", h0 - 0.0005),
                 ("dispatch", h0 - 0.0002), ("fetch", h0 - 0.0001),
                 ("harvest", h1), ("emit", h1 + 0.0003)]
        prev_end = h1 + 0.0005
        recs.append(("decode/synthetic", k + 1, marks[0][1], marks,
                     prev_end))
    clock = tracing.clock_offset(recs, runs, "fetch")
    assert clock["offset_ns"] == pytest.approx(off, abs=1e3)
    by_phase = tracing.phases_over(
        recs, timeline.idle_gaps(trace, {PROGRAM}), clock["offset_ns"])
    assert "(no record)" not in by_phase
    assert {"between_steps", "tables", "dispatch", "harvest", "emit",
            "fetch"} >= set(by_phase)
    assert by_phase["between_steps"] > by_phase["emit"] > 0


def test_a_trace_with_no_device_plane_reads_nothing(tmp_path):
    empty = tmp_path / "empty.xplane.pb"
    empty.write_bytes(b"")
    got = timeline.read_trace(str(empty))
    assert got == {"profile_start_ns": None, "modules": [], "ops": []}
    assert timeline.device_by_scope(got) == {}
    assert timeline.idle_gaps(got, {PROGRAM}) == []
