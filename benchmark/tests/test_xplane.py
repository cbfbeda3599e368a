"""The reduction from the profiler's trace, on a small trace recorded
on the chip (`record_trace.py`: three executions of one jitted program,
each in a `bench:step` span, a 20 ms host sleep before the third)."""

import os

import pytest

from benchmark import xplane

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "tpu_small.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    import jax

    return xplane.reduce_profile(jax.profiler.ProfileData.from_file(TRACE))


def test_programs_and_their_device_time(reduced):
    assert reduced["chips"] == 1
    assert set(reduced["programs"]) == {"jit_small_step"}
    prog = reduced["programs"]["jit_small_step"]
    assert prog["n"] == 3
    # 13.358 + 19.101 + 19.503 us, as the trace's XLA Modules line has them
    assert prog["seconds"] == pytest.approx(51.962e-6, rel=1e-6)
    assert prog["median_s"] == pytest.approx(19.101e-6, rel=1e-6)


def test_busy_is_the_union_of_operations_and_no_more_than_the_window(reduced):
    # the async copies overlap the fusion they feed: a sum would count
    # them twice, the union does not
    assert 0 < reduced["busy_s"] <= reduced["programs"]["jit_small_step"][
        "seconds"] + 1e-9
    assert reduced["busy_s"] < reduced["window_s"]
    # first module start to last module end: 21.72 ms, nearly all of it
    # the host's sleep
    assert reduced["window_s"] == pytest.approx(21.7237e-3, rel=1e-3)


def test_top_operations_and_gaps_by_span(reduced):
    ops = reduced["device_ops"]
    assert ops[0][0] == "%fusion f32[1024,1024]" and len(ops) <= 10
    assert ops == sorted(ops, key=lambda kv: -kv[1])
    gaps = reduced["idle_gaps"]
    assert gaps and len(gaps) <= 10
    # the long gap is the host's sleep between two executions; the
    # others are nanoseconds between one execution's operations
    assert gaps[0][0] == "jit_small_step>jit_small_step[n=2,max=0.020848]"
    assert gaps[1][0].startswith("inside:jit_small_step[n=8,")
    assert sum(g[1] for g in gaps) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)


def test_a_trace_with_no_device_plane_reads_nothing():
    class Empty:
        planes = []

    out = xplane.reduce_profile(Empty())
    assert out["busy_s"] == 0.0 and out["programs"] == {}
