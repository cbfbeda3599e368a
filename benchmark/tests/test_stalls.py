"""Every serving run says how many stalls it had, on standard error
only: a step made to sleep is counted, named by its engine step, and
the result line is what it is without the sleep."""

import json
import re
import time

import pytest

from benchmark import run
from benchmark.drivers import serve

SLOW_STEP = 40          # past tiny-closed's warm-up of 8 steps


def test_stalls_are_the_turns_over_three_times_the_median():
    laps = [(n, 0.020, 0.001) for n in range(100, 200)]
    laps[17] = (117, 0.150, 0.001)      # inside `step_once`
    laps[60] = (160, 0.020, 0.090)      # around it: the clients' turn
    laps[80] = (180, 0.055, 0.001)      # long, under the mark
    stalled, median, under = serve.stalls(laps)
    assert [n for n, _ in stalled] == [117, 160]
    assert [s for _, s in stalled] == pytest.approx([0.151, 0.110])
    assert (median, under) == pytest.approx((0.021, 0.056))


def _run(capsys):
    assert run.main(["--workload", "tiny-serve", "--seed", "13",
                     "--seconds", "1.0", "--trace", "0"]) == 0
    io = capsys.readouterr()
    (line,) = io.out.strip().splitlines()
    stalled = re.search(r"^stalls, window steps over 3 times the median of "
                        r"[\d.]+ s: (\d+), ([\d.]+) s in all \(engine step, "
                        r"s\): ([\d. ,]*);", io.err, re.M)
    return json.loads(line), int(stalled[1]), float(stalled[2]), {
        int(part.split()[0]): float(part.split()[1])
        for part in stalled[3].split(", ") if part}, io.err


def _shape(res: dict):
    """The result line but for what the clocks read: its keys in their
    order, every metric's name and unit, the verdict and its limits.
    `attempted` is the count of a window that closes by the clock."""
    return (list(res), res["correct"], res["failed"],
            [(k, m["unit"]) for k, m in res["metrics"].items()],
            res["device"], [(k, v[1]) for k, v in res["compared"].items()])


def test_a_slept_step_is_counted_and_the_result_line_is_as_without(
        tiny, monkeypatch, capsys):
    from deeplearning4j_tpu.serving.continuous import DecodeEngine

    plain, _, _, _, err = _run(capsys)
    # an untraced run logs the window's phase table and the deal's rest
    assert "host phases over the window's" in err and "fetch" in err
    assert "requests left to send at the close" in err
    assert "'kv_pages_gathered'" in err and "'kv_pages_live'" in err

    sound = DecodeEngine.step_once

    def slow(self):
        out = sound(self)
        if self.stats()["steps"] == SLOW_STEP:
            time.sleep(0.05)
        return out

    monkeypatch.setattr(DecodeEngine, "step_once", slow)
    slept, count, total, by_step, _ = _run(capsys)
    assert count == len(by_step) >= 1 and SLOW_STEP in by_step
    assert 0.05 <= by_step[SLOW_STEP] and by_step[SLOW_STEP] <= total
    assert _shape(slept) == _shape(plain)
