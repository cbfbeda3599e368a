"""The control of each cell comes out as not correct where the program
comes out correct: the plain reference, computed one precision below
what the configuration states, in the program's place. At the cells'
own sizes this is `benchmark/study.py` on the chip (readings in
PERF.md); here the same code at a size a test run can hold."""

import json

import pytest

from benchmark import study
from benchmark.correct import verdict
from benchmark.tests.conftest import SERVE_LIMITS, TRAIN_LIMITS


def rows(capsys, monkeypatch, tmp_path, cell, seeds, *extra):
    monkeypatch.setattr(study, "ROOT", str(tmp_path))
    assert study.main(["--workload", cell, "--seeds", seeds, *extra]) == 0
    out = [json.loads(line) for line in
           capsys.readouterr().out.strip().splitlines()]
    assert [str(r["seed"]) for r in out] == seeds.split(",")
    return out


def test_fp8_control_and_half_batch_fail_training(tiny, capsys, monkeypatch,
                                                  tmp_path):
    for r in rows(capsys, monkeypatch, tmp_path, "tiny-train", "31"):
        assert verdict(r["program"], TRAIN_LIMITS)[0], r["program"]
        assert not verdict(r["control_fp8"], TRAIN_LIMITS)[0], r
        assert not verdict(r["fault_half_batch"], TRAIN_LIMITS)[0], r


def test_fp8_control_and_altered_token_fail_serving(tiny, capsys,
                                                     monkeypatch, tmp_path):
    for r in rows(capsys, monkeypatch, tmp_path, "tiny-serve", "31,32",
                  "--seconds", "1.0"):
        assert r["failed"] == 0 and r["finished"] > 0
        assert verdict(r["program"], SERVE_LIMITS)[0], r["program"]
        assert not verdict(r["control_fp8"], SERVE_LIMITS)[0], r
        assert not verdict(r["fault_token_altered"], SERVE_LIMITS)[0]
