"""`tpot_p95_ms` is end to end in the serving cells whose runs repeat it
within its bound, and per layer (`tpot_p95_ms.serve`) in the GPT-2
cells, whose steps are so short that the host's stalls move it past any
bound the contract allows (PERF.md, section 2). Every per-layer metric
names, under `moves`, an end-to-end metric each of its cells reports."""

import importlib.util
import os

import pytest

from benchmark.tests.conftest import ROOT, _json

GPT2 = {"gpt2m-chat-closed32", "gpt2m-shared-prefix-closed32",
        "gpt2m-unshared-prefix-closed32"}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader", os.path.join(ROOT, "benchmark", "layer_metrics",
                               f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _listed(bench, cell):
    return {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
            if cell in m.get("workloads", [cell])}


def test_the_reader_gives_the_windows_tail_and_nothing_where_there_is_none():
    read = _reader("tpot_p95_ms.serve")
    assert read({"end_to_end": {"tpot_p95_ms": 7.4037}}) == 7.4037
    assert read({"end_to_end": {"tpot_p95_ms": float("nan")}}) is None
    assert read({"end_to_end": {}}) is None


def test_each_serving_cell_reports_tpot_once_end_to_end_or_per_layer():
    bench = _json("..", "BENCHMARK.json")
    serving = [w["name"] for w in bench["workloads"]
               if _json("configs", f"{w['config']}.json")["driver"]
               .startswith("serve")]
    assert GPT2 <= set(serving)
    for cell in serving:
        listed = _listed(bench, cell)
        per_layer = cell in GPT2
        assert ("tpot_p95_ms.serve" in listed) == per_layer, cell
        assert ("tpot_p95_ms" in listed) != per_layer, cell
        assert {"decode_tok_per_s", "ttft_p95_ms", "setup_s"} <= listed


@pytest.mark.parametrize("kind", ["per_layer", "end_to_end"])
def test_every_metric_lists_cells_that_exist(kind):
    bench = _json("..", "BENCHMARK.json")
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench[kind]:
        assert set(m.get("workloads", cells)) <= cells, m["name"]
        if kind == "per_layer":
            assert os.path.exists(os.path.join(
                ROOT, "benchmark", "layer_metrics", f"{m['name']}.py"))


def test_every_per_layer_metric_moves_what_each_of_its_cells_reports():
    bench = _json("..", "BENCHMARK.json")
    e2e = {m["name"]: set(m.get("workloads",
                                [w["name"] for w in bench["workloads"]]))
           for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        assert set(m["workloads"]) <= e2e[m["moves"]], m["name"]


def test_the_end_to_end_bounds_stand():
    """Taking the GPT-2 cells out of `tpot_p95_ms` loosens nothing."""
    bench = _json("..", "BENCHMARK.json")
    bounds = {m["name"]: m["bound"] for m in bench["end_to_end"]}
    assert bounds == {"train_img_per_s": 0.01, "decode_tok_per_s": 0.075,
                      "ttft_p95_ms": 0.1, "tpot_p95_ms": 0.1,
                      "setup_s": 0.1}
