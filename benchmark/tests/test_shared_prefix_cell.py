"""`gpt2m-shared-prefix-closed32` on the CPU: its files against the
harness's contract, and a tiny cell of its kind through `run.main`, so
that the trie's share of the prompt pages is read where requests share
a prefix and the served tokens are still the reference's."""

import copy

import pytest

from benchmark import roofline, run
from benchmark.tests.conftest import TINY as BASE
from benchmark.tests.conftest import _json, last_line

CELL = "gpt2m-shared-prefix-closed32"
TINY = {
    "workloads/tiny-shared.json": dict(
        BASE["workloads/tiny-serve.json"], name="tiny-shared",
        traffic="tiny-shared"),
    "configs/tiny-gpt2.json": BASE["configs/tiny-gpt2.json"],
    # two tenants' prefixes of four pages of 8, tails of one or two
    "traffic/tiny-shared.json": {
        "kind": "requests", "loop": "closed", "clients": 4,
        "requests_per_client": 40, "prompt_tokens": [4, 16],
        "output_tokens": [4, 12], "shared_prefix": 32, "tenants": 2,
        "warmup_steps": 12},
}


@pytest.fixture
def tiny(monkeypatch):
    import jax
    from benchmark.traffic import generate

    files = copy.deepcopy(TINY)
    monkeypatch.setattr(run, "load_json",
                        lambda *parts: files["/".join(parts)])
    monkeypatch.setattr(generate, "load",
                        lambda name: files[f"traffic/{name}.json"])
    monkeypatch.setattr(run, "require_chips",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "place_cache", lambda: None)
    cell_metrics = run.cell_metrics
    monkeypatch.setattr(run, "cell_metrics", lambda cell: cell_metrics(CELL))
    monkeypatch.setitem(roofline.PEAKS, jax.devices()[0].device_kind,
                        {"flops": 1e12, "bytes_per_s": 1e11,
                         "source": "test"})
    return files


def test_traced_run_reads_the_tries_share_and_is_correct(tiny, capsys):
    assert run.main(["--workload", "tiny-shared", "--seed", str(2**31 + 9),
                     "--seconds", "1.5", "--trace", "1"]) == 0
    res = last_line(capsys)
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0
    got = res["metrics"]
    # four of a prompt's five or six pages are a tenant's once cached
    assert 0.5 < got["prefix_chunk_skip_share.serve"]["value"] < 0.85
    assert got["compiles_in_window.serve"]["value"] == 0


def test_cell_fits_the_contract_of_the_harness():
    """The engine is the chat cell's, the deal fits GPT-2's window, and
    the warm-up covers the first round's chunks (one whole prompt and
    31 tails behind tenant 0's cached prefix) with a fifth to spare."""
    from benchmark.traffic import generate

    cell = _json("workloads", f"{CELL}.json")
    chat = _json("workloads", "gpt2m-chat-closed32.json")
    for key in ("config", "engine", "limits", "sample_rows", "chips"):
        assert cell[key] == chat[key]
    mix = generate.load(cell["traffic"])
    assert (mix["shared_prefix"], mix["tenants"]) == (512, 4)
    lists = generate.requests(mix, 3, 50257)
    page = cell["engine"]["page_size"]
    first = [len(reqs[0]["prompt"]) for reqs in lists]
    # every client's first request is tenant 0's
    assert len({tuple(reqs[0]["prompt"][:512]) for reqs in lists}) == 1
    chunks = -(-first[0] // page) + sum(-(-(n - 512) // page)
                                        for n in first[1:])
    assert chunks == 122
    assert chunks * 1.2 <= mix["warmup_steps"] <= chunks * 1.25
    assert all(528 <= len(r["prompt"]) <= 576 and 16 <= r["max_new"] <= 64
               for reqs in lists for r in reqs)
    assert max(len(r["prompt"]) + r["max_new"] for reqs in lists
               for r in reqs) <= 1024
    bench = _json("..", "BENCHMARK.json")
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "prefix_chunk_skip_share.serve")
    assert entry["workloads"] == [CELL] and entry["moves"] == "ttft_p95_ms"
