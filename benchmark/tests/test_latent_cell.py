"""The latent-attention, sparse-expert configuration on the CPU: a tiny
cell of it through `run.main` traced and untraced, the control and the
fault of its study, its readers on a synthetic trace, and its count
functions against a hand count at the published widths."""

import copy
import json
import os

import numpy as np
import pytest

from benchmark import roofline, run, scope_times, study
from benchmark.correct import verdict
from benchmark.reference import pangu_ultra_moe as ref
from benchmark.tests.conftest import ROOT, _json, last_line

CELL = "pangu-ultra-chat-closed32"
# float32 on the CPU: the program sits within rounding of the reference
# (a router near-tie aside: none on these seeds); fp8 operands read 0.1
# and more
LIMITS = {"served_logit_gap": 1e-3, "served_logit_gap_p99": 1e-4}
TINY = {
    "workloads/tiny-latent.json": dict(
        _json("workloads", f"{CELL}.json"), name="tiny-latent",
        config="tiny-pangu", traffic="tiny-closed", limits=LIMITS,
        trace_steps=4, trace_settle_steps=2,
        engine={"max_slots": 4, "page_size": 8, "n_pages": None,
                "max_ctx": 64,
                "engine_kwargs": {"max_prefills_per_step": 1}}),
    "configs/tiny-pangu.json": dict(
        _json("configs", "pangu-ultra-moe-718b.json"), vocab_size=512,
        hidden_size=64, num_attention_heads=4, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
        router_experts=8, experts_held=[0, 1, 2, 5], n_routed_experts=4,
        num_experts_per_tok=2, num_hidden_layers=3,
        constructor={"param_dtype": "float32"},
        # 0.02 x sqrt(7680 / 64): the products' gain at the real widths
        init={"w_std": 0.2}),
    "traffic/tiny-closed.json": {
        "kind": "requests", "loop": "closed", "clients": 4,
        "requests_per_client": 40, "prompt_tokens": [4, 24],
        "output_tokens": [4, 16], "shared_prefix": 0, "warmup_steps": 8},
}
SERVE_METRICS = {"decode_tok_per_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}


@pytest.fixture
def tiny(monkeypatch):
    """`conftest.tiny`'s pattern for this configuration: run.py and the
    generator find the tiny files, the chip is whatever jax has, and
    the cell has the metrics `BENCHMARK.json` lists for the real one."""
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


def test_untraced_run_is_correct_and_prints_the_end_to_end_metrics(
        tiny, capsys):
    assert run.main(["--workload", "tiny-latent", "--seed", str(2**31 + 7),
                     "--seconds", "1.5", "--trace", "0"]) == 0
    res = last_line(capsys)
    assert res["correct"] is True, res["compared"]
    assert set(res["metrics"]) == SERVE_METRICS
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0


def test_traced_run_reports_the_counters_readers(tiny, capsys):
    assert run.main(["--workload", "tiny-latent", "--seed", "11",
                     "--seconds", "1.5", "--trace", "1"]) == 0
    res = last_line(capsys)
    got = res["metrics"]
    assert got["compiles_in_window.serve"]["value"] == 0
    # 4 of 8 experts held, 2 a token: a pair falls on a held expert about
    # half the time; the most loaded of four is above their mean
    assert 1.0 <= got["expert_load_skew.serve"]["value"] <= 4.0
    assert 0 < got["mfu.serve"]["value"] < 100
    # no TPU plane in a CPU trace: the trace's readers return nothing
    for name in ("moe_ms.serve", "moe_roofline.serve",
                 "mla_attn_roofline.serve", "kv_read_ms.serve"):
        assert name not in got
    assert set(res["end_to_end"]) == SERVE_METRICS


def test_fp8_control_and_altered_token_fail_where_the_program_passes(
        tiny, capsys, monkeypatch, tmp_path):
    monkeypatch.setattr(study, "ROOT", str(tmp_path))
    assert study.main(["--workload", "tiny-latent", "--seeds", "31,32",
                       "--seconds", "1.0"]) == 0
    rows = [json.loads(line) for line in
            capsys.readouterr().out.strip().splitlines()]
    assert [r["seed"] for r in rows] == [31, 32]
    for r in rows:
        assert r["failed"] == 0 and r["finished"] > 0
        # the study runs each side through the harness's own comparison
        # under the cell's limits, and says so
        for side in ("program", "control_fp8", "fault_token_altered"):
            assert set(r[side]) == {*LIMITS, "correct"}
            assert r[side]["correct"] == verdict(r[side], LIMITS)[0]
        assert r["program"]["correct"], r["program"]
        assert not r["control_fp8"]["correct"], r
        # rounding every product moves most tokens, so the 99th
        # percentile alone refuses the control; one altered token of a
        # few hundred is refused by the widest gap
        assert r["control_fp8"]["served_logit_gap_p99"] \
            > LIMITS["served_logit_gap_p99"], r
        assert not r["fault_token_altered"]["correct"], r


# ------------------------------------------------- the readers, synthetic
def _facts(scopes, delta, n=4):
    cfg = _json("configs", "pangu-ultra-moe-718b.json")
    return {"config": cfg, "delta": delta, "mean_context": 1500.0,
            "reference": ref, "chips": 1,
            "peaks": roofline.device_peaks("TPU v5 lite"),
            "scope_times": {"jit_decode_fn": {
                "n": n, "scopes": {k: v * n for k, v in scopes.items()}}}}


def _reader(name):
    return lambda facts: run.read_layer_metric(name, facts)


def test_scope_of_takes_the_innermost_known_scope():
    assert scope_times.scope_of(
        "jit(decode_fn)/moe/experts/dot_general") == "moe/experts"
    assert scope_times.scope_of("jit(decode_fn)/q_proj/mul") == "q_proj"
    assert scope_times.scope_of(
        "jit(decode_fn)/attn_out/moe/shared/add") == "moe/shared"
    assert scope_times.scope_of("jit(decode_fn)/convert") == "(unscoped)"


def test_new_readers_on_a_synthetic_step():
    """32 active rows a step over 4 expert layers: 1,024 pairs, 64 on
    held experts that touch 40 of the 64 held; the expert layers took
    8 ms and the attention scopes 4 ms of the step."""
    steps = 10
    delta = {"steps": steps, "tokens_total": 32 * steps,
             "moe_assignments": 1024 * steps,
             "moe_assignments_held": 64 * steps,
             "moe_max_held_load": 12 * steps, "moe_experts_hit": 40 * steps}
    scopes = {"moe/router": 0.5e-3, "moe/experts": 6.5e-3,
              "moe/shared": 1.0e-3, "q_proj": 1e-3, "kv_proj": 0.5e-3,
              "kv_read": 1e-3, "attn": 1e-3, "attn_out": 0.5e-3,
              "head": 0.3e-3}
    facts = _facts(scopes, delta)
    assert _reader("moe_ms.serve")(facts) == pytest.approx(8.0)
    # bytes bind: 40 experts of 94.4 MB + 4 x (shared 94.4 MB + router
    # 3.9 MB) = 4.17 GB at 819 GB/s = 5.09 ms of the 8
    assert _reader("moe_roofline.serve")(facts) == pytest.approx(
        100 * (2 * (40 * 47_185_920 + 4 * (47_185_920 + 7680 * 256))
               / 819e9) / 8e-3)
    # 5 layers of 196.6M parameters at 2 bytes + 32 x 1,500 live rows of
    # 1,152 bytes a layer = 2.24 GB: 2.74 ms of the 4
    got = _reader("mla_attn_roofline.serve")(facts)
    nbytes = 5 * (196_575_232 * 2 + (48_000 + 32) * 1152)
    assert got == pytest.approx(100 * nbytes / 819e9 / 4e-3)
    assert _reader("expert_load_skew.serve")(facts) == pytest.approx(
        12 * 16 / 64)


def test_new_readers_return_nothing_where_there_is_nothing_to_read():
    facts = _facts({"attn": 1e-3, "qkv": 1e-3},
                   {"steps": 5, "tokens_total": 100})
    for name in ("moe_ms.serve", "moe_roofline.serve",
                 "expert_load_skew.serve"):
        assert _reader(name)(facts) is None
    facts["scope_times"] = {}
    assert _reader("mla_attn_roofline.serve")(facts) is None


# --------------------------------------------------- counts, by hand
def test_published_widths_by_hand():
    cfg = _json("configs", "pangu-ultra-moe-718b.json")
    h = 7680
    attn = (h * 1536 + 1536 * 128 * 192 + h * 576 + 512 * 128 * 256
            + 128 * 128 * h)
    assert ref.attn_params(cfg) == attn == 196_575_232
    expert = 3 * h * 2048
    assert ref.expert_params(cfg) == expert == 47_185_920
    moe = 16 * expert + expert + h * 256
    gains = 5 * (4 * h + 1536 + 512) + h
    total = 5 * attn + 3 * h * 18432 + 4 * moe + 2 * 19200 * h + gains
    assert ref.n_params(cfg) == total
    assert 4.91e9 < total < 4.93e9                     # 4.92B
    assert 9.83e9 < 2 * total < 9.85e9                 # 9.84 GB, bfloat16
    assert ref.cell_bytes(cfg) == 1152
    # one token at 1,000 live positions: 0.5 routed experts a layer
    through = 5 * attn + 3 * h * 18432 + 19200 * h \
        + 4 * (h * 256 + expert + 0.5 * expert)
    assert ref.flops_per_token(cfg, 1000) == pytest.approx(
        2 * through + 5 * 2 * 128 * (192 + 128) * 1000)
    # a step: every matrix a row reaches once (of 16 held experts a
    # layer the 16 * (1 - (31/32)**32) = 10.21 that 32 rows of 8 in 256
    # hit at the mean), the live rows once, one written a slot
    assert ref.experts_hit(cfg, 32) == pytest.approx(10.2065, abs=1e-3)
    assert ref.experts_hit(cfg, 512) == pytest.approx(16.0, abs=1e-5)
    assert ref.decode_step_bytes(cfg, 32_000, 32) == pytest.approx(
        2 * (total - gains - 19200 * h - 4 * (16 - 16 * (1 - (31 / 32) ** 32)) * expert)
        + 32_032 * 5 * 1152)


def test_config_file_keeps_every_published_width():
    """Every number of the catalog's `config` stands under the same key,
    or the key is in `reduced` with the published value beside it."""
    cfg = _json("configs", "pangu-ultra-moe-718b.json")
    catalog = os.path.join(os.sep, "opt", "skills", "guides",
                           "model-configs", "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "openPangu-Ultra-MoE-718B")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert cfg["router_experts"] == row["config"]["n_routed_experts"]
    assert len(cfg["experts_held"]) == cfg["n_routed_experts"]
    bench = _json("..", "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"]


def test_cell_fits_the_contract_of_the_harness():
    """The files are found by name, and the traffic's warm-up covers the
    first round's chunks with at least a fifth to spare (how far past
    that it goes is set by where the deal lets a window close: the
    traffic's `why`)."""
    from benchmark.traffic import generate

    cell = _json("workloads", f"{CELL}.json")
    mix = generate.load(cell["traffic"])
    page = cell["engine"]["page_size"]
    lists = generate.requests(mix, 3, 19200)
    first = sum(-(-len(reqs[0]["prompt"]) // page) for reqs in lists)
    assert first == 293 and first * 1.2 <= mix["warmup_steps"] <= first * 1.6
    longest = max(len(r["prompt"]) + r["max_new"] for reqs in lists
                  for r in reqs)
    assert longest <= cell["engine"]["max_ctx"]
    assert all(0 <= t < 19200 for reqs in lists for r in reqs
               for t in r["prompt"])
    assert os.path.exists(os.path.join(ROOT, "benchmark", "drivers",
                                       "serve_latent.py"))
