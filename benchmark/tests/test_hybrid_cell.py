"""The configuration with a per-slot state on the CPU: a tiny cell of
it through `run.main` traced and untraced, the control and the fault of
its study, its readers on a synthetic trace, and its count functions
against a hand count at the published widths."""

import copy
import json
import os

import pytest

from benchmark import kda_scopes, roofline, run, study
from benchmark.correct import verdict
from benchmark.reference import kimi_linear as ref
from benchmark.tests.conftest import ROOT, _json, last_line

CELL = "kimi-linear-reason-closed64"
# float32 on the CPU: the program sits within rounding of the reference
# (a router near-tie aside: none on these seeds); fp8 operands read 0.1
# and more
LIMITS = {"served_logit_gap": 1e-3, "served_logit_gap_p99": 1e-4}
TINY = {
    "workloads/tiny-hybrid.json": dict(
        _json("workloads", f"{CELL}.json"), name="tiny-hybrid",
        config="tiny-kimi", traffic="tiny-closed", limits=LIMITS,
        trace_steps=4, trace_settle_steps=2,
        engine={"max_slots": 4, "page_size": 8, "n_pages": None,
                "max_ctx": 64,
                "engine_kwargs": {"max_prefills_per_step": 1}}),
    "configs/tiny-kimi.json": dict(
        _json("configs", "kimi-linear-48b-a3b.json"), vocab_size=512,
        hidden_size=64, num_attention_heads=4, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        intermediate_size=128, moe_intermediate_size=32,
        router_experts=8, experts_held=[0, 1, 2, 5], num_experts=4,
        num_experts_per_token=2, num_experts_per_tok=2,
        num_hidden_layers=4,
        linear_attn_config={"kda_layers": [1, 2, 3],
                            "full_attn_layers": [4], "num_heads": 4,
                            "head_dim": 16, "short_conv_kernel_size": 4},
        constructor={"param_dtype": "float32"},
        # 0.02 x sqrt(2304 / 64): the products' gain at the real widths;
        # taps of order one as at the real widths (0.12 x 4 = 0.02 x 25)
        init={"w_std": 0.12, "conv_scale": 4.0}),
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
    assert run.main(["--workload", "tiny-hybrid", "--seed", str(2**31 + 7),
                     "--seconds", "1.5", "--trace", "0"]) == 0
    res = last_line(capsys)
    assert res["correct"] is True, res["compared"]
    assert set(res["metrics"]) == SERVE_METRICS
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0


def test_traced_run_reports_the_counters_readers(tiny, capsys):
    assert run.main(["--workload", "tiny-hybrid", "--seed", "11",
                     "--seconds", "1.5", "--trace", "1"]) == 0
    res = last_line(capsys)
    got = res["metrics"]
    assert got["compiles_in_window.serve"]["value"] == 0
    # every decoding row advances its state, and a chunk's rows beside
    # them: more than the rows a step emits, under slots + a page
    rows = got["state_rows_per_step.serve"]["value"]
    assert 100 * rows / 4 > got["slot_occupancy.serve"]["value"]
    assert rows < 4 + 8
    assert 1.0 <= got["expert_load_skew.serve"]["value"] <= 4.0
    assert 0 < got["mfu.serve"]["value"] < 100
    # no TPU plane in a CPU trace: the trace's readers return nothing
    for name in ("kda_ms.serve", "kda_chunk_ms.serve", "kda_roofline.serve",
                 "moe_ms.serve", "mla_attn_roofline.serve"):
        assert name not in got
    assert set(res["end_to_end"]) == SERVE_METRICS


def test_fp8_control_and_altered_token_fail_where_the_program_passes(
        tiny, capsys, monkeypatch, tmp_path):
    monkeypatch.setattr(study, "ROOT", str(tmp_path))
    assert study.main(["--workload", "tiny-hybrid", "--seeds", "31,32",
                       "--seconds", "1.0"]) == 0
    rows = [json.loads(line) for line in
            capsys.readouterr().out.strip().splitlines()]
    assert [r["seed"] for r in rows] == [31, 32]
    for r in rows:
        assert r["failed"] == 0 and r["finished"] > 0
        for side in ("program", "control_fp8", "witness_bfloat16",
                     "fault_token_altered"):
            assert set(r[side]) == {*LIMITS, "correct"}
            assert r[side]["correct"] == verdict(r[side], LIMITS)[0]
        assert r["program"]["correct"], r["program"]
        assert not r["control_fp8"]["correct"], r
        assert r["control_fp8"]["served_logit_gap_p99"] \
            > LIMITS["served_logit_gap_p99"], r
        assert not r["fault_token_altered"]["correct"], r


# ------------------------------------------------- the readers, synthetic
def _facts(scopes, delta, program="jit_decode_fn", n=4):
    cfg = _json("configs", "kimi-linear-48b-a3b.json")
    return {"config": cfg, "delta": delta, "mean_context": 3000.0,
            "reference": ref, "chips": 1,
            "peaks": roofline.device_peaks("TPU v5 lite"),
            "kda_scope_times": {program: {
                "n": n, "scopes": {k: v * n for k, v in scopes.items()}}}}


def _reader(name):
    return lambda facts: run.read_layer_metric(name, facts)


def test_scope_of_knows_the_state_layers_scopes_and_leaves_the_rest():
    assert kda_scopes.scope_of(
        "jit(decode_fn)/kda/state/reduce_sum") == "kda/state"
    assert kda_scopes.scope_of("jit(chunk_fn)/kda/proj/dot_general") \
        == "kda/proj"
    assert kda_scopes.scope_of(
        "jit(decode_fn)/moe/experts/dot_general") == "moe/experts"
    assert kda_scopes.scope_of("jit(decode_fn)/attn_out/mul") == "attn_out"
    assert kda_scopes.scope_of("jit(decode_fn)/convert") == "(unscoped)"


def test_new_readers_on_a_synthetic_step():
    """60 active rows a step; the state layers took 4.4 ms of the step,
    and 3 ms of a chunk."""
    steps = 10
    delta = {"steps": steps, "tokens_total": 60 * steps,
             "state_rows": 110 * steps}
    scopes = {"kda/proj": 0.7e-3, "kda/conv": 0.2e-3, "kda/state": 3.2e-3,
              "kda/out": 0.3e-3, "moe/experts": 9e-3, "attn": 2e-3}
    facts = _facts(scopes, delta)
    assert _reader("kda_ms.serve")(facts) == pytest.approx(4.4)
    assert _reader("kda_chunk_ms.serve")(facts) is None
    # bytes bind: six layers' 39.46M matrix parameters at 2 bytes and 60
    # rows' state (2,244,608 bytes a layer) read and written = 2.09 GB
    # at 819 GB/s = 2.55 ms of the 4.4
    nbytes = 6 * (39_460_864 * 2 + 2 * 60 * 2_244_608)
    assert _reader("kda_roofline.serve")(facts) == pytest.approx(
        100 * nbytes / 819e9 / 4.4e-3)
    assert _reader("state_rows_per_step.serve")(facts) == pytest.approx(110)
    chunk = _facts({"kda/state": 2e-3, "kda/proj": 1e-3}, delta,
                   "jit_chunk_fn")
    assert _reader("kda_chunk_ms.serve")(chunk) == pytest.approx(3.0)


def test_new_readers_return_nothing_where_there_is_nothing_to_read():
    facts = _facts({"attn": 1e-3, "moe/experts": 1e-3},
                   {"steps": 5, "tokens_total": 100})
    for name in ("kda_ms.serve", "kda_chunk_ms.serve", "kda_roofline.serve",
                 "state_rows_per_step.serve"):
        assert _reader(name)(facts) is None
    assert _reader("prefix_chunk_skip_share.serve")(facts) is None
    assert _reader("prefix_chunk_skip_share.serve")(dict(facts, delta={
        "prefix_hits": 32, "prefill_chunks": 3})) == pytest.approx(32 / 35)


# --------------------------------------------------- counts, by hand
def test_published_widths_by_hand():
    cfg = _json("configs", "kimi-linear-48b-a3b.json")
    h, c = 2304, 32 * 128
    kda = 3 * h * c + c * h + 2 * (h * 128 + 128 * c) + h * 32
    assert ref.kda_params(cfg) == kda == 39_460_864
    mla = h * 32 * 192 + h * 576 + 512 * 32 * 256 + c * h
    assert ref.attn_params(cfg) == mla == 29_114_368
    expert = 3 * h * 1024
    assert ref.expert_params(cfg) == expert == 7_077_888
    moe = 64 * expert + expert + h * 256
    small = 6 * (h + 3 * 4 * c + c + 32 + 128) + 2 * (h + 512) \
        + 8 * h + 7 * 256 + h
    total = 6 * kda + 2 * mla + 3 * h * 9216 + 7 * moe + 2 * 40960 * h \
        + small
    assert ref.n_params(cfg) == total
    assert 3.76e9 < total < 3.78e9                     # 3.77B
    assert 7.53e9 < 2 * total < 7.56e9                 # 7.55 GB, bfloat16
    assert ref.cell_bytes(cfg) == 1152
    # a slot, a layer: 32 matrices of 128 x 128 and three inputs of the
    # convolution, float32
    assert ref.state_bytes(cfg) == 4 * (32 * 128 * 128 + 3 * 3 * c) \
        == 2_244_608
    # one token at 3,000 live positions: 8 x 64 / 256 = 2 routed experts
    # a layer, the recurrence 7 operations an element of S
    through = 6 * kda + 2 * mla + 3 * h * 9216 + 40960 * h \
        + 7 * (h * 256 + expert + 2 * expert)
    assert ref.flops_per_token(cfg, 3000) == pytest.approx(
        2 * through + 6 * 7 * 32 * 128 * 128
        + 2 * 2 * 32 * (192 + 128) * 3000)
    flops, nbytes = ref.kda_step(cfg, 60)
    assert flops == pytest.approx(6 * 60 * (2 * kda + 7 * 32 * 128 * 128))
    assert nbytes == pytest.approx(6 * (2 * kda + 120 * 2_244_608))
    # a step: of 64 held experts a layer the 64 * (1 - (31/32)**60) =
    # 54.5 that 60 rows of 8 in 256 hit at the mean
    assert ref.experts_hit(cfg, 60) == pytest.approx(54.47, abs=1e-2)
    matrices = total - small - 40960 * h
    assert ref.decode_step_bytes(cfg, 180_000, 60) == pytest.approx(
        2 * (matrices - 7 * (64 - ref.experts_hit(cfg, 60)) * expert)
        + 180_060 * 2 * 1152 + 2 * 60 * 6 * 2_244_608)
    flops, nbytes = ref.mla_step(cfg, 60, 180_000)
    assert nbytes == pytest.approx(2 * (2 * mla + 180_060 * 1152))


def test_config_file_keeps_every_published_width():
    """Every number of the catalog's `config` stands under the same key,
    or the key is in `reduced` with the published value beside it."""
    cfg = _json("configs", "kimi-linear-48b-a3b.json")
    catalog = os.path.join(os.sep, "opt", "skills", "guides",
                           "model-configs", "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    lin, pub = cfg["linear_attn_config"], row["config"]["linear_attn_config"]
    for key in ("head_dim", "num_heads", "short_conv_kernel_size"):
        assert lin[key] == pub[key]          # no width inside the group
    assert lin["kda_layers"] == [i for i in pub["kda_layers"] if i <= 8]
    assert lin["full_attn_layers"] == [i for i in pub["full_attn_layers"]
                                       if i <= 8]
    assert cfg["router_experts"] == row["config"]["num_experts"]
    assert len(cfg["experts_held"]) == cfg["num_experts"]
    bench = _json("..", "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"]


def test_cell_fits_the_contract_of_the_harness():
    """The files are found by name, and the traffic's warm-up covers the
    first round's chunks with a fifth to spare."""
    from benchmark.traffic import generate

    cell = _json("workloads", f"{CELL}.json")
    mix = generate.load(cell["traffic"])
    page = cell["engine"]["page_size"]
    lists = generate.requests(mix, 3, 40960)
    first = sum(-(-len(reqs[0]["prompt"]) // page) for reqs in lists)
    assert first == 689 and first * 1.2 <= mix["warmup_steps"] <= first * 1.25
    longest = max(len(r["prompt"]) + r["max_new"] for reqs in lists
                  for r in reqs)
    assert longest <= cell["engine"]["max_ctx"]
    assert all(0 <= t < 40960 for reqs in lists for r in reqs
               for t in r["prompt"])
    assert cell["engine"]["n_pages"] == 1 + 64 * 8192 // page
    assert os.path.exists(os.path.join(ROOT, "benchmark", "drivers",
                                       "serve_hybrid.py"))
