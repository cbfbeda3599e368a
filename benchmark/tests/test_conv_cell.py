"""The two cells of PR 36 on the CPU. `lfm2-moe-chat-closed128`: a tiny
cell of its configuration through `run.main` traced and untraced, the
control and the fault of its study, its readers on a synthetic trace,
its count functions against a hand count at the published widths, its
files against the catalog and the harness's contract.
`gpt2m-unshared-prefix-closed32`: its files against the cell it is the
control of."""

import copy
import json
import os

import pytest

from benchmark import roofline, run, study
from benchmark.correct import verdict
from benchmark.reference import lfm2_moe as ref
from benchmark.tests.conftest import ROOT, _json, last_line

CELL = "lfm2-moe-chat-closed128"
CONTROL = "gpt2m-unshared-prefix-closed32"
# float32 on the CPU: the program sits within rounding of the reference
# (a router near-tie aside: none on these seeds); fp8 operands read 0.1
# and more
LIMITS = {"served_logit_gap": 1e-3, "served_logit_gap_p99": 1e-4}
TINY = {
    "workloads/tiny-conv.json": dict(
        _json("workloads", f"{CELL}.json"), name="tiny-conv",
        config="tiny-lfm2", traffic="tiny-closed", limits=LIMITS,
        trace_steps=4, trace_settle_steps=2,
        engine={"max_slots": 4, "page_size": 8, "n_pages": None,
                "max_ctx": 64,
                "engine_kwargs": {"max_prefills_per_step": 1}}),
    "configs/tiny-lfm2.json": dict(
        _json("configs", "lfm2-24b-a2b.json"), vocab_size=512,
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=128, moe_intermediate_size=32,
        router_experts=8, num_experts=8, experts_held=list(range(8)),
        num_experts_per_tok=2, num_hidden_layers=5,
        layer_types=["conv", "full_attention", "conv", "conv", "conv"],
        constructor={"param_dtype": "float32"},
        # 0.02 x sqrt(2048 / 64): the products' gain at the real widths;
        # taps of order one as at the real widths (0.11 x 4.5 = 0.02 x 25)
        init={"w_std": 0.11, "conv_scale": 4.5}),
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
    assert run.main(["--workload", "tiny-conv", "--seed", str(2**31 + 7),
                     "--seconds", "1.5", "--trace", "0"]) == 0
    res = last_line(capsys)
    assert res["correct"] is True, res["compared"]
    assert set(res["metrics"]) == SERVE_METRICS
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0


def test_traced_run_reports_the_counters_readers(tiny, capsys):
    assert run.main(["--workload", "tiny-conv", "--seed", "11",
                     "--seconds", "1.5", "--trace", "1"]) == 0
    res = last_line(capsys)
    got = res["metrics"]
    assert got["compiles_in_window.serve"]["value"] == 0
    # every decoding row advances its tails, and a chunk's rows beside
    # them: more than the rows a step emits, under slots + a page
    rows = got["state_rows_per_step.serve"]["value"]
    assert 100 * rows / 4 > got["slot_occupancy.serve"]["value"]
    assert rows < 4 + 8
    # every expert is held: the skew is over all eight
    assert 1.0 <= got["expert_load_skew.serve"]["value"] <= 8.0
    assert 0 < got["mfu.serve"]["value"] < 100
    # no TPU plane in a CPU trace: the trace's readers return nothing
    for name in ("conv_ms.serve", "conv_roofline.serve",
                 "gqa_attn_roofline.serve", "moe_ms.serve",
                 "moe_roofline.serve", "kv_read_ms.serve"):
        assert name not in got
    assert set(res["end_to_end"]) == SERVE_METRICS


def test_fp8_control_and_altered_token_fail_where_the_program_passes(
        tiny, capsys, monkeypatch, tmp_path):
    monkeypatch.setattr(study, "ROOT", str(tmp_path))
    assert study.main(["--workload", "tiny-conv", "--seeds", "31,32",
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
    """Both scope tables a reader may ask (`timeline.analysis`'s and
    `scope_times.by_scope`'s), filled by hand."""
    cfg = _json("configs", "lfm2-24b-a2b.json")
    table = {program: {"n": n, "seconds": sum(scopes.values()) * n,
                       "scopes": {k: v * n for k, v in scopes.items()}}}
    return {"config": cfg, "delta": delta, "mean_context": 1200.0,
            "reference": ref, "chips": 1,
            "peaks": roofline.device_peaks("TPU v5 lite"),
            "timeline": {"device": table}, "scope_times": table}


def _reader(name):
    return lambda facts: run.read_layer_metric(name, facts)


def test_timelines_table_keeps_the_conv_scopes_apart():
    from benchmark import scope_times, timeline

    assert timeline.scope_of("jit(decode_fn)/conv/mix/mul") == "conv/mix"
    assert timeline.scope_of(
        "jit(chunk_fn)/conv/in_proj/dot_general") == "conv/in_proj"
    assert timeline.scope_of("jit(decode_fn)/kv_read/gather") == "kv_read"
    for scope in ("qkv", "kv_write", "kv_read", "attn", "attn_out"):
        assert scope_times.scope_of(f"jit(decode_fn)/{scope}/mul") == scope


def test_new_readers_on_a_synthetic_step():
    """120 active rows a step at 1,200 live positions each; the
    convolution layers took 1.5 ms of the step, attention 6 ms."""
    steps = 10
    delta = {"steps": steps, "tokens_total": 120 * steps}
    scopes = {"conv/in_proj": 0.9e-3, "conv/mix": 0.2e-3,
              "conv/out_proj": 0.4e-3, "moe/experts": 20e-3, "qkv": 0.3e-3,
              "kv_write": 0.1e-3, "kv_read": 2.6e-3, "attn": 2.5e-3,
              "attn_out": 0.5e-3, "mlp": 0.5e-3}
    facts = _facts(scopes, delta)
    assert _reader("conv_ms.serve")(facts) == pytest.approx(1.5)
    # bytes bind: seven layers' 16.78M matrix parameters at 2 bytes and
    # 120 rows' tails (16,384 bytes a layer) read and written
    nbytes = 7 * (16_777_216 * 2 + 2 * 120 * 16_384)
    assert _reader("conv_roofline.serve")(facts) == pytest.approx(
        100 * nbytes / 819e9 / 1.5e-3)
    # two layers' 10.49M projection parameters and 144,120 token rows of
    # 2,048 bytes, over the 6 ms under the five scopes
    nbytes = 2 * (10_485_760 * 2 + (120 * 1200 + 120) * 2048)
    assert _reader("gqa_attn_roofline.serve")(facts) == pytest.approx(
        100 * nbytes / 819e9 / 6e-3)


def test_new_readers_return_nothing_where_there_is_nothing_to_read():
    """A program without these layers (the parent's, another model's):
    the readers return None and do not raise."""
    facts = _facts({"moe/experts": 1e-3, "mlp": 1e-3},
                   {"steps": 5, "tokens_total": 100})
    for name in ("conv_ms.serve", "conv_roofline.serve",
                 "gqa_attn_roofline.serve"):
        assert _reader(name)(facts) is None
    facts = _facts({}, {"steps": 5, "tokens_total": 100}, n=0)
    for name in ("conv_ms.serve", "conv_roofline.serve",
                 "gqa_attn_roofline.serve"):
        assert _reader(name)(facts) is None


# --------------------------------------------------- counts, by hand
def test_published_widths_by_hand():
    cfg = _json("configs", "lfm2-24b-a2b.json")
    h = 2048
    conv = h * 3 * h + h * h
    assert ref.conv_params(cfg) == conv == 16_777_216
    attn = h * 32 * 64 + 2 * h * 8 * 64 + 32 * 64 * h
    assert ref.attn_params(cfg) == attn == 10_485_760
    expert = 3 * h * 1536
    assert ref.expert_params(cfg) == expert == 9_437_184
    moe = 64 * expert + h * 64
    small = 7 * (h + 3 * h) + 2 * (h + 64 + 64) + 9 * h + 8 * 64 + h
    total = 7 * conv + 2 * attn + 3 * h * 11776 + 8 * moe + 65536 * h + small
    assert ref.n_params(cfg) == total
    assert 5.17e9 < total < 5.19e9                     # 5.178B
    assert 10.34e9 < 2 * total < 10.37e9               # 10.36 GB, bfloat16
    # a token, an attention layer: a K row and a V row of 8 x 64 bfloat16
    assert ref.cell_bytes(cfg) == 2048
    # a slot, a convolution layer: two rows of 2,048 float32
    assert ref.state_bytes(cfg) == 16_384
    # one token at 1,200 live positions: 4 of 64 experts a layer, not
    # the 64 the program runs
    through = 7 * conv + 2 * attn + 3 * h * 11776 + 65536 * h \
        + 8 * (h * 64 + 4 * expert)
    assert ref.flops_per_token(cfg, 1200) == pytest.approx(
        2 * through + 7 * 8 * h + 2 * 2 * 32 * 128 * 1200)
    flops, nbytes = ref.conv_step(cfg, 120)
    assert flops == pytest.approx(7 * 120 * (2 * conv + 8 * h))
    assert nbytes == pytest.approx(7 * (2 * conv + 240 * 16_384))
    # 128 rows of 4 in 64 leave an expert out once in 3,900 layers
    assert ref.experts_hit(cfg, 128) == pytest.approx(63.98, abs=1e-2)
    matrices = total - small
    assert ref.decode_step_bytes(cfg, 144_000, 120) == pytest.approx(
        2 * (matrices - 8 * (64 - ref.experts_hit(cfg, 120)) * expert)
        + 144_120 * 2 * 2048 + 2 * 120 * 7 * 16_384)
    flops, nbytes = ref.gqa_step(cfg, 120, 144_000)
    assert flops == pytest.approx(
        2 * (2 * 120 * attn + 2 * 32 * 128 * 144_000))
    assert nbytes == pytest.approx(2 * (2 * attn + 144_120 * 2048))
    # every pair falls on a held expert; no shared expert in either count
    flops, nbytes = ref.moe_step(cfg, 120, 8 * 120 * 4, 8 * 63.9)
    assert flops == pytest.approx(2 * (3840 * expert + 8 * 120 * h * 64))
    assert nbytes == pytest.approx(2 * (8 * 63.9 * expert + 8 * h * 64))


def test_config_file_keeps_every_published_width():
    """Every number of the catalog's `config` stands under the same key,
    or the key is in `reduced` with the published value beside it."""
    cfg = _json("configs", "lfm2-24b-a2b.json")
    catalog = os.path.join(os.sep, "opt", "skills", "guides",
                           "model-configs", "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "layer_types"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    # the cut: published layers 1-9, a leading dense layer and two
    # whole periods, three convolutions to one attention after it
    assert cfg["layer_types"] == row["config"]["layer_types"][1:10]
    assert cfg["layer_types"][1:] == ["full_attention", "conv", "conv",
                                      "conv"] * 2
    assert cfg["num_hidden_layers"] == 9 == len(cfg["layer_types"])
    assert cfg["num_dense_layers"] == cfg["first_k_dense_replace"] == 1
    # every expert is held
    assert cfg["experts_held"] == list(range(64))
    assert cfg["router_experts"] == cfg["num_experts"] == 64
    bench = _json("..", "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_cell_fits_the_contract_of_the_harness():
    """The files are found by name, the engine is the issue's, and the
    traffic's warm-up covers the first round's chunks with a fifth to
    spare."""
    from benchmark.traffic import generate

    cell = _json("workloads", f"{CELL}.json")
    assert cell["engine"] == {
        "max_slots": 128, "page_size": 128, "n_pages": 4097,
        "max_ctx": 4096, "engine_kwargs": {"max_prefills_per_step": 1}}
    mix = generate.load(cell["traffic"])
    assert (mix["clients"], mix["requests_per_client"]) == (128, 16)
    page = cell["engine"]["page_size"]
    lists = generate.requests(mix, 3, 65536)
    first = sum(-(-len(reqs[0]["prompt"]) // page) for reqs in lists)
    assert first == 398 and first * 1.2 <= mix["warmup_steps"] <= first * 1.25
    sizes = [(len(r["prompt"]), r["max_new"]) for reqs in lists
             for r in reqs]
    assert all(64 <= p <= 1024 and 256 <= n <= 2048 for p, n in sizes)
    assert max(p + n for p, n in sizes) == 2913 <= cell["engine"]["max_ctx"]
    assert all(0 <= t < 65536 for reqs in lists for r in reqs
               for t in r["prompt"])
    assert cell["engine"]["n_pages"] == 1 + 128 * 4096 // page
    # the traced slice ends before the window and holds chunks (the
    # deal's replay: a chunk in engine steps 454-456 and 460-465)
    stop = mix["warmup_steps"] - cell["trace_settle_steps"]
    assert stop - cell["trace_steps"] <= 454 and 465 <= stop
    assert os.path.exists(os.path.join(ROOT, "benchmark", "drivers",
                                       "serve_conv.py"))
    bench = _json("..", "BENCHMARK.json")
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert {"conv_ms.serve", "conv_roofline.serve",
            "gqa_attn_roofline.serve", "moe_ms.serve", "moe_roofline.serve",
            "expert_load_skew.serve", "state_rows_per_step.serve",
            "kv_read_ms.serve", "decode_step_roofline.serve",
            "decode_tok_per_s", "ttft_p95_ms", "tpot_p95_ms"} <= listed
    assert not {m for m in listed if m.startswith(("mla_", "kda_",
                                                   "prefix_"))}


def test_the_control_cell_is_the_shared_prefix_cell_with_nothing_shared():
    """`gpt2m-unshared-prefix-closed32`: the engine, limits and trace
    settings of the cell it is the control of, the same prompt and
    output lengths with no tenant's prefix, a warm-up of the first
    round's chunks and a fifth, and a place in every list the shared
    cell is in."""
    from benchmark.traffic import generate

    cell = _json("workloads", f"{CONTROL}.json")
    shared = _json("workloads", "gpt2m-shared-prefix-closed32.json")
    for key in ("config", "engine", "limits", "sample_rows", "chips",
                "trace_steps", "trace_settle_steps"):
        assert cell[key] == shared[key]
    mix = generate.load(cell["traffic"])
    assert mix["shared_prefix"] == 0 and "tenants" not in mix
    assert (mix["clients"], mix["requests_per_client"]) == (32, 32)
    lists = generate.requests(mix, 3, 50257)
    page = cell["engine"]["page_size"]
    assert all(528 <= len(r["prompt"]) <= 576 and 16 <= r["max_new"] <= 64
               for reqs in lists for r in reqs)
    # nothing shared: no two clients' first prompts begin alike
    assert len({tuple(reqs[0]["prompt"][:16]) for reqs in lists}) == 32
    chunks = sum(-(-len(reqs[0]["prompt"]) // page) for reqs in lists)
    assert chunks == 1119
    assert chunks * 1.2 <= mix["warmup_steps"] <= chunks * 1.25
    assert max(len(r["prompt"]) + r["max_new"] for reqs in lists
               for r in reqs) <= 1024
    bench = _json("..", "BENCHMARK.json")
    for m in bench["per_layer"] + bench["end_to_end"]:
        names = m.get("workloads", [])
        assert (CONTROL in names) == ("gpt2m-shared-prefix-closed32"
                                      in names), m["name"]
