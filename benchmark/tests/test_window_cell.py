"""The cell `laguna-code-closed32` on the CPU: a tiny cell of its
configuration through `run.main` traced and untraced, the control and
the fault of its study, its new readers on a synthetic trace, its count
functions against a hand count at the published widths, its files
against the published config and the harness's contract."""

import copy
import json
import re

import pytest

from benchmark import roofline, run, scope_times, study, swa_scopes, timeline
from benchmark.correct import verdict
from benchmark.reference import laguna as ref
from benchmark.tests.conftest import ROOT, _json, last_line

CELL = "laguna-code-closed32"
CONFIG = "laguna-s-2.1"
# float32 on the CPU: the program sits within rounding of the reference
# (a router near-tie aside: none on these seeds); fp8 operands read 0.1
# and more
LIMITS = {"served_logit_gap": 1e-3, "served_logit_gap_p99": 1e-4}
TINY = {
    "workloads/tiny-window.json": dict(
        _json("workloads", f"{CELL}.json"), name="tiny-window",
        config="tiny-laguna", traffic="tiny-closed", limits=LIMITS,
        trace_steps=4, trace_settle_steps=2,
        engine={"max_slots": 4, "page_size": 8, "n_pages": None,
                "max_ctx": 64,
                "engine_kwargs": {"max_prefills_per_step": 1}}),
    "configs/tiny-laguna.json": dict(
        _json("configs", f"{CONFIG}.json"), vocab_size=512,
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, intermediate_size=128, moe_intermediate_size=16,
        shared_expert_intermediate_size=16, router_experts=16,
        num_experts=4, experts_held=[0, 1, 2, 3], num_experts_per_tok=3,
        sliding_window=16, num_attention_heads_per_layer=[4, 6, 6, 6, 4],
        constructor={"param_dtype": "float32"},
        # 0.02 x sqrt(3072 / 64): the products' gain at the real widths
        init={"w_std": 0.14}),
    "traffic/tiny-closed.json": {
        "kind": "requests", "loop": "closed", "clients": 4,
        "requests_per_client": 40, "prompt_tokens": [4, 40],
        "output_tokens": [4, 16], "shared_prefix": 0, "warmup_steps": 8},
}
SERVE_METRICS = {"decode_tok_per_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}
NEW = {"swa_ms.serve", "swa_roofline.serve"}


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
    assert run.main(["--workload", "tiny-window", "--seed", str(2**33 + 5),
                     "--seconds", "1.5", "--trace", "0"]) == 0
    res = last_line(capsys)
    assert res["correct"] is True, res["compared"]
    assert set(res["metrics"]) == SERVE_METRICS
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0


def test_traced_run_reports_the_counters_readers(tiny, capsys):
    assert run.main(["--workload", "tiny-window", "--seed", "11",
                     "--seconds", "1.5", "--trace", "1"]) == 0
    res = last_line(capsys)
    got = res["metrics"]
    assert got["compiles_in_window.serve"]["value"] == 0
    # every decoding row writes its rings, and a chunk's rows beside
    # them: more than the rows a step emits, under slots + a chunk
    rows = got["state_rows_per_step.serve"]["value"]
    assert 100 * rows / 4 > got["slot_occupancy.serve"]["value"]
    assert rows < 4 + 64
    assert 1.0 <= got["expert_load_skew.serve"]["value"] <= 4.0
    assert 0 < got["mfu.serve"]["value"] < 100
    # no TPU plane in a CPU trace: the trace's readers return nothing
    for name in NEW | {"gqa_attn_roofline.serve", "moe_ms.serve",
                       "moe_roofline.serve", "kv_read_ms.serve"}:
        assert name not in got
    assert set(res["end_to_end"]) == SERVE_METRICS


def test_fp8_control_and_altered_token_fail_where_the_program_passes(
        tiny, capsys, monkeypatch, tmp_path):
    monkeypatch.setattr(study, "ROOT", str(tmp_path))
    assert study.main(["--workload", "tiny-window", "--seeds", "31,32",
                       "--seconds", "1.0"]) == 0
    rows = [json.loads(line) for line in
            capsys.readouterr().out.strip().splitlines()]
    assert [r["seed"] for r in rows] == [31, 32]
    for r in rows:
        assert r["failed"] == 0 and r["finished"] > 0
        for side in ("program", "control_fp8", "witness_bfloat16",
                     "fault_gate_dropped", "fault_token_altered"):
            assert set(r[side]) == {*LIMITS, "correct"}
            assert r[side]["correct"] == verdict(r[side], LIMITS)[0]
        assert r["program"]["correct"], r["program"]
        assert not r["control_fp8"]["correct"], r
        assert not r["fault_gate_dropped"]["correct"], r
        assert not r["fault_token_altered"]["correct"], r


# ------------------------------------------------- the readers, synthetic
def _facts(scopes, delta, program="jit_decode_fn", n=4):
    cfg = _json("configs", f"{CONFIG}.json")
    table = {program: {"n": n, "seconds": sum(scopes.values()) * n,
                       "scopes": {k: v * n for k, v in scopes.items()}}}
    return {"config": cfg, "delta": delta, "mean_context": 4000.0,
            "reference": ref, "chips": 1,
            "peaks": roofline.device_peaks("TPU v5 lite"),
            "timeline": {"device": table}, "scope_times": table,
            "swa_scope_times": table}


def _reader(name):
    return lambda facts: run.read_layer_metric(name, facts)


def test_no_window_scope_is_one_another_table_knows():
    """`scope_times.scope_of` keeps the innermost KNOWN token, so a part
    named like a known scope would be filed there (`swa/attn` under
    `attn`): none is, the window layers' time is `(unscoped)` in both
    other tables and `swa/<part>` in this one, and the model names no
    other part."""
    with open(f"{ROOT}/deeplearning4j_tpu/zoo/window_moe.py") as f:
        used = set(re.findall(r'named_scope\("swa/([a-z_]+)"\)', f.read()))
    assert used == set(swa_scopes.PARTS)
    known = set(timeline.SERVE_SCOPES + timeline.TRAIN_KINDS
                + timeline.TRAIN_SCOPES + scope_times.SCOPES
                + scope_times.GROUPS)
    assert not (set(swa_scopes.PARTS) | {swa_scopes.GROUP}) & known
    for part in swa_scopes.PARTS:
        op = f"jit(decode_fn)/swa/{part}/dot_general"
        assert timeline.scope_of(op) == timeline.UNSCOPED
        assert scope_times.scope_of(op) == timeline.UNSCOPED
        assert swa_scopes.scope_of(op) == f"swa/{part}"
    for scope in ("qkv", "kv_write", "kv_read", "attn", "attn_out"):
        assert swa_scopes.scope_of(f"jit(decode_fn)/{scope}/mul") == scope


def test_new_readers_on_a_synthetic_step():
    """30 active rows a step whose three window layers attended 46,080
    ring cells (512 a row and layer); the window layers took 2 ms of the
    step."""
    steps = 10
    delta = {"steps": steps, "tokens_total": 30 * steps,
             "window_cells_live": 46_080 * steps}
    scopes = {"swa/proj": 1.0e-3, "swa/ring_write": 0.1e-3,
              "swa/ring_read": 0.1e-3, "swa/mix": 0.3e-3,
              "swa/out": 0.5e-3, "moe/experts": 2e-3, "qkv": 0.3e-3,
              "kv_read": 8e-3, "attn": 8e-3, "attn_out": 0.5e-3}
    facts = _facts(scopes, delta)
    assert _reader("swa_ms.serve")(facts) == pytest.approx(2.0)
    # bytes bind: three layers' 63.14M matrix parameters at 2 bytes, the
    # 46,080 cells read and 90 written of 4,096 bytes
    nbytes = 3 * 63_135_744 * 2 + (46_080 + 90) * 4096
    assert _reader("swa_roofline.serve")(facts) == pytest.approx(
        100 * nbytes / 819e9 / 2e-3)


def test_new_readers_return_nothing_where_there_is_nothing_to_read():
    """A program without these layers (the parent's, another model's):
    the readers return None and do not raise."""
    facts = _facts({"moe/experts": 1e-3, "attn": 1e-3},
                   {"steps": 5, "tokens_total": 100})
    for name in NEW:
        assert _reader(name)(facts) is None
    facts = _facts({"swa/mix": 1e-3}, {"steps": 5, "tokens_total": 100})
    assert _reader("swa_ms.serve")(facts) == pytest.approx(1.0)
    assert _reader("swa_roofline.serve")(facts) is None
    facts = _facts({}, {"steps": 5, "tokens_total": 100}, n=0)
    for name in NEW:
        assert _reader(name)(facts) is None


# --------------------------------------------------- counts, by hand
def test_published_widths_by_hand():
    cfg = _json("configs", f"{CONFIG}.json")
    h, d = 3072, 128
    full = h * 48 * d + 2 * h * 8 * d + 48 * d * h + h * 48
    window = h * 72 * d + 2 * h * 8 * d + 72 * d * h + h * 72
    assert ref.attn_params(cfg, 48) == full == 44_187_648
    assert ref.attn_params(cfg, 72) == window == 63_135_744
    expert = 3 * h * 1024
    assert ref.expert_params(cfg) == expert == 9_437_184
    fixed = h * 256 + 3 * h * 1024                 # router + shared
    dense = 3 * h * 12288
    vocab = 12544 * h
    gains = 5 * 2 * h + h
    total = 2 * full + 3 * window + dense + 4 * (fixed + 32 * expert) \
        + 2 * vocab + gains
    assert ref.n_params(cfg) == total
    assert 1.716e9 < total < 1.718e9                   # 1.717B
    assert 3.43e9 < 2 * total < 3.44e9                 # 3.43 GB, bfloat16
    # a token, an attention layer: a K row and a V row of 8 x 128
    assert ref.cell_bytes(cfg) == 4096
    # one token at 4,000 live positions: 10 x 32 / 256 experts a layer
    through = 2 * full + 3 * window + dense + vocab \
        + 4 * (fixed + 1.25 * expert)
    assert ref.flops_per_token(cfg, 4000) == pytest.approx(
        2 * through + 2 * 2 * 48 * 256 * 4000 + 3 * 2 * 72 * 256 * 512)
    # 32 rows of 10 in 256 reach 23 of the 32 held experts
    assert ref.experts_hit(cfg, 32) == pytest.approx(23.06, abs=1e-2)
    matrices = total - gains - vocab
    assert ref.decode_step_bytes(cfg, 128_000, 32) == pytest.approx(
        2 * (matrices - 4 * (32 - ref.experts_hit(cfg, 32)) * expert)
        + (2 * 128_000 + 3 * 32 * 512 + 5 * 32) * 4096)
    flops, nbytes = ref.gqa_step(cfg, 30, 120_000)
    assert flops == pytest.approx(
        2 * (2 * 30 * full + 2 * 48 * 256 * 120_000))
    assert nbytes == pytest.approx(2 * (2 * full + 120_030 * 4096))
    flops, nbytes = ref.swa_step(cfg, 30, 46_080)
    assert flops == pytest.approx(3 * 2 * 30 * window
                                  + 2 * 72 * 256 * 46_080)
    assert nbytes == pytest.approx(3 * 2 * window + (46_080 + 90) * 4096)
    flops, nbytes = ref.moe_step(cfg, 30, 4 * 30 * 1.25, 4 * 22.0)
    assert flops == pytest.approx(2 * (150 * expert + 4 * 30 * fixed))
    assert nbytes == pytest.approx(2 * (88 * expert + 4 * fixed))


def test_the_memory_reckoning():
    """Weights, pool and rings of the cell fill the chip between the
    floor of 25% and the ceiling of 92% of its 15.75 GiB before the
    programs' temporaries (the decode step's widest needs 2.70 GB more:
    the two planes of a full layer's gathered window, compiled for a
    described v5e)."""
    cfg = _json("configs", f"{CONFIG}.json")
    eng = _json("workloads", f"{CELL}.json")["engine"]
    weights = 2 * ref.n_params(cfg)
    pages = eng["max_ctx"] // eng["page_size"]
    assert eng["n_pages"] == 1 + eng["max_slots"] * pages == 2561
    pool = 2 * 2 * eng["n_pages"] * eng["page_size"] * ref.cell_bytes(cfg) // 2
    rings = 3 * eng["max_slots"] * 512 * ref.cell_bytes(cfg)
    gathered = 2 * eng["max_slots"] * eng["max_ctx"] * ref.cell_bytes(cfg)
    assert pool == pytest.approx(2.685e9, rel=1e-3)
    assert rings == pytest.approx(0.201e9, rel=1e-2)
    assert gathered == pytest.approx(2.684e9, rel=1e-3)
    chip = 15.75 * 2**30
    assert 0.25 < (weights + pool + rings) / chip
    assert (weights + pool + rings + gathered) / chip < 0.92


def _published():
    """The published `config.json` of Laguna-S-2.1 (the configuration's
    `source`), the keys that give its shape."""
    rope = {"full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}}
    return {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
        "intermediate_size": 12288, "num_hidden_layers": 48,
        "num_attention_heads": 48, "num_key_value_heads": 8,
        "head_dim": 128, "max_position_embeddings": 1048576,
        "attention_bias": False, "rms_norm_eps": 1e-06, "num_experts": 256,
        "num_experts_per_tok": 10, "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [0],
        "tie_word_embeddings": False, "gating": "per-head",
        "sliding_window": 512, "rope_parameters": rope,
        "layer_types": (["full_attention"] + ["sliding_attention"] * 3)
        * 12,
        "moe_apply_router_weight_on_input": False,
        "mlp_layer_types": ["dense"] + ["sparse"] * 47,
        "gating_types": ["per_head"] * 48,
        "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
        "moe_router_logit_softcapping": 0}


def test_config_file_keeps_every_published_width():
    """Every key of the published config stands under the same key, or
    the key is in `reduced` with the published value beside it; `reduced`
    is exactly the keys changed, and names no width."""
    cfg = _json("configs", f"{CONFIG}.json")
    pub = _published()
    changed = [k for k, v in pub.items() if cfg[k] != v]
    assert sorted(cfg["reduced"]) == sorted(changed)
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "mlp_layer_types",
                              "num_attention_heads_per_layer",
                              "gating_types", "num_experts", "vocab_size"]
    for key, value in pub.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    # the cut: published layers 0-4, the leading dense full layer and a
    # whole period of three window layers to one full one
    assert cfg["num_hidden_layers"] == 5
    for key in ("layer_types", "mlp_layer_types", "gating_types",
                "num_attention_heads_per_layer"):
        assert cfg[key] == pub[key][:5], key
    assert cfg["first_k_dense_replace"] == 1
    # 32 of 256 experts held (8 chips share a layer); an eighth of the
    # vocabulary
    assert cfg["experts_held"] == list(range(32)) and cfg["num_experts"] == 32
    assert cfg["router_experts"] == pub["num_experts"] == 256
    assert cfg["vocab_size"] * 8 == pub["vocab_size"]
    assert cfg["constructor"] == {"param_dtype": "bfloat16"}
    bench = _json("..", "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"


def test_cell_fits_the_contract_of_the_harness():
    """The files are found by name, the engine is the one stated, the
    traffic's warm-up covers the first round's chunks with a fifth to
    spare, and the new metrics list exactly the new cell."""
    from benchmark.traffic import generate

    cell = _json("workloads", f"{CELL}.json")
    assert cell["engine"] == {
        "max_slots": 32, "page_size": 128, "n_pages": 2561,
        "max_ctx": 10240, "engine_kwargs": {"max_prefills_per_step": 1}}
    mix = generate.load(cell["traffic"])
    assert (mix["clients"], mix["requests_per_client"]) == (32, 12)
    page = cell["engine"]["page_size"]
    lists = generate.requests(mix, 3, 12544)
    first = sum(-(-len(reqs[0]["prompt"]) // page) for reqs in lists)
    assert first == 695 and first * 1.2 <= mix["warmup_steps"] <= first * 1.25
    sizes = [(len(r["prompt"]), r["max_new"]) for reqs in lists
             for r in reqs]
    assert all(512 <= p <= 8192 and 256 <= n <= 2048 for p, n in sizes)
    assert max(p + n for p, n in sizes) == 9349 <= cell["engine"]["max_ctx"]
    assert all(0 <= t < 12544 for reqs in lists for r in reqs
               for t in r["prompt"])
    assert mix["shared_prefix"] == 0
    # the traced slice ends before the window and holds chunks (the
    # deal's replay: every one of the warm-up's last 50 steps has one)
    stop = mix["warmup_steps"] - cell["trace_settle_steps"]
    assert mix["warmup_steps"] - 50 <= stop - cell["trace_steps"]
    bench = _json("..", "BENCHMARK.json")
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert (m["source"], m["layer"], m["moves"]) == (
                "device_trace", "kernels", "decode_tok_per_s")
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert NEW | {"gqa_attn_roofline.serve", "moe_ms.serve",
                  "moe_roofline.serve", "expert_load_skew.serve",
                  "experts_read_share.serve", "state_rows_per_step.serve",
                  "kv_read_ms.serve", "decode_step_roofline.serve",
                  "window_step_ms.serve", "window_chunk_ms.serve",
                  "decode_tok_per_s", "ttft_p95_ms", "tpot_p95_ms"} <= listed
    assert not {m for m in listed if m.startswith(("mla_", "kda_", "conv_",
                                                   "prefix_"))}
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["why"] == cell["why"] and len(entry["why"]) <= 200
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, cell["traffic"], 1)
