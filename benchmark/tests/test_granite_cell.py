"""The cell `granite-h-chat-closed128` on the CPU: a tiny cell of its
configuration through `run.main` traced and untraced, the control and
the fault of its study, its new readers on a synthetic trace and the
program's own scopes, its count functions against a hand count at the
published widths, its files against the published config and the
harness's contract."""

import copy
import json

import pytest

from benchmark import group_scopes, roofline, run, scope_times, study, timeline
from benchmark.correct import verdict
from benchmark.reference import granite_hybrid as ref
from benchmark.tests.conftest import _json, last_line

CELL = "granite-h-chat-closed128"
CONFIG = "granite-4.0-h-small"
# float32 on the CPU: the program sits within rounding of the reference;
# the reference's products in float8 read 0.1 and more, and a state
# rounded to bfloat16 a token drifts by its last place and more
LIMITS = {"served_logit_gap": 1e-3, "served_logit_gap_p99": 1e-4,
          "state_gap": 1e-4}
TINY = {
    "workloads/tiny-granite.json": dict(
        _json("workloads", f"{CELL}.json"), name="tiny-granite",
        config="tiny-granite", traffic="tiny-closed", limits=LIMITS,
        trace_steps=4, trace_settle_steps=2, sample_rows=8,
        engine={"max_slots": 4, "page_size": 8, "n_pages": None,
                "max_ctx": 64,
                "engine_kwargs": {"max_prefills_per_step": 1}}),
    "configs/tiny-granite.json": dict(
        _json("configs", f"{CONFIG}.json"), vocab_size=512,
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=16, shared_intermediate_size=32,
        router_experts=16, num_local_experts=4, experts_held=[0, 1, 2, 3],
        num_experts_per_tok=3, num_hidden_layers=4,
        layer_types=["mamba", "mamba", "attention", "mamba"],
        mamba_n_heads=4, mamba_d_head=32, mamba_d_state=16,
        # logits of order one at these widths
        logits_scaling=1.0, attention_multiplier=0.25,
        constructor={"param_dtype": "float32"},
        # 0.02 x sqrt(4096 / 64): the products' gain at the real widths
        init={"w_std": 0.16, "embedding_std": 0.16 / 12,
              "conv_scale": 2.0}),
    "traffic/tiny-closed.json": {
        "kind": "requests", "loop": "closed", "clients": 4,
        "requests_per_client": 40, "prompt_tokens": [4, 40],
        "output_tokens": [4, 16], "shared_prefix": 0, "warmup_steps": 8},
}
SERVE_METRICS = {"decode_tok_per_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}
NEW = {"ssd_ms.serve", "ssd_chunk_ms.serve", "ssd_roofline.serve"}


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
    assert run.main(["--workload", "tiny-granite", "--seed", str(2**33 + 7),
                     "--seconds", "1.5", "--trace", "0"]) == 0
    res = last_line(capsys)
    assert res["correct"] is True, res["compared"]
    assert set(res["metrics"]) == SERVE_METRICS
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0


def test_traced_run_reports_the_counters_readers(tiny, capsys):
    assert run.main(["--workload", "tiny-granite", "--seed", "11",
                     "--seconds", "1.5", "--trace", "1"]) == 0
    res = last_line(capsys)
    got = res["metrics"]
    assert got["compiles_in_window.serve"]["value"] == 0
    # every decoding row advances its state, and a chunk's rows beside
    # them: more than the rows a step emits, under slots + a chunk
    rows = got["state_rows_per_step.serve"]["value"]
    assert 100 * rows / 4 > got["slot_occupancy.serve"]["value"]
    assert rows < 4 + 64
    assert 1.0 <= got["expert_load_skew.serve"]["value"] <= 4.0
    assert 0 < got["experts_read_share.serve"]["value"] <= 1.0
    assert 0 < got["mfu.serve"]["value"] < 100
    # no TPU plane in a CPU trace: the trace's readers return nothing
    for name in NEW | {"gqa_attn_roofline.serve", "moe_ms.serve",
                       "moe_roofline.serve", "kv_read_ms.serve"}:
        assert name not in got
    assert set(res["end_to_end"]) == SERVE_METRICS


def test_controls_and_faults_fail_where_the_program_passes(
        tiny, capsys, monkeypatch, tmp_path):
    """Float8 operands turn some served tokens over, and an altered
    token reads its whole gap; the reference in bfloat16 and each fault
    of the program's state (rounded to bfloat16 a step, or dropped)
    read the state's drift; the program sits within rounding."""
    monkeypatch.setattr(study, "ROOT", str(tmp_path))
    assert study.main(["--workload", "tiny-granite", "--seeds", "31",
                       "--seconds", "1.0"]) == 0
    rows = [json.loads(line) for line in
            capsys.readouterr().out.strip().splitlines()]
    assert [r["seed"] for r in rows] == [31]
    for r in rows:
        assert r["failed"] == 0 and r["finished"] > 0
        for side in ("program", "control_bfloat16", "control_state_bfloat16",
                     "witness_fp8", "fault_token_altered",
                     "fault_state_bfloat16", "fault_state_dropped"):
            assert set(r[side]) == {*LIMITS, "correct"}
            assert r[side]["correct"] == verdict(r[side], LIMITS)[0]
        assert r["program"]["correct"], r["program"]
        for side in ("control_bfloat16", "control_state_bfloat16",
                     "witness_fp8", "fault_token_altered",
                     "fault_state_bfloat16", "fault_state_dropped"):
            assert not r[side]["correct"], (side, r)
        # the faults leave the served tokens alone: the state reads them
        assert r["fault_state_bfloat16"]["state_gap"] > 1e-3
        assert r["fault_state_dropped"]["state_gap"] > 0.3
        assert r["program"]["state_gap"] < 1e-5


# ------------------------------------------------- the readers, synthetic
def _facts(scopes, delta, program="jit_decode_fn", n=4):
    cfg = _json("configs", f"{CONFIG}.json")
    table = {program: {"n": n, "seconds": sum(scopes.values()) * n,
                       "scopes": {k: v * n for k, v in scopes.items()}}}
    return {"config": cfg, "delta": delta, "mean_context": 1500.0,
            "reference": ref, "chips": 1,
            "peaks": roofline.device_peaks("TPU v5 lite"),
            "timeline": {"device": table}, "scope_times": table,
            "ssd_scope_times": table}


def _reader(name):
    return lambda facts: run.read_layer_metric(name, facts)


PARTS = ("in_proj", "conv", "scan", "out")


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_both_programs_name_the_four_parts_and_no_other(program):
    """The operations of the compiled programs (the tiny configuration's,
    on the CPU) carry `ssd/<part>` for exactly the four parts, and the
    scope table files each under its part; `scope_times`' table knows
    none of them, so the layers' time is nowhere else."""
    import re
    from types import SimpleNamespace

    from benchmark.drivers import serve_granite
    from deeplearning4j_tpu.zoo.mamba_moe import MambaMoETransformer

    cfg = TINY["configs/tiny-granite.json"]
    cell = TINY["workloads/tiny-granite.json"]
    prog = serve_granite.build(SimpleNamespace(config=cfg, cell=cell))
    model = prog.model
    assert isinstance(model, MambaMoETransformer)
    model.params = None
    model.init()
    rec = next(r for r in prog.lint_records() if r.name.startswith(
        "decode_step" if program == "decode" else "decode_prefill"))
    import jax

    text = jax.jit(rec.fn).lower(*rec.example_args).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    parts = {group_scopes.scope_of(n, "ssd") for n in names}
    assert {p for p in parts if p.startswith("ssd/")} == {
        f"ssd/{p}" for p in PARTS}
    for p in PARTS:
        op = f"jit(decode_fn)/ssd/{p}/dot_general"
        assert scope_times.scope_of(op) == timeline.UNSCOPED
        assert group_scopes.scope_of(op, "ssd") == f"ssd/{p}"
    for scope in ("qkv", "kv_write", "kv_read", "attn", "attn_out"):
        assert group_scopes.scope_of(f"jit(decode_fn)/{scope}/mul",
                                     "ssd") == scope


def test_new_readers_on_a_synthetic_step():
    """96 active rows a step; the nine Mamba-2 layers took 12 ms of the
    decode step and 6 ms of a chunk."""
    steps = 10
    delta = {"steps": steps, "tokens_total": 96 * steps}
    scopes = {"ssd/in_proj": 1.5e-3, "ssd/conv": 0.5e-3, "ssd/scan": 9e-3,
              "ssd/out": 1.0e-3, "moe/experts": 2e-3, "attn": 1e-3}
    facts = _facts(scopes, delta)
    assert _reader("ssd_ms.serve")(facts) == pytest.approx(12.0)
    assert _reader("ssd_chunk_ms.serve")(facts) is None
    # bytes bind: nine layers' 102.2M matrix parameters at 2 bytes, and
    # 96 rows' state of 4,295,680 bytes read and written
    nbytes = 9 * (102_236_160 * 2 + 2 * 96 * 4_295_680)
    assert _reader("ssd_roofline.serve")(facts) == pytest.approx(
        100 * nbytes / 819e9 / 12e-3)
    chunk = _facts({"ssd/scan": 6e-3}, delta, program="jit_chunk_fn")
    assert _reader("ssd_chunk_ms.serve")(chunk) == pytest.approx(6.0)


def test_new_readers_return_nothing_where_there_is_nothing_to_read():
    """A program without these layers (the parent's, another model's):
    the readers return None and do not raise."""
    facts = _facts({"moe/experts": 1e-3, "attn": 1e-3},
                   {"steps": 5, "tokens_total": 100})
    for name in NEW:
        assert _reader(name)(facts) is None
    facts = _facts({}, {"steps": 5, "tokens_total": 100}, n=0)
    for name in NEW:
        assert _reader(name)(facts) is None
    # another model's reference counts no such layers
    from benchmark.reference import lfm2_moe

    facts = dict(_facts({"ssd/scan": 1e-3},
                        {"steps": 5, "tokens_total": 100}),
                 reference=lfm2_moe)
    assert _reader("ssd_roofline.serve")(facts) is None


# --------------------------------------------------- counts, by hand
def test_published_widths_by_hand():
    cfg = _json("configs", f"{CONFIG}.json")
    h, inner, n, heads = 4096, 8192, 128, 128
    mamba = h * (2 * inner + 2 * n + heads) + inner * h
    assert ref.mamba_params(cfg) == mamba == 102_236_160
    attn = 2 * h * 128 * (32 + 8)
    assert ref.attn_params(cfg) == attn == 41_943_040
    expert = 3 * h * 768
    assert ref.expert_params(cfg) == expert == 9_437_184
    fixed = h * 72 + 3 * h * 1536                     # router + shared
    vocab = 25088 * h
    channels = inner + 2 * n
    vectors = 4 * channels + channels + 3 * heads + inner   # taps, ...
    gains = 9 * (2 * h) + 2 * h + h
    total = 9 * (mamba + vectors) + attn + 10 * (fixed + 18 * expert) \
        + vocab + gains
    assert ref.n_params(cfg) == total
    assert 2.955e9 < total < 2.957e9                   # 2.956B
    assert 5.91e9 < 2 * total < 5.92e9                 # 5.91 GB, bfloat16
    # a slot and layer: the 128 matrices of 64 x 128 and 3 rows of 8,448
    assert ref.state_bytes(cfg) == 4 * (heads * 64 * n + 3 * channels) \
        == 4_295_680
    assert ref.cell_bytes(cfg) == 4096
    # 128 rows of 10 in 72 reach every one of the 18 held experts
    assert ref.experts_hit(cfg, 128) == pytest.approx(18.0, abs=1e-6)
    matrices = 9 * mamba + attn + 10 * (fixed + 18 * expert) + vocab
    step = ref.decode_step_bytes(cfg, 96 * 1500, 96)
    assert step == pytest.approx(
        2 * matrices + (96 * 1500 + 96) * 4096 + 2 * 96 * 9 * 4_295_680)
    # 96 rows at a mean context of 1,500: 13.9 GB, the state 53% of it;
    # 128 rows beside 0.4 GB of K/V rows: 16.2 GB, the state 61%
    assert 13.9e9 < step < 14.0e9
    assert 0.53 < ref.state_share(cfg, 96 * 1500, 96) < 0.54
    assert 16.1e9 < ref.decode_step_bytes(cfg, 97_656, 128) < 16.3e9
    assert 0.60 < ref.state_share(cfg, 97_656, 128) < 0.62
    flops, nbytes = ref.ssd_step(cfg, 96)
    scan = 2 * 4 * channels + channels + 5 * heads * 64 * n + 4 * inner
    assert flops == pytest.approx(9 * 96 * (2 * mamba + scan))
    assert nbytes == pytest.approx(9 * (2 * mamba + 2 * 96 * 4_295_680))
    flops, nbytes = ref.gqa_step(cfg, 96, 150_000)
    assert flops == pytest.approx(2 * 96 * attn + 2 * 32 * 256 * 150_000)
    assert nbytes == pytest.approx(2 * attn + (150_000 + 96) * 4096)
    flops, nbytes = ref.moe_step(cfg, 96, 10 * 96 * 2.5, 10 * 18.0)
    assert flops == pytest.approx(2 * (2400 * expert + 10 * 96 * fixed))
    assert nbytes == pytest.approx(2 * (180 * expert + 10 * fixed))
    through = matrices - 10 * (18 - 2.5) * expert
    assert ref.flops_per_token(cfg, 1500) == pytest.approx(
        2 * through + 9 * scan + 2 * 32 * 256 * 1500)


def test_the_memory_reckoning():
    """Weights, pool and state of the cell fill the chip well past the
    floor of 25% of its 15.75 GiB; with the widest decode step's
    gathered window and its zeroed copy (four planes of the attention
    layer's rows, 3.0 GiB) they stay under it. The pool keeps the pages
    of 128 slots (the fallback to 96 kept the rest of the engine)."""
    cfg = _json("configs", f"{CONFIG}.json")
    eng = _json("workloads", f"{CELL}.json")["engine"]
    weights = 2 * ref.n_params(cfg)
    pages = eng["max_ctx"] // eng["page_size"]
    assert eng["n_pages"] == 1 + 128 * pages == 4097
    assert eng["max_slots"] * pages < eng["n_pages"]
    pool = 2 * eng["n_pages"] * eng["page_size"] * ref.cell_bytes(cfg) // 2
    state = 9 * eng["max_slots"] * ref.state_bytes(cfg)
    window = 4 * eng["max_slots"] * eng["max_ctx"] * ref.cell_bytes(cfg) // 2
    assert pool == pytest.approx(2.148e9, rel=1e-3)
    assert state == pytest.approx(3.711e9, rel=1e-3)
    chip = 15.75 * 2**30
    assert 0.25 < (weights + pool + state) / chip
    assert (weights + pool + state + window) / chip < 1.0


def _published():
    """The published `config.json` of Granite-4.0-H-Small (the
    configuration's `source`), the keys that give its shape."""
    kinds = ["mamba"] * 5 + ["attention"] + ["mamba"] * 9 + ["attention"] \
        + ["mamba"] * 9 + ["attention"] + ["mamba"] * 9 + ["attention"] \
        + ["mamba"] * 4
    return {
        "attention_bias": False, "attention_multiplier": 0.0078125,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 768, "layer_types": kinds,
        "logits_scaling": 16, "mamba_chunk_size": 256,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 128, "mamba_proj_bias": False,
        "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 10, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 72,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 1536, "tie_word_embeddings": True,
        "vocab_size": 100352}


def test_config_file_keeps_every_published_width():
    """Every key of the published config stands under the same key, or
    the key is in `reduced` with the published value beside it; `reduced`
    is exactly the keys changed, and names no width."""
    cfg = _json("configs", f"{CONFIG}.json")
    pub = _published()
    assert len(pub["layer_types"]) == 40
    assert [i for i, k in enumerate(pub["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    changed = [k for k, v in pub.items() if cfg[k] != v]
    assert sorted(cfg["reduced"]) == sorted(changed)
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "num_local_experts", "vocab_size"]
    for key, value in pub.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    # the cut: published layers 0-9, one whole period
    assert cfg["num_hidden_layers"] == 10
    assert cfg["layer_types"] == pub["layer_types"][:10]
    assert cfg["first_k_dense_replace"] == 0
    # 18 of 72 experts held (4 chips share a layer); a quarter of the
    # vocabulary
    assert cfg["experts_held"] == list(range(18))
    assert cfg["num_local_experts"] == 18
    assert cfg["router_experts"] == pub["num_local_experts"] == 72
    assert cfg["vocab_size"] * 4 == pub["vocab_size"]
    assert cfg["constructor"] == {"param_dtype": "bfloat16"}
    bench = _json("..", "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"


def test_cell_fits_the_contract_of_the_harness():
    """The files are found by name, the engine is the one stated, the
    traffic is `chat-2k-closed128` as it stands, and the new metrics
    list exactly the new cell."""
    cell = _json("workloads", f"{CELL}.json")
    assert cell["engine"] == {
        "max_slots": 96, "page_size": 128, "n_pages": 4097,
        "max_ctx": 4096, "engine_kwargs": {"max_prefills_per_step": 1}}
    assert cell["traffic"] == "chat-2k-closed128"
    bench = _json("..", "BENCHMARK.json")
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert (m["source"], m["layer"]) == ("device_trace", "kernels")
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert NEW | {"gqa_attn_roofline.serve", "moe_ms.serve",
                  "moe_roofline.serve", "expert_load_skew.serve",
                  "experts_read_share.serve", "state_rows_per_step.serve",
                  "kv_read_ms.serve", "decode_step_roofline.serve",
                  "window_step_ms.serve", "window_chunk_ms.serve",
                  "peak_hbm_share.serve", "decode_tok_per_s", "ttft_p95_ms",
                  "tpot_p95_ms"} <= listed
    assert not {m for m in listed if m.startswith(("mla_", "kda_", "conv_",
                                                   "swa_", "prefix_"))}
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["why"] == cell["why"] and len(entry["why"]) <= 200
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "chat-2k-closed128", 1)
