"""Tiny cells for the CPU: the harness end to end without a chip."""

import copy
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402


def _json(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


TINY = {
    "workloads/tiny-train.json": dict(
        _json("workloads", "resnet50-train-b256.json"),
        name="tiny-train", config="tiny-resnet", traffic="tiny-pool"),
    # float32 and a small rate: at 64 pixels and 16 images batch-norm
    # is so ill-conditioned that bfloat16 alone reads like a fault
    "configs/tiny-resnet.json": dict(
        _json("configs", "resnet50-imagenet.json"),
        image_size=64, num_classes=10,
        constructor=dict(_json("configs", "resnet50-imagenet.json")
                         ["constructor"], compute_dtype=None,
                         learning_rate=1e-3)),
    "traffic/tiny-pool.json": {"kind": "batch_pool", "batch": 16, "pool": 4},
    "workloads/tiny-serve.json": dict(
        _json("workloads", "gpt2m-chat-closed32.json"),
        name="tiny-serve", config="tiny-gpt2", traffic="tiny-closed",
        engine={"max_slots": 4, "page_size": 8, "n_pages": None,
                "engine_kwargs": {"max_prefills_per_step": 1}}),
    "configs/tiny-gpt2.json": dict(
        _json("configs", "gpt2-medium.json"), vocab_size=8192,
        n_positions=128, n_embd=64, n_layer=2, n_head=4, n_inner=256,
        # 0.02 x sqrt(1024 / 64): the blocks' gain of the real widths, so
        # the stream is the blocks' and not the tied embedding's echo
        init={"w_std": 0.08}),
    "traffic/tiny-closed.json": {
        "kind": "requests", "loop": "closed", "clients": 4,
        "requests_per_client": 40, "prompt_tokens": [4, 24],
        "output_tokens": [4, 16], "shared_prefix": 0, "warmup_steps": 8},
}
# float32 on the CPU sits within rounding of the reference at this size
# (seed 31: gaps of norms 0.002 and 0.005, noise 4e-5), where the scaled
# fp8 control reads 0.22, 0.18 and 0.77 and half a batch 0.70, 0.59, 1.5;
# the change rides on the chaos of three steps at this size (0.21 on one
# seed before the residual branches started small), so its limit is wide
TRAIN_LIMITS = {"loss_gap": 0.01, "grad_norm_gap": 0.1,
                "change_norm_gap": 0.5, "grad_noise_median": 0.01}
# the CPU's float32 program sits within rounding of the reference; a
# vocabulary of 8,192 has near-ties that bfloat16 turns over
SERVE_LIMITS = {"served_logit_gap": 1e-4}
TINY["workloads/tiny-train.json"]["limits"] = TRAIN_LIMITS
TINY["workloads/tiny-serve.json"]["limits"] = SERVE_LIMITS


@pytest.fixture(scope="session", autouse=True)
def compile_cache(tmp_path_factory):
    """Every test builds the same tiny programs: compile each once."""
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("jax_cache")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


@pytest.fixture
def tiny(monkeypatch):
    """run.py and the generator find the tiny files; the look for a chip
    is steered to whatever device jax has here."""
    import jax

    from benchmark import roofline, run
    from benchmark.traffic import generate

    files = copy.deepcopy(TINY)
    monkeypatch.setattr(run, "load_json",
                        lambda *parts: files["/".join(parts)])
    monkeypatch.setattr(generate, "load",
                        lambda name: files[f"traffic/{name}.json"])
    monkeypatch.setattr(run, "require_chips",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "place_cache", lambda: None)
    bench = _json("..", "BENCHMARK.json")
    # the plane of a listed cell is its configuration's driver
    driver_of = {w["name"]: _json("configs", f"{w['config']}.json")["driver"]
                 for w in bench["workloads"]}

    def cell_metrics(cell):
        """A tiny cell reports what the first listed cell of the same
        driver reports."""
        config = files[f"workloads/{cell}.json"]["config"]
        driver = files[f"configs/{config}.json"]["driver"]
        like = next(w for w, d in driver_of.items() if d == driver)
        pick = lambda ms: [m for m in ms  # noqa: E731
                           if like in m.get("workloads", [like])]
        return pick(bench["end_to_end"]), pick(bench["per_layer"])

    monkeypatch.setattr(run, "cell_metrics", cell_metrics)
    monkeypatch.setitem(roofline.PEAKS, jax.devices()[0].device_kind,
                        {"flops": 1e12, "bytes_per_s": 1e11,
                         "source": "test"})
    return files


def last_line(capsys) -> dict:
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])
