"""chip_smoke.py rehearsed on the CPU (rehearsal 1 of the
on-chip-measurement guide): the same phases through the same `main`, at
toy sizes, with the platform check steered by monkeypatch — wrong
paths, arguments and control flow are found here, not on the chip's
budget. Nothing here says anything about the chip; the chip run is
`python chip_smoke.py` through the builder's tool.

Also pinned here: the script refuses the CPU when not steered, the one
compile-cache helper, and `device_peaks()` refusing a device it has no
published peak for.
"""

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

# toy sizes: every phase and every check of FULL, seconds on the CPU
TINY = {
    "runtime": dict(n=64, chain=2),
    "train": dict(batch=8, hw=32, n_classes=8, steps=4),
    "kernels": dict(batch=2, hw=8, c_mid=8, c_out=16),
    "serve": dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                  max_ctx=64),
    "mesh": dict(batch=8, hw=32, n_classes=8, steps=2),
    "latent": dict(vocab_size=64, hidden=32, n_heads=2, q_lora_rank=16,
                   kv_lora_rank=8, qk_nope_dim=8, qk_rope_dim=4,
                   v_head_dim=8, dense_ff=64, moe_ff=16, n_experts=4,
                   top_k=2, experts_held=(0, 2), n_dense_layers=1,
                   n_moe_layers=1, max_ctx=64, param_dtype="bfloat16"),
}


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def steered(smoke, monkeypatch):
    """The platform check answers with the CPU device and the peak
    table with a peak no work can fall short of: `main` then runs
    whole."""
    import jax

    from benchmark import roofline

    dev = jax.devices()[0]
    monkeypatch.setattr(smoke, "require_chip", lambda: dev)
    monkeypatch.setitem(roofline.PEAKS, str(dev.device_kind),
                        {"flops": 1e30, "bytes_per_s": 1e30,
                         "source": "steered"})
    return smoke


def _lines(capsys):
    return [json.loads(line) for line in
            capsys.readouterr().out.strip().splitlines()]


def test_one_chip_run_at_toy_size(steered, capsys):
    assert steered.main([], sizes=TINY) == 0
    lines = _lines(capsys)
    assert [ln.get("phase") for ln in lines[:-1]] == [
        "start", "runtime", "kernels", "train", "serve", "serve", "latent",
        "done"]
    # the last line is the contract's object and nothing else
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 8}}
    by = {(ln["phase"], ln.get("compute_dtype")): ln for ln in lines[:-1]}
    assert by["kernels", None]["interpret"] is True    # CPU: interpreted
    train = by["train", None]
    assert len(train["losses"]) == 4
    assert train["losses"][-1] < train["losses"][0]
    assert train["checkpoints"] and train["trace_counts"]["train"] == 1
    for dtype in ("float32", "bfloat16"):
        serve = by["serve", dtype]
        assert serve["prefix_requests_hit"] >= 1
        assert serve["audit"]["leaked"] == 0
    latent = by["latent", None]
    assert latent["pool_dtype"] == "bfloat16" and len(latent["pool"]) == 4
    assert 0 < latent["moe_assignments_held"] < latent["moe_assignments"]


def test_four_chip_run_at_toy_size(steered, capsys, monkeypatch):
    """`--chips 4` runs the mesh phase and its one-device comparison,
    and no other phase (rehearsal 2: virtual CPU devices)."""
    import jax

    monkeypatch.setattr(jax, "devices",
                        lambda *a, _all=jax.devices: _all(*a)[:4])
    # a 32x32 input leaves ResNet50 a 1x1 final feature map and
    # batch-norm statistics over 8 values: so ill-conditioned that
    # rounding alone moves step 1 by 15%. The toy run rehearses the
    # control flow; the tolerance as written is for the real size.
    monkeypatch.setattr(steered, "MESH_VS_ONE_RTOL", 0.5)
    assert steered.main(["--chips", "4"], sizes=TINY) == 0
    lines = _lines(capsys)
    assert [ln.get("phase") for ln in lines[:-1]] == [
        "start", "mesh", "done"]
    assert lines[-1]["device"]["count"] == 4
    mesh = lines[1]
    assert len(mesh["devices"]) == 4
    assert len(mesh["dp_zero1"]["state_shard_devices"]) == 4
    assert "all-reduce" in mesh["dp_replicated"]["collectives"]


def test_a_failed_check_fails_the_run(steered, monkeypatch):
    """No phase result may let the run reach exit 0."""
    monkeypatch.setattr(steered, "KERNEL_TOL", 0.0)
    with pytest.raises(steered.SmokeFailure):
        steered.main([], sizes=TINY)


def test_script_refuses_the_cpu():
    """Not steered: no TPU, a non-zero exit code and no result line."""
    p = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")], cwd=ROOT,
        capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("env_dir", ["/somewhere/else", None],
                         ids=["env-set", "env-unset"])
def test_compile_cache_is_placed_once(env_dir, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: jax honours it alone and the
    code sets no directory. Unset: `<checkout>/.jax_cache`, a fixed
    path. Either way the key takes in the programs' metadata (the
    scope names a device trace is read by), with locations cut to one
    frame."""
    import jax

    from deeplearning4j_tpu.nn.jit_cache import place_compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: calls.append((key, value)))
    keyed = [("jax_compilation_cache_include_metadata_in_key", True),
             ("jax_traceback_in_locations_limit", 1)]
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert place_compile_cache() == str(ROOT / ".jax_cache")
        assert calls == keyed + [("jax_compilation_cache_dir",
                                  str(ROOT / ".jax_cache"))]
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert place_compile_cache() == env_dir
        assert calls == keyed


@pytest.mark.parametrize("kind,known", [("TPU v5 lite", True),
                                        ("TPU v9 imaginary", False),
                                        ("cpu", False)])
def test_device_peaks_raises_on_unknown_kind(smoke, monkeypatch, kind,
                                             known):
    """A device without a published peak is an error, not a default,
    and the CPU has none: `main` asks the benchmark's table (the only
    one) right after the platform check, before any phase."""
    import types

    dev = types.SimpleNamespace(device_kind=kind, platform="tpu")
    monkeypatch.setattr(smoke, "require_chip", lambda: dev)
    started = []
    monkeypatch.setattr(smoke, "report",
                        lambda phase, **facts: started.append(facts))
    monkeypatch.setattr(smoke, "run_phase", lambda *a, **k: None)
    if known:
        assert smoke.main([]) == 0
        assert (started[0]["peak_flops"],
                started[0]["peak_bytes_per_s"]) == (197e12, 819e9)
        return
    with pytest.raises(KeyError, match="no published peak"):
        smoke.main([])
    assert not started
