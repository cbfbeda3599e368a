"""The decode step's floor (`decode_step_bytes`, `decode_step_roofline.serve`)
counts the held experts the window's rows chose, as the program counted
them (`moe_experts_hit`), where the program counts them, and what it
counted before where it does not: the five expert references and the
reader on synthetic windows, against values pinned from the even-routing
count."""

import importlib

import pytest

from benchmark import roofline, run
from benchmark.tests.conftest import _json

PEAKS = roofline.device_peaks("TPU v5 lite")

# reference module, configuration, expert layers
MOE = [("lfm2_moe", "lfm2-24b-a2b", 8),
       ("kimi_linear", "kimi-linear-48b-a3b", 7),
       ("pangu_ultra_moe", "pangu-ultra-moe-718b", 4),
       ("laguna", "laguna-s-2.1", 4),
       ("granite_hybrid", "granite-4.0-h-small", 10)]

# decode_step_bytes(cfg, 40_000, 30) and (cfg, 150_000.5, 127.2) under
# even routing, as the count stood before it took the program's hits
EVEN_BYTES = {
    "lfm2_moe": (9132523287.068808, 10997206668.174326),
    "kimi_linear": (5808956050.728464, 11015558704.746737),
    "pangu_ultra_moe": (7443501183.550919, 10301308489.854034),
    "laguna": (3142841492.9361153, 5373316065.834949),
    "granite_hybrid": (8355784289.852805, 16360741664.550196),
}

# decode_step_roofline.serve on `_facts` with no `moe_experts_hit` in
# the delta, as the reader read before it took the program's hits
EVEN_READING = {
    "gpt2": 165.4041579161579,
    "lfm2_moe": 69.16379759280237,
    "kimi_linear": 64.05134108938888,
    "pangu_ultra_moe": 63.66465610660639,
    "laguna": 31.587640013785222,
    "granite_hybrid": 90.01743873949971,
}
CONFIGS = dict({m: c for m, c, _ in MOE}, gpt2="gpt2-medium")


def _ref(module):
    return importlib.import_module(f"benchmark.reference.{module}")


def _expert_bytes(ref, cfg):
    return ref.BYTES * ref.expert_params(cfg)


def _facts(module, **delta):
    """100 rows a step over 1,700 steps at a mean context of 1,250, the
    decode step 19.2 ms at the slice's median."""
    cfg = _json("configs", f"{CONFIGS[module]}.json")
    return {"config": cfg, "reference": _ref(module),
            "mean_context": 1250.0, "chips": 1, "peaks": PEAKS,
            "trace": {"programs": {"jit_decode_fn": {"n": 15,
                                                     "median_s": 0.0192}}},
            "delta": dict({"steps": 1700, "tokens_total": 1700 * 100},
                          **delta)}


def _read(name, facts):
    return run.read_layer_metric(name, facts)


@pytest.mark.parametrize("module,config,n_moe", MOE)
def test_without_a_count_the_floor_is_the_even_routing_one(
        module, config, n_moe):
    ref, cfg = _ref(module), _json("configs", f"{config}.json")
    for (live, slots), even in zip([(40_000, 30), (150_000.5, 127.2)],
                                   EVEN_BYTES[module]):
        assert ref.decode_step_bytes(cfg, live, slots) == even
        assert ref.decode_step_bytes(cfg, live, slots,
                                     experts_hit=None) == even


@pytest.mark.parametrize("module,config,n_moe", MOE)
def test_a_count_takes_the_unchosen_experts_out(module, config, n_moe):
    """Each hit the program did not count is one expert's weights less
    than even routing reads; all held hit is every held expert."""
    ref, cfg = _ref(module), _json("configs", f"{config}.json")
    held = len(ref.dims(cfg)["held"])
    for live, slots in [(40_000, 30), (150_000.5, 127.2)]:
        even = ref.decode_step_bytes(cfg, live, slots)
        even_hits = ref.experts_hit(cfg, slots)
        for counted in (0.6 * n_moe * held, n_moe * held, 1.0):
            assert ref.decode_step_bytes(
                cfg, live, slots, experts_hit=counted) == pytest.approx(
                    even - (even_hits * n_moe - counted)
                    * _expert_bytes(ref, cfg), rel=1e-12)


def test_lfm2_at_its_window_reads_two_gigabytes_less():
    """127.2 rows a step of 4 in 64 hit 63.98 experts a layer at the
    mean; the program counts 408 over its 8 layers (51 a layer, four
    fifths): 103.9 experts of 18.9 MB fewer, 1.96 GB, 2.39 ms at
    819 GB/s."""
    ref, cfg = _ref("lfm2_moe"), _json("configs", "lfm2-24b-a2b.json")
    live = 127.2 * 1250
    less = ref.decode_step_bytes(cfg, live, 127.2) \
        - ref.decode_step_bytes(cfg, live, 127.2, experts_hit=408)
    assert less == pytest.approx(1.96e9, rel=0.03)
    assert less / PEAKS["bytes_per_s"] == pytest.approx(2.39e-3, rel=0.03)


@pytest.mark.parametrize("module", sorted(EVEN_READING))
def test_the_reader_without_the_counter_reads_as_before(module):
    assert _read("decode_step_roofline.serve", _facts(module)) \
        == EVEN_READING[module]


@pytest.mark.parametrize("module,config,n_moe", MOE)
def test_the_reader_takes_the_windows_hits_a_step(module, config, n_moe):
    """The delta's `moe_experts_hit` over its `steps` is the count the
    reference is given, and fewer hits read lower."""
    facts = _facts(module, moe_experts_hit=1700 * 2.5 * n_moe)
    ref, cfg = facts["reference"], facts["config"]
    least = roofline.roofline_seconds(
        100 * ref.flops_per_token(cfg, 1250.0),
        ref.decode_step_bytes(cfg, 100 * 1250.0, 100,
                              experts_hit=2.5 * n_moe), PEAKS)
    got = _read("decode_step_roofline.serve", facts)
    assert got == pytest.approx(100 * least / 0.0192, rel=1e-12)
    assert got < EVEN_READING[module]


def test_the_step_and_the_expert_layer_count_the_same_weights():
    """In LFM2 on one synthetic window, ten hits a step more raise the
    least time of the whole step and of the expert layers alike: ten
    experts' weights at the bandwidth."""
    steps = 1700
    scopes = {"moe/router": 0.1e-3, "moe/experts": 10.0e-3}

    def facts(hits):
        out = _facts("lfm2_moe", moe_experts_hit=hits * steps,
                     moe_assignments=4 * 8 * 100 * steps,
                     moe_assignments_held=4 * 8 * 100 * steps)
        out["scope_times"] = {"jit_decode_fn": {
            "n": 15, "scopes": {k: v * 15 for k, v in scopes.items()}}}
        return out

    ten = 10 * _expert_bytes(_ref("lfm2_moe"), facts(0)["config"]) \
        / PEAKS["bytes_per_s"]
    for name, seconds in [("decode_step_roofline.serve", 0.0192),
                          ("moe_roofline.serve", 10.1e-3)]:
        lo, hi = (_read(name, facts(h)) for h in (400, 410))
        assert (hi - lo) / 100 * seconds == pytest.approx(ten, rel=1e-9)
