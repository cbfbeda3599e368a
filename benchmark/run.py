"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds everything by name and nothing by a list in code:
`workloads/<cell>.json` names the configuration, the traffic mix, the
chips, the limits of `correct`; `configs/<config>.json` names its driver
(`drivers/<plane>.py`) and its plain reference (`reference/<arch>.py`);
`traffic/<mix>.json` parameterises the one generator; each per-layer
metric of `BENCHMARK.json` that lists the cell is read by
`layer_metrics/<name>.py`. The last line of standard output is the
result; everything else goes to standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
TRACE_SECONDS = 1.5     # the steady slice the profiler records, just
#                         before the window, so that the window is clean;
#                         a cell whose steps are long gives its own


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def require_chips(chips: int):
    """The devices the cell runs on, or exit: no accelerator, no result."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        log(f"need {chips} TPU chip(s); jax found {len(devs)} x "
            f"{devs[0].platform}")
        raise SystemExit(3)
    return devs[:chips]


def place_cache() -> None:
    """One fixed compile-cache directory inside the checkout (or where
    JAX_COMPILATION_CACHE_DIR says), every program cached however short
    its compile, so only a cell's first run there compiles."""
    import jax
    from deeplearning4j_tpu.nn.jit_cache import place_compile_cache

    place_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def mark(what: str) -> None:
    """How far into the run a phase of set-up ended, for PERF.md."""
    log(f"[{time.perf_counter() - T_START:7.2f} s] {what}")


def device_peak(devices):
    """(in use, reserved) at the peak of the fullest chip: what the
    allocator had in use, and what the runtime held reserved for the
    loaded programs' temporaries, which the v5e's runtime counts apart
    (`tests/memory_probe.py`; PERF.md, Findings). Their sum is the
    peak."""
    def peak(d):
        m = d.memory_stats() or {}
        return m.get("peak_bytes_in_use", 0), m.get("peak_bytes_reserved", 0)
    return max((peak(d) for d in devices), key=sum)


def tracing(ctx):
    """(start, stop) of the profiler for a driver: the device's lines,
    with no Python call tracing and the host's tracer off: at level 1
    and above the runtime's own threads write eleven million futex
    events into a 1.5 s slice of the training cell, 370 MB, and stall
    the steps they serve. The device's peak is read before the start,
    since the profiler resets it."""
    import jax

    def start():
        ctx.peaks.append(device_peak(ctx.devices))
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        jax.profiler.start_trace(ctx.trace_dir, profiler_options=opts)

    return start, jax.profiler.stop_trace


def cell_metrics(cell: str):
    """(end_to_end, per_layer) entries of BENCHMARK.json for this cell."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    pick = lambda ms: [m for m in ms  # noqa: E731
                       if cell in m.get("workloads", [cell])]
    return pick(bench["end_to_end"]), pick(bench["per_layer"])


def read_layer_metric(name: str, facts: dict):
    spec = importlib.util.spec_from_file_location(
        "layer_metric", os.path.join(HERE, "layer_metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(facts)


def make_context(workload: str, seed: int, seconds: float, trace: bool):
    """(ctx, driver module, end-to-end entries, per-layer entries) of a
    cell: what a run and a study of its limits both start from. Exits
    where the chips are not there."""
    cell = load_json("workloads", f"{workload}.json")
    config = load_json("configs", f"{cell['config']}.json")
    e2e, per_layer = cell_metrics(workload)
    devices = require_chips(int(cell["chips"]))
    place_cache()

    from benchmark.traffic import generate

    ctx = SimpleNamespace(
        t_start=T_START, cell=cell, config=config,
        mix=generate.load(cell["traffic"]), seed=seed,
        seconds=seconds, trace=trace, devices=devices,
        trace_dir=TRACE_DIR, log=log, mark=mark,
        trace_seconds=float(cell.get("trace_seconds", TRACE_SECONDS)),
        peaks=[],
        reference=importlib.import_module(
            f"benchmark.reference.{config['reference']}"))
    ctx.start_trace, ctx.stop_trace = tracing(ctx)
    driver = importlib.import_module(f"benchmark.drivers.{config['driver']}")
    return ctx, driver, e2e, per_layer


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    ctx, driver, e2e, per_layer = make_context(
        args.workload, args.seed, args.seconds, bool(args.trace))
    cell, config, devices = ctx.cell, ctx.config, ctx.devices

    from benchmark import roofline, xplane
    from benchmark.correct import print_compared, verdict

    mark("imports, cell and cache placed")
    run = driver.run(ctx)        # set-up, window; program state still live
    mark("window closed")

    in_use, reserved = max(ctx.peaks + [device_peak(devices)], key=sum)
    peak = in_use + reserved
    limit = min((d.memory_stats() or {}).get("bytes_limit", 0)
                for d in devices)
    log(f"device memory after the window: {devices[0].memory_stats()}")
    run.free()                   # the reference gets the chip to itself
    t0 = time.perf_counter()
    numbers = run.check()
    log(f"reference and comparison: {time.perf_counter() - t0:.2f} s")
    correct, compared = verdict(numbers, cell["limits"])

    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(peak),
              "peak_bytes_in_use": int(in_use),
              "peak_bytes_reserved": int(reserved)}
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed}
    if ctx.trace:
        tr = xplane.reduce_trace(
            TRACE_DIR, programs=set(config["programs"].values()))
        facts = dict(run.facts, trace=tr, end_to_end=run.end_to_end,
                     peaks=roofline.device_peaks(kind), chips=len(devices),
                     config=config, cell=cell, mix=ctx.mix,
                     memory_peak_bytes=peak, memory_limit_bytes=limit,
                     reference=ctx.reference)
        metrics = {}
        for m in per_layer:
            v = read_layer_metric(m["name"], facts)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        # what the traced window read end to end, beside the layers'
        # counts of that same window; the driver reads `metrics`
        result.update(metrics=metrics, device=device, breakdown={
            "device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]},
            end_to_end={k: float(v) for k, v in run.end_to_end.items()})
        log(f"end to end in this traced run: {result['end_to_end']}")
    else:
        result.update(metrics={
            m["name"]: {"value": float(run.end_to_end[m["name"]]),
                        "unit": m["unit"]} for m in e2e}, device=device)
    result["compared"] = compared
    print_compared(compared, correct)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    sys.exit(main())
