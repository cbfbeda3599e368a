"""Telemetry overhead benchmark. Prints ONE JSON line (same shape as
bench.py): {"metric": ..., "value": ..., "unit": ..., ...}.

Measures the cost of the observability substrate on the two hot paths
it instruments, each with telemetry ON (the default: guarded registry
emission per step/batch/request) vs OFF (`observability.enable(False)`
— the constant-time no-op fast path):

  training   TrainingMaster.fit on a small CPU MLP (the bench_resilience
             baseline shape): steps/sec, emission sites = steps_total +
             step_seconds + data_wait per step.
  serving    the bench_serving stub-RTT closed loop (5 ms dispatch RTT,
             4 ms compute, 24 clients, pipelined depth 2): req/s,
             emission sites = batches_total + occupancy + queue gauge
             per dispatched batch.

A third training config (`train_traced`) also attaches a Tracer, so the
per-step span cost (4 span records/step) is visible separately —
tracing is opt-in precisely because it is the expensive half.

Methodology (PERF.md hygiene): warmup pass first (compile excluded),
then `reps` interleaved on/off passes, headline = best rep per config
(transients only slow a rep down). The acceptance bar is <2% overhead
for telemetry ON on both paths; numbers land in PERF.md "Telemetry
overhead".
"""

import gc
import json
import sys
import time

import numpy as np


def bench_training(steps=300, reps=12):
    """One net + ONE compiled step program shared by every pass —
    rebuilding the net per pass would re-trace XLA each time and the
    compile/allocator drift (±30% on this box) would drown the ~1%
    effect being measured. Only the telemetry switch (and the attached
    tracer) differs between configs."""
    from deeplearning4j_tpu import (
        MultiLayerNetwork,
        NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.conf import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.observability import Tracer, enable
    from deeplearning4j_tpu.parallel.training_master import TrainingMaster

    n_in, hidden, n_out, rows = 64, 256, 8, 64
    conf = (NeuralNetConfiguration.Builder().seed(3).updater("adam")
            .learning_rate(1e-3).activation("tanh").weight_init("xavier")
            .list()
            .layer(DenseLayer(n_out=hidden))
            .layer(OutputLayer(n_out=n_out, loss="mcxent"))
            .set_input_type(InputType.feed_forward(n_in))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(17)
    x = rng.normal(size=(rows, n_in)).astype(np.float32)
    y = np.eye(n_out, dtype=np.float32)[rng.integers(0, n_out, rows)]
    tm = TrainingMaster(net)
    cursor = [0]
    tm.fit(lambda s: (x, y), 5, start_step=0)   # compile + stage
    cursor[0] = 5

    def run(config):
        gc.collect()   # a stale pass's garbage must not bill this one
        enable(config != "off")
        tm.tracer = Tracer() if config == "traced" else None
        if config == "profiled":
            from deeplearning4j_tpu.observability.perf import (
                StepPhaseProfiler,
            )

            # device-sync sampling OFF (sync_every=0): measures the
            # pure mark+emit cost; sampled syncs are a separate,
            # deliberate purchase (PERF.md)
            tm.phase_profiler = StepPhaseProfiler(
                accumulator=tm._obs_acc, sync_every=0)
        try:
            start = cursor[0]
            t0 = time.perf_counter()
            tm.fit(lambda s: (x, y), start + steps, start_step=start)
            float(net.score())   # host sync: honest timed window
            dt = time.perf_counter() - t0
            cursor[0] = start + steps
            return steps / dt
        finally:
            tm.tracer = None
            tm.phase_profiler = None
            enable(True)

    runs = {"on": [], "off": [], "traced": [], "profiled": []}
    pairs = {"on": [], "traced": [], "profiled": []}
    # session ramp warmup: a cold process climbs ~40% over its first
    # seconds (allocator/branch caches, CPU boost) — run throwaway
    # passes until adjacent passes agree within 3% so the measured
    # pairs start at steady state
    prev = run("off")
    for _ in range(8):
        curv = run("off")
        if abs(curv - prev) / max(prev, 1e-9) < 0.03:
            break
        prev = curv
    # strictly adjacent (config, off) pairs — a third config BETWEEN
    # the two passes being compared would re-open the window for the
    # box's slow drift; alternate order so drift can't favour one side
    # passes are ~0.3 s, so many reps are cheap — and the headline
    # needs them: single-pass throughput swings ±5-10% on a shared
    # 1-core box, so BOTH configs must get enough draws to catch the
    # box's fast windows before best-of converges
    for rep in range(max(4, reps)):
        for config in ("on", "traced", "profiled"):
            a, b = ((config, "off") if rep % 2 == 0
                    else ("off", config))
            first, second = run(a), run(b)
            cfg_v, off_v = ((first, second) if a == config
                            else (second, first))
            runs[config].append(cfg_v)
            runs["off"].append(off_v)
            pairs[config].append((cfg_v, off_v))
    out = {k: float(np.median(v)) for k, v in runs.items()}
    out["spread"] = {k: [round(min(v), 1), round(max(v), 1)]
                     for k, v in runs.items()}
    # headline: BEST pass per config — transient load only ever slows
    # a pass down, so each config's fastest pass is its closest view of
    # the systematic cost floor (a shared 1-core box swings adjacent
    # passes ±10%, which drowns a ~1% effect in any averaged estimator)
    out["overhead_pct"] = {
        k: round((1.0 - max(runs[k]) / max(runs["off"])) * 100.0, 2)
        for k in ("on", "traced", "profiled")}
    # secondary: median of adjacent-pair ratios (the two passes of a
    # pair share the box's transient load) — noisier, kept for honesty
    out["overhead_pct_paired_median"] = {
        k: round(float(np.median(
            [1.0 - a / b for a, b in pairs[k]])) * 100.0, 2)
        for k in ("on", "traced", "profiled")}
    return out


def bench_serving_rtt(reps=8):
    from bench_serving import _run_load, _StubRTTNet

    from deeplearning4j_tpu.observability import enable
    from deeplearning4j_tpu.parallel.inference import ParallelInference

    def one_pass():
        gc.collect()
        pi = ParallelInference(_StubRTTNet(), batch_limit=32,
                               queue_limit=256, pipeline_depth=2,
                               max_wait_ms=1.0, warmup=False)
        try:
            _run_load(pi, 300, 24, (1, 2, 3, 4, 6, 8), 256, seed=99)
            elapsed, _ = _run_load(pi, 1500, 24, (1, 2, 3, 4, 6, 8),
                                   256, seed=1)
            return 1500 / elapsed
        finally:
            pi.shutdown()

    # the closed-loop stub bench has a ±3-5% best-of spread (thread
    # scheduling jitter dominates); the MEDIAN of interleaved passes is
    # the honest estimator for a ~1% effect
    runs = {"on": [], "off": []}
    one_pass()   # throwaway warmup
    one_pass()
    for rep in range(max(6, reps)):
        order = ("on", "off") if rep % 2 == 0 else ("off", "on")
        for config in order:
            enable(config == "on")
            try:
                runs[config].append(one_pass())
            finally:
                enable(True)
    out = {k: float(np.median(v)) for k, v in runs.items()}
    out["spread"] = {k: [round(min(v), 1), round(max(v), 1)]
                     for k, v in runs.items()}
    out["overhead_pct"] = round(
        (1.0 - max(runs["on"]) / max(runs["off"])) * 100.0, 2)
    out["overhead_pct_paired_median"] = round(float(np.median(
        [1.0 - a / b for a, b in zip(runs["on"], runs["off"])]))
        * 100.0, 2)
    return out


def main():
    from deeplearning4j_tpu.nn.jit_cache import place_compile_cache

    place_compile_cache()
    steps = int(sys.argv[1]) if len(sys.argv) > 1 else 300
    train = bench_training(steps=steps)
    serve = bench_serving_rtt()

    def pct(on, off):
        return round((off - on) / off * 100.0, 2) if off else None

    out = {
        "metric": "telemetry_overhead_train_pct",
        "value": train["overhead_pct"]["on"],
        "unit": "% (positive = telemetry costs throughput)",
        "train_steps_per_sec": {
            "on": round(train["on"], 1),
            "off": round(train["off"], 1),
            "traced": round(train["traced"], 1),
            "profiled": round(train["profiled"], 1),
            "spread": train["spread"]},
        "train_overhead_pct_cross_median": pct(train["on"],
                                               train["off"]),
        "train_overhead_pct_paired_median":
            train["overhead_pct_paired_median"]["on"],
        "train_traced_overhead_pct": train["overhead_pct"]["traced"],
        "train_profiled_overhead_pct":
            train["overhead_pct"]["profiled"],
        "serving_overhead_pct": serve["overhead_pct"],
        "serving_overhead_pct_paired_median":
            serve["overhead_pct_paired_median"],
        "serving_requests_per_sec": {
            "on": round(serve["on"], 1),
            "off": round(serve["off"], 1),
            "spread": serve["spread"]},
        "config": (f"train: mlp 64-256-8 f32 batch64 x{steps} steps; "
                   "serving: stub rtt=5ms compute=4ms batch_limit=32 "
                   "24 clients pipelined depth 2"),
    }
    from bench_serving import _device_facts

    out.update(_device_facts())
    print(json.dumps(out))


if __name__ == "__main__":
    main()
