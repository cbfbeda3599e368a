"""Guard-overhead microbench (PR 3 acceptance: per-step finite-check
sampling must add <5% step time; watchdog/supervisor must be free on
the happy path).

Measures TrainingMaster.fit steps/sec on a CPU MLP under:
  baseline        no self-healing hooks
  watchdog        StepWatchdog attached (beats only — no hang)
  watchdog_hb     StepWatchdog + cluster HeartbeatFile lease (PR 4:
                  the beat path additionally renews an atomic mtime
                  lease, throttled to one json write + rename per
                  0.2s — the per-step cost the ClusterSupervisor adds
                  to a supervised worker)
  guard_abort_N   NonFiniteGuard(policy='abort', check_every=N)
                  (pure check cost: one jitted all-finite reduction +
                  host bool fetch per checked step, no snapshot)
  guard_skip_N    NonFiniteGuard(policy='skip_step', check_every=N)
                  (adds the pre-step device-copy snapshot on checked
                  steps — the price of byte-identical skip recovery)

Usage: python bench_resilience.py [steps] [rows] [hidden]
Prints a JSON blob; numbers discussed in PERF.md ("Self-healing
training" section).
"""

import json
import os
import sys
import time

import numpy as np


def build(hidden):
    from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer

    conf = (NeuralNetConfiguration.Builder().seed(7).updater("adam")
            .learning_rate(1e-3).activation("relu").weight_init("xavier")
            .list()
            .layer(DenseLayer(n_out=hidden))
            .layer(DenseLayer(n_out=hidden))
            .layer(OutputLayer(n_out=10, loss="mcxent"))
            .set_input_type(InputType.feed_forward(64))
            .build())
    return MultiLayerNetwork(conf).init()


def main():
    steps = int(sys.argv[1]) if len(sys.argv) > 1 else 200
    rows = int(sys.argv[2]) if len(sys.argv) > 2 else 256
    hidden = int(sys.argv[3]) if len(sys.argv) > 3 else 256

    from deeplearning4j_tpu.nn.jit_cache import place_compile_cache
    from deeplearning4j_tpu.parallel.training_master import TrainingMaster
    from deeplearning4j_tpu.resilience import NonFiniteGuard, StepWatchdog

    place_compile_cache()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(rows, 64)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, rows)]
    batch_fn = lambda s: (x, y)

    configs = [("baseline", {})]
    configs.append(("watchdog",
                    {"watchdog": StepWatchdog(timeout_s=300.0)}))
    import tempfile

    from deeplearning4j_tpu.resilience.cluster import HeartbeatFile

    hb_path = os.path.join(tempfile.mkdtemp(prefix="bench_hb_"),
                           "worker-0.hb.json")
    configs.append(("watchdog_hb", {"watchdog": StepWatchdog(
        timeout_s=300.0, heartbeat=HeartbeatFile(hb_path))}))
    for n in (1, 4, 16):
        configs.append((f"guard_abort_{n}", {"guard": NonFiniteGuard(
            policy="abort", check_every=n)}))
    for n in (1, 4, 8):
        configs.append((f"guard_skip_{n}", {"guard": NonFiniteGuard(
            policy="skip_step", check_every=n)}))

    # one TrainingMaster per config, compiled up front; timed passes
    # run round-robin (best-of-N per config) so slow host drift on a
    # shared/noisy bench box hits every config equally instead of
    # penalizing whichever ran last
    tms, best, cursor = {}, {}, {}
    for label, kw in configs:
        tm = TrainingMaster(build(hidden), **kw)
        tm.fit(batch_fn, 20)                    # warmup + compile
        float(tm.net.score())                   # sync
        tms[label], best[label], cursor[label] = tm, float("inf"), 20
    for _ in range(3):
        for label, _ in configs:
            tm = tms[label]
            t0 = time.perf_counter()
            tm.fit(batch_fn, cursor[label] + steps,
                   start_step=cursor[label])
            float(tm.net.score())               # sync
            best[label] = min(best[label], time.perf_counter() - t0)
            cursor[label] += steps
    results = [{"label": label,
                "steps_per_s": round(steps / best[label], 1),
                "ms_per_step": round(best[label] / steps * 1e3, 4)}
               for label, _ in configs]
    base = results[0]["ms_per_step"]
    for r in results:
        r["overhead_pct"] = round(
            (r["ms_per_step"] / base - 1.0) * 100.0, 2)
    print(json.dumps({"steps": steps, "rows": rows, "hidden": hidden,
                      "results": results}, indent=2))


if __name__ == "__main__":
    main()
