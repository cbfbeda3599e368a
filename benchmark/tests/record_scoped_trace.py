"""How `data/tpu_scoped.xplane.pb` and `data/tpu_scoped.records.json`
were recorded (on the chip, PR 26): five executions of one small jitted
program whose operations run under three `jax.named_scope`s, traced as
`run.tracing` traces (no host tracer), each inside a step of a
`StepPhaseProfiler` with the phases `dispatch`, `fetch` (a host fetch
of the result) and `harvest` (a host sleep), and a sleep between two
steps. The records are the timeline's, as JSON. It also prints the two
host clocks around `start_trace`, for the trace's timebase.

    chiprun -- python3 benchmark/tests/record_scoped_trace.py
"""

import glob
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.observability.perf import (
        CLOCK_ANCHOR,
        StepPhaseProfiler,
        get_timeline,
    )

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3

    @jax.jit
    def scoped_step(a, idx):
        with jax.named_scope("conv/c1"):
            b = a @ a
        with jax.named_scope("bn/b1"):
            b = (b - jnp.mean(b, axis=0)) * jax.lax.rsqrt(
                jnp.var(b, axis=0) + 1e-5)
        with jax.named_scope("kv_read"):
            g = b[idx]
        return jnp.tanh(g).sum(axis=0)

    a = jnp.ones((2048, 2048), jnp.float32)
    idx = jnp.arange(0, 2048, 2)
    np.asarray(scoped_step(a, idx))
    out = os.path.join(ROOT, "chiprun_out", "tpu_scoped")
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    pp = StepPhaseProfiler(owner="decode/recorded", emit_metrics=False)
    before = (time.time_ns(), time.perf_counter_ns())
    jax.profiler.start_trace(out, profiler_options=opts)
    after = (time.time_ns(), time.perf_counter_ns())
    for i in range(5):
        pp.begin_step(since_last="between_steps")
        pp.mark("dispatch")
        r = scoped_step(a, idx)
        pp.mark("fetch")
        np.asarray(r)
        pp.mark("harvest")
        time.sleep(0.002)
        pp.end_step(step=i + 1)
        time.sleep(0.005 if i != 2 else 0.02)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    dst = os.path.join(ROOT, "chiprun_out", "tpu_scoped.xplane.pb")
    shutil.copy(found[0], dst)
    shutil.rmtree(out, ignore_errors=True)
    records = [list(r) for r in get_timeline()
               if r[0] == "decode/recorded"]
    with open(os.path.join(ROOT, "chiprun_out",
                           "tpu_scoped.records.json"), "w") as f:
        json.dump({"records": records, "clock_anchor": CLOCK_ANCHOR,
                   "around_start_trace": [before, after]}, f)
    print(os.path.getsize(dst), "bytes;", len(records), "records")
    print("time_ns, perf_counter_ns before start_trace:", before,
          "after:", after, "anchor:", CLOCK_ANCHOR)
    return 0


if __name__ == "__main__":
    sys.exit(main())
