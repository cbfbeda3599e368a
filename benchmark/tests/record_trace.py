"""How `data/tpu_small.xplane.pb` was recorded (on the chip, PR 24):
three executions of one small jitted program, each inside a `bench:step`
annotation, with a host sleep between the second and the third.

    chiprun -- python3 benchmark/tests/record_trace.py
"""

import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3

    @jax.jit
    def small_step(a):
        return jnp.tanh(a @ a) * 0.5

    a = jnp.ones((1024, 1024), jnp.float32)
    small_step(a).block_until_ready()
    out = os.path.join(ROOT, "chiprun_out", "tpu_small")
    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(out)
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench:step"):
            a = small_step(a)
            a.block_until_ready()
        if i == 1:
            time.sleep(0.02)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    shutil.copy(found[0], os.path.join(ROOT, "chiprun_out",
                                       "tpu_small.xplane.pb"))
    shutil.rmtree(out, ignore_errors=True)
    print(os.path.getsize(os.path.join(ROOT, "chiprun_out",
                                       "tpu_small.xplane.pb")), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
