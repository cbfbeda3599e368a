"""Does `memory_stats()`' peak count a loaded program's temporaries?
Run on the chip, not by pytest:

    python3 benchmark/tests/memory_probe.py

One program whose 8.59 GB of temporaries the compiler reports. On a
TPU v5e (chip run, PR 24) it read:

    before  bytes_in_use 27,136          bytes_reserved 0
    memory_analysis      temp_size_in_bytes 8,589,999,104
    after   peak_bytes_in_use 2,557,952  peak_bytes_reserved 8,589,967,360
            largest_free_block_bytes 8,316,810,752 of bytes_limit 16,909,336,064

The temporaries sit in `bytes_reserved` and never in `peak_bytes_in_use`,
and in use + reserved + the largest free block make `bytes_limit`. So
`run.py` reports `memory_peak_bytes` as the sum of the two peaks, and
both apart in `device`.
"""

import jax
import jax.numpy as jnp


@jax.jit
def f(x):
    a = jnp.sin(x)[:, None] * jnp.cos(x)[None, :]      # 4 GB
    return jnp.sum(jnp.sort(a, axis=1)[:, ::7])        # sorted: it has to exist


if __name__ == "__main__":
    d = jax.devices()[0]
    print("before", d.memory_stats())
    x = jnp.arange(32768, dtype=jnp.float32)
    print(f.lower(x).compile().memory_analysis())
    print(float(f(x)))
    print("after", d.memory_stats())
