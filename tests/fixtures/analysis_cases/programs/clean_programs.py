"""The false-positive guard per prog-* rule: each record sits just on
the CLEAN side of the behavior its bad_programs twin violates."""

from deeplearning4j_tpu.analysis.program_lint import ProgramRecord

SRC = "tests/fixtures/analysis_cases/programs/clean_programs.py"


def build_records():
    import jax.numpy as jnp

    records = []

    # bf16 matmul under the bf16 policy (the promised cast happens);
    # the f32 master-param add after the cast must NOT flag
    def bf16_matmul(params, x):
        y = x.astype(jnp.bfloat16) @ params["w"].astype(jnp.bfloat16)
        return params["b"] + y.astype(jnp.float32)

    records.append(ProgramRecord(
        name="clean_bf16_matmul", fn=bf16_matmul,
        example_args=({"w": jnp.zeros((16, 8), jnp.float32),
                       "b": jnp.zeros((8,), jnp.float32)},
                      jnp.zeros((4, 16), jnp.float32)),
        precision_policy="bf16", source=SRC))

    # donation honored: same-shape update aliases the donated buffer
    def donated_step(y):
        return y * 0.9, (y * y).sum()

    records.append(ProgramRecord(
        name="clean_donation", fn=donated_step,
        example_args=(jnp.zeros((8, 64), jnp.float32),),
        donate_argnums=(0,), source=SRC))

    # one authored transpose (the weight transpose every backward pass
    # legitimately pays) stays under the churn threshold
    def one_transpose(x):
        return jnp.transpose(x) + 1.0

    records.append(ProgramRecord(
        name="clean_single_transpose", fn=one_transpose,
        example_args=(jnp.zeros((128, 128), jnp.float32),), source=SRC))

    # pure device program: no host edges
    def devicey(x):
        return jnp.tanh(x) + 1.0

    records.append(ProgramRecord(
        name="clean_no_host_transfer", fn=devicey,
        example_args=(jnp.zeros((4, 4), jnp.float32),), source=SRC))

    # all computed outputs consumed; the UNconsumed output is a pure
    # input pass-through, which costs nothing and must not flag
    def passthrough(x):
        return x + 1.0, x

    records.append(ProgramRecord(
        name="clean_passthrough_output", fn=passthrough,
        example_args=(jnp.zeros((8, 8), jnp.float32),),
        consumed_outputs=(0,), source=SRC))

    # full buckets: the pow2 coalescer's fill > 0.5 invariant
    records.append(ProgramRecord(
        name="clean_full_bucket", bucket_capacity=8,
        bucket_rows_per_dispatch=8.0, source=SRC))

    # honestly-sharded ZeRO-1 shape: optimizer state staged sharded at
    # the call site, reduce-scatter/shard-local/all-gather constraints
    # inside, donated — the clean side of bad_unsharded_optimizer
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("dp",))
    rep = NamedSharding(mesh, P())
    sh = NamedSharding(mesh, P("dp"))

    def sharded_opt(p, m, x):
        g = jnp.mean(x) * jnp.ones_like(p)
        g = jax.lax.with_sharding_constraint(g, sh)
        ps = jax.lax.with_sharding_constraint(p, sh)
        m2 = 0.9 * m + g
        p2 = jax.lax.with_sharding_constraint(ps - 0.1 * m2, rep)
        return p2, m2

    records.append(ProgramRecord(
        name="clean_sharded_optimizer", fn=sharded_opt,
        example_args=(jax.device_put(jnp.zeros((16, 4)), rep),
                      jax.device_put(jnp.zeros((16, 4)), sh),
                      jax.device_put(jnp.ones((8,)), sh)),
        donate_argnums=(0, 1),
        sharded_argnums=(1,), source=SRC))
    return records
