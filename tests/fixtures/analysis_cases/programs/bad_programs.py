"""One deliberately-broken ProgramRecord per prog-* rule (true
positives for analysis/program_lint). Imported and executed by
tests/test_static_analysis.py under JAX_PLATFORMS=cpu — unlike the AST
fixtures these are REAL programs: the lint traces and lowers them.
"""

from deeplearning4j_tpu.analysis.program_lint import ProgramRecord

SRC = "tests/fixtures/analysis_cases/programs/bad_programs.py"


def build_records():
    import jax
    import jax.numpy as jnp
    import numpy as np

    records = []

    # prog-fp32-matmul-under-policy: f32 dot under a declared bf16
    # policy (the cast the policy promises never happens)
    def fp32_matmul(params, x):
        return x @ params["w"] + params["b"]

    records.append(ProgramRecord(
        name="bad_fp32_matmul", fn=fp32_matmul,
        example_args=({"w": jnp.zeros((16, 8), jnp.float32),
                       "b": jnp.zeros((8,), jnp.float32)},
                      jnp.zeros((4, 16), jnp.float32)),
        precision_policy="bf16", source=SRC))

    # prog-unhonored-donation: donated [n_pad, C] buffer can never
    # alias the [n_real, C] output (the pre-fix tsne shape)
    def sliced_step(y):
        return y[:6] * 2.0, (y * y).sum()

    records.append(ProgramRecord(
        name="bad_unhonored_donation", fn=sliced_step,
        example_args=(jnp.zeros((8, 64), jnp.float32),),
        donate_argnums=(0,), source=SRC))

    # prog-transpose-churn: eight authored layout round-trips of the
    # whole activation tensor (the rule counts authored
    # stablehlo.transpose bytes against the program signature)
    def churny(x):
        acc = x
        for i in range(8):
            acc = jnp.transpose(acc) + float(i + 1)
        return acc

    records.append(ProgramRecord(
        name="bad_transpose_churn", fn=churny,
        example_args=(jnp.zeros((128, 128), jnp.float32),), source=SRC))

    # prog-hidden-host-transfer: a host callback inside the program
    def hosty(x):
        y = jax.pure_callback(
            lambda v: np.asarray(v) * 2.0,
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return y + 1.0

    records.append(ProgramRecord(
        name="bad_host_transfer", fn=hosty,
        example_args=(jnp.zeros((4, 4), jnp.float32),), source=SRC))

    # prog-dead-output: output 1 is computed but declared unconsumed
    def deady(x):
        return x + 1.0, jnp.tanh(x) @ x.T

    records.append(ProgramRecord(
        name="bad_dead_output", fn=deady,
        example_args=(jnp.zeros((8, 8), jnp.float32),),
        consumed_outputs=(0,), source=SRC))

    # prog-excess-padding: 3 real rows per dispatch into a 32-bucket
    records.append(ProgramRecord(
        name="bad_excess_padding", bucket_capacity=32,
        bucket_rows_per_dispatch=3.0, source=SRC))

    # prog-unsharded-optimizer-state: the registration declares the
    # optimizer-state argument mesh-sharded (ZeRO-1), but the call
    # site stages it REPLICATED — the silent n-x memory regression
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("dp",))
    rep = NamedSharding(mesh, P())

    def unsharded_opt(p, m, x):
        g = jnp.mean(x) * jnp.ones_like(p)
        m2 = 0.9 * m + g
        return p - 0.1 * m2, m2

    records.append(ProgramRecord(
        name="bad_unsharded_optimizer", fn=unsharded_opt,
        example_args=(jax.device_put(jnp.zeros((16, 4)), rep),
                      jax.device_put(jnp.zeros((16, 4)), rep),
                      jax.device_put(jnp.ones((8,)), rep)),
        donate_argnums=(0, 1),
        sharded_argnums=(1,), source=SRC))
    return records
