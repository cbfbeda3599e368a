"""The training plane: one `TrainingMaster(net).fit(batch_fn, n)` over
the window, as `chip_smoke._fit` drives it, with no guard, no stats
listener and no checkpoint on the timed path.

Set-up builds the one object the window drives (the net with its
compiled step, behind one TrainingMaster), installs weights made from
the seed, takes it through its first three steps on three different
batches (keeping each loss, the first gradient as the updater's state
holds it, and the parameters' change), warms the pipeline, and hands
the same object to the window. The mesh and `sharding` come from the
cell's file.
"""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np

CHECK_STEPS = 3      # the steps the reference follows
WARM_STEPS = (4, 12)  # two timed calls after them: their difference is
#                      eight steps without a call's fixed cost, and sizes
#                      the window's step count
MOVED = 1e-3         # a leaf counts where the reference's first gradient
#                      is at least this share of the median leaf's


def build(ctx):
    """(net, tm): the program, its parameters not yet the seed's."""
    from deeplearning4j_tpu import zoo
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.parallel.training_master import TrainingMaster

    cfg, cell = ctx.config, ctx.cell
    hw, classes = int(cfg["image_size"]), int(cfg["num_classes"])
    net = getattr(zoo, cfg["zoo_class"])(
        num_classes=classes, input_shape=(hw, hw, 3),
        **cfg["constructor"]).init_model()
    mesh = cell.get("mesh")
    profiler = None
    if ctx.trace:       # marks only, no device sync: the steps still overlap
        from deeplearning4j_tpu.observability.perf import StepPhaseProfiler

        profiler = StepPhaseProfiler(sync_every=0)
    tm = TrainingMaster(
        net, mesh=make_mesh(**mesh) if mesh else None,
        sharding=cell.get("sharding"), phase_profiler=profiler)
    return net, tm


def install(net, w) -> None:
    """The seed's weights into the program, its updater state at zero
    and its step count at 0: the state every comparison starts from.
    The program gets a copy: its step donates what it is given, and
    the reference needs `w` after the window."""
    import jax
    import jax.numpy as jnp

    mine = {k: v for k, v in net.params.items() if v}
    shape_of = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)  # noqa: E731
    if shape_of(mine) != shape_of(w):
        raise SystemExit("the configuration's shapes are not the program's")
    zeros = jax.tree_util.tree_map(jnp.zeros_like, net.updater_states)
    net.params = {k: jax.tree_util.tree_map(jnp.copy, w.get(k, {}))
                  for k in net.params}
    net.updater_states = zeros
    net.iteration = 0


def first_steps(net, tm, w, batch_fn, lr: float):
    """Drive the window's own object through CHECK_STEPS steps by the
    window's own call. Returns (losses, the first gradient's leaves as
    the updater got it, the leaves of the parameters' change), on the
    host: the step donates its state."""
    import jax
    import jax.numpy as jnp

    from benchmark.correct import host_leaves

    losses, g1 = [], None
    for s in range(CHECK_STEPS):
        tm.fit(batch_fn, s + 1, start_step=s)
        losses.append(float(net.score()))
        if s == 0:      # v1 = -lr g1 while v0 = 0
            g1 = [a / -lr for a in host_leaves(
                {k: u["v"] for k, u in net.updater_states.items()
                 if u.get("v")})]
    now = {k: v for k, v in net.params.items() if v}
    change = host_leaves(jax.jit(lambda a, b: jax.tree_util.tree_map(
        jnp.subtract, a, b))(now, w))
    return np.asarray(losses), g1, change


def compare(prog, ref, names=None, log=None) -> dict:
    """The numbers of `correct` from (losses, g1, change) of each side.
    Leaves whose gradient is nought to rounding in the reference (a
    convolution's bias before batch-norm) move by round-off alone:
    they are left out by the rule on the reference's gradient. The
    gaps of norms by the worst leaf; the norm of the first gradient's
    difference by the median leaf, which is where a precision shows:
    squared (`grad_noise_median`, the noise's power over the signal's),
    since rounding errors add in power and the norm itself closes in on
    the square root of 2 as they grow."""
    from benchmark.correct import leaf_diffs, leaf_gaps, norms

    (pl, pg, pc), (rl, rg, rc) = prog, ref
    png, rng, pcn, rcn = norms(pg), norms(rg), norms(pc), norms(rc)
    moved = rng >= MOVED * np.median(rng)
    grad, change = leaf_gaps(png, rng, moved), leaf_gaps(pcn, rcn, moved)
    diff = leaf_diffs(pg, rg, moved)[moved]
    gi, ci = int(np.argmax(grad)), int(np.argmax(change))
    rel = np.abs(pl - rl) / np.abs(rl)
    if log is not None and names is not None:
        log(f"losses program {pl.tolist()} reference {rl.tolist()}; the "
            f"first gradient's difference by the median leaf "
            f"{np.median(diff):.4f}")
        log(f"{int(moved.sum())} of {moved.size} leaves counted (median "
            f"gradient {np.median(rng):.5g}); worst gradient {names[gi]} "
            f"{png[gi]:.5g} vs {rng[gi]:.5g}; worst change {names[ci]} "
            f"{pcn[ci]:.5g} vs {rcn[ci]:.5g}")
    return {"loss_gap": float(np.max(rel)),
            "grad_norm_gap": float(grad[gi]),
            "change_norm_gap": float(change[ci]),
            "grad_noise_median": float(np.median(diff)) ** 2}


def _weights(ctx, seed: int):
    from benchmark import weights

    return weights.make_weights(ctx.reference.param_shapes(ctx.config),
                                seed, ctx.config["init"])


def run(ctx):
    from benchmark.correct import leaf_names
    from benchmark.traffic import generate

    cfg = ctx.config
    net, tm = build(ctx)
    ctx.mark("model built")
    w = _weights(ctx, ctx.seed)
    install(net, w)
    pool = generate.batch_pool(ctx.mix, ctx.seed, int(cfg["image_size"]),
                               int(cfg["num_classes"]))
    ctx.mark("weights and batch pool made")
    batch = int(ctx.mix["batch"])
    batch_fn = lambda step: pool[step % len(pool)]  # noqa: E731
    lr = float(cfg["constructor"]["learning_rate"])
    prog = first_steps(net, tm, w, batch_fn, lr)
    ctx.mark("first steps (the step compiled or loaded)")

    step, took = CHECK_STEPS, []
    for k in WARM_STEPS:
        t0 = time.perf_counter()
        tm.fit(batch_fn, step + k, start_step=step)
        net.score()
        took.append(time.perf_counter() - t0)
        step += k
    step_s = (took[1] - took[0]) / (WARM_STEPS[1] - WARM_STEPS[0])
    n = max(2, int(round(ctx.seconds / step_s)))
    n_traced = max(2, int(ctx.trace_seconds / step_s))
    traces0 = dict(net._jit_cache.trace_counts())

    if ctx.trace:       # a steady slice just before the window
        ctx.start_trace()
        tm.fit(batch_fn, step + n_traced, start_step=step)
        net.score()
        ctx.stop_trace()
        step += n_traced
    phases0 = (dict(tm.phase_profiler.totals), tm.phase_profiler.steps) \
        if ctx.trace else None
    t_win = time.perf_counter()
    setup_s = t_win - ctx.t_start
    tm.fit(batch_fn, step + n, start_step=step)
    net.score()     # a host fetch of the last step's loss: the step is done
    window_s = time.perf_counter() - t_win
    rate = n * batch / window_s
    ctx.log(f"window: {n} steps of {batch} in {window_s:.3f} s "
            f"(warm-up step {step_s * 1e3:.1f} ms)")

    traces1 = dict(net._jit_cache.trace_counts())
    facts = {"steps": n, "batch": batch, "window_s": window_s,
             "compiles_in_window": sum(traces1.values())
             - sum(traces0.values())}
    if ctx.trace:
        pp = tm.phase_profiler
        facts["phase_seconds"] = {k: v - phases0[0].get(k, 0.0)
                                  for k, v in pp.totals.items()}
        facts["phase_steps"] = pp.steps - phases0[1]

    state = {"net": net, "tm": tm}

    def free():
        state["net"].params = None
        state["net"].updater_states = None
        state["net"].states = None
        state.clear()

    def check():
        ref = ctx.reference.train_steps(
            w, pool[:CHECK_STEPS], lr, float(cfg["momentum"]))
        return compare(prog, ref, leaf_names(w), ctx.log)

    return SimpleNamespace(
        attempted=n, failed=0, facts=facts, free=free, check=check,
        end_to_end={"train_img_per_s": rate, "setup_s": setup_s})


def study(ctx, seeds):
    """For each seed, in this one process: the program's first steps,
    the reference's, the control's (the reference in the program's place
    with every convolution in scaled fp8), a witness's (the reference's
    path in bfloat16, the program's own precision) and the half-batch
    fault's (the reference on the first half of each batch); each
    compared with the reference. Yields one dict per seed."""
    from benchmark.correct import leaf_names
    from benchmark.traffic import generate

    cfg, ref = ctx.config, ctx.reference
    net, tm = build(ctx)
    lr = float(cfg["constructor"]["learning_rate"])
    mu = float(cfg["momentum"])
    mix = dict(ctx.mix, pool=CHECK_STEPS)
    for seed in seeds:
        w = _weights(ctx, seed)
        names = leaf_names(w)
        install(net, w)
        pool = generate.batch_pool(mix, seed, int(cfg["image_size"]),
                                   int(cfg["num_classes"]))
        sides = {"program": first_steps(
            net, tm, w, lambda s: pool[s % len(pool)], lr)}
        want = ref.train_steps(w, pool, lr, mu)
        half = len(pool[0][0]) // 2
        sides["control_fp8"] = ref.train_steps(w, pool, lr, mu, "fp8")
        sides["witness_bf16"] = ref.train_steps(w, pool, lr, mu, "bf16")
        sides["fault_half_batch"] = ref.train_steps(
            w, [(x[:half], y[:half]) for x, y in pool], lr, mu)
        yield dict({"seed": seed, "losses": want[0].tolist()},
                   **{k: compare(v, want, names, ctx.log)
                      for k, v in sides.items()})
