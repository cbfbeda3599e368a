"""chip_smoke.py — the quickest proof that the system still starts on
the chip.

    python chip_smoke.py            one chip: kernels, training, serving
    python chip_smoke.py --chips 4  four chips: the dp mesh and ZeRO-1
                                    against one device, nothing else

Drives the main path once, through the entry points a user calls, at
the full width of the models the repository runs:

  runtime   block_until_ready against the least time a known amount of
            work can take, and the cost of one small dispatch + fetch.
  kernels   the four Pallas entry points of nn/helpers/pallas_conv.py
            at a ResNet50 batch-128 width, compiled by Mosaic (never
            interpret mode), against the pure-jnp references beside
            them.
  train     zoo ResNet50, 224x224x3, 1000 classes, batch 128, bf16
            compute, helpers="fused", through TrainingMaster.fit ->
            StepProgram.run for a few steps, with a guard, a listener
            and a checkpoint writer reading state after each donated
            step; then one StepProgram.run_group dispatch.
  serve     CausalTransformer (vocab 512, d_model 128, 4 heads, 4
            layers, ctx 128) in f32 and in bf16 compute: DecodeProgram
            warm-up, a threaded DecodeEngine answering mixed-length
            prompts, two of which share a 3-page prefix, one request
            over HTTP (ModelServer + ModelClient), every token stream
            compared with serving.continuous.sequential_decode.
  mesh      (--chips 4 only) the same ResNet50 on make_mesh(dp=4),
            replicated and under sharding="zero1", against the
            one-device run with the same seed and batches.

Weights and data are random, made from SEED. Every rate printed is an
observation of one run, not a benchmark. One JSON line per phase, then
as the LAST line `{"ok": true, "device": {...}}` with the device as jax
reports it. Exit code 0 only if every check held; a phase that raises
ends the run with a traceback and a non-zero code. Without a TPU it
exits 1 before any phase and prints no result. One process: it starts
no child, so nothing else asks for the chip.
"""

import argparse
import json
import sys
import tempfile
import time

import numpy as np

SEED = 42

# The sizes the script is run at. tests/test_chip_smoke.py runs the same
# phases at toy sizes on the CPU (rehearsal 1 of the on-chip-measurement
# guide); nothing else may shrink them.
FULL = {
    # sixteen chained 8192^3 bf16 matmuls: 17.6 TFLOP, at least 89 ms
    "runtime": dict(n=8192, chain=16),
    "train": dict(batch=128, hw=224, n_classes=1000, steps=8),
    # ResNet50 stage 1 at batch 128: 56x56 spatial, 64 -> 256 channels
    "kernels": dict(batch=128, hw=56, c_mid=64, c_out=256),
    "serve": dict(vocab_size=512, d_model=128, n_heads=4, n_layers=4,
                  max_ctx=128),
    "mesh": dict(batch=128, hw=224, n_classes=1000, steps=4),
    # the tiny preset of the latent-attention, sparse-expert block
    # (tests/test_latent_moe.py), 4 of its 8 experts held, in bfloat16
    "latent": dict(vocab_size=512, hidden=64, n_heads=4, q_lora_rank=24,
                   kv_lora_rank=16, qk_nope_dim=16, qk_rope_dim=8,
                   v_head_dim=16, dense_ff=128, moe_ff=32, n_experts=8,
                   top_k=2, experts_held=(0, 1, 2, 5), n_dense_layers=1,
                   n_moe_layers=2, max_ctx=128, param_dtype="bfloat16"),
}


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def report(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


def run_phase(name: str, phase, **kwargs) -> None:
    """Run one phase and print its facts, and the seconds it took, as
    one JSON line. A phase that raises ends the run: nothing here
    catches it."""
    t0 = time.perf_counter()
    facts = phase(**kwargs)
    report(name, **facts,
           phase_seconds=round(time.perf_counter() - t0, 1))


def require_chip():
    """The device every phase runs on, or exit 1: no TPU, no result.
    Also refuses a backend on which the Pallas kernels would pick
    interpret mode (`pallas_conv._interpret`), which is for CPU tests."""
    import jax

    from deeplearning4j_tpu.nn.helpers import pallas_conv

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: jax found no TPU (platform {dev.platform!r}, "
            f"kind {dev.device_kind!r}); nothing was checked")
    if pallas_conv._interpret():
        raise SystemExit(
            "chip_smoke: the Pallas kernels would run in interpret "
            f"mode on backend {jax.default_backend()!r}")
    return dev


def _rel_err(got, ref) -> float:
    """max |got - ref| over max |ref|, in f32: the error at the scale
    of the tensor, which is what bf16 rounding bounds."""
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(got.shape == ref.shape, f"shape {got.shape} != {ref.shape}")
    check(np.all(np.isfinite(got)), "non-finite kernel output")
    return float(np.max(np.abs(got - ref))
                 / (np.max(np.abs(ref)) + 1e-30))


# ----------------------------------------------------------------- runtime
def phase_runtime(peak_flops, n, chain) -> dict:
    """Two facts every timing in this repository rests on, looked at
    once on the machine at hand. `jax.block_until_ready` must wait for
    the work: a chain of matmuls cannot finish sooner than its flops
    over the chip's peak, so a wait that returns earlier did not wait.
    And what one small dispatch plus a host fetch costs, which bounds
    every loop that returns to the host each step (the decode engine)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def work(a):
        for _ in range(chain):
            a = jnp.dot(a, a) * (1.0 / n)
        return a

    x = jnp.ones((n, n), jnp.bfloat16)
    float(work(x)[0, 0])                      # compile, warm
    floor = 2.0 * n ** 3 * chain / peak_flops
    t0 = time.perf_counter()
    y = work(x)
    t_dispatch = time.perf_counter() - t0
    jax.block_until_ready(y)
    t_block = time.perf_counter() - t0
    float(y[0, 0])
    t_fetch = time.perf_counter() - t0 - t_block
    check(t_block >= floor,
          f"block_until_ready returned after {t_block:.4f}s, sooner than "
          f"the {floor:.4f}s the work needs at the chip's peak: it is "
          "no barrier here")
    tiny = jax.jit(lambda a: a + 1)
    one = jnp.zeros((8,), jnp.int32)
    int(tiny(one)[0])
    trips = []
    for _ in range(50):
        t0 = time.perf_counter()
        int(tiny(one)[0])
        trips.append(time.perf_counter() - t0)
    return {"dispatch_returned_seconds": round(t_dispatch, 5),
            "block_until_ready_seconds": round(t_block, 5),
            "least_possible_seconds": round(floor, 5),
            "fetch_after_block_seconds": round(t_fetch, 5),
            "small_dispatch_and_fetch_seconds_median":
                round(float(np.median(trips)), 6)}


# ----------------------------------------------------------------- kernels
# bf16 keeps 8 bits: one rounding is 2^-8 = 0.4% of a value. Kernel and
# reference both accumulate in f32 but round intermediates (u, y, du) at
# slightly different points and sum in another order, so a few such
# roundings separate them; 2% of the tensor's scale is five of them.
KERNEL_TOL = 2e-2


def phase_kernels(batch, hw, c_mid, c_out) -> dict:
    """fused_conv1x1 / fused_conv3x3 against ref_fused_conv1x1 /
    ref_fused_conv3x3, and dgrad_conv1x1 / wgrad_conv1x1 against the
    vjp of ref_fused_conv1x1 taken in f32, all in the affine + relu
    prologue form the fused graph runs between two convolutions."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.helpers import pallas_conv as pc

    m = batch * hw * hw
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(SEED), 8)
    x4 = jax.random.normal(ks[0], (batch, hw, hw, c_mid), bf)
    x = x4.reshape(m, c_mid)
    w1 = (jax.random.normal(ks[1], (c_mid, c_out)) * 0.1).astype(bf)
    w3 = (jax.random.normal(ks[2], (3, 3, c_mid, c_mid)) * 0.1).astype(bf)
    b1 = jax.random.normal(ks[3], (c_out,))
    b3 = b1[:c_mid]
    s = jax.random.normal(ks[4], (c_mid,)) * 0.5 + 1.0
    t = jax.random.normal(ks[5], (c_mid,)) * 0.1
    dy = jax.random.normal(ks[6], (m, c_out), bf)
    errs = {}

    y, ssum, ssq, u = pc.fused_conv1x1(x, w1, b1, scale=s, shift=t,
                                       relu=True, emit_u=True)
    ry, rsum, rsq, ru = pc.ref_fused_conv1x1(x, w1, b1, scale=s, shift=t,
                                             relu=True, emit_u=True)
    errs["fused_conv1x1"] = max(_rel_err(y, ry), _rel_err(ssum, rsum),
                                _rel_err(ssq, rsq), _rel_err(u, ru))

    y3, ssum3, ssq3 = pc.fused_conv3x3(x4, w3, b3, scale=s, shift=t,
                                       relu=True)
    r3 = pc.ref_fused_conv3x3(x4, w3, b3, scale=s, shift=t, relu=True)
    errs["fused_conv3x3"] = max(_rel_err(y3, r3[0]), _rel_err(ssum3, r3[1]),
                                _rel_err(ssq3, r3[2]))

    def ref_y(x_, w_, b_, s_, t_):
        return pc.ref_fused_conv1x1(x_, w_, b_, scale=s_, shift=t_,
                                    relu=True)[0]

    f32 = jnp.float32
    _, vjp = jax.vjp(ref_y, x.astype(f32), w1.astype(f32), b1, s, t)
    rdx, rdw, rdb, rds, rdt = vjp(dy.astype(f32))
    dx1, _, ds1, dt1, _, _, db = pc.dgrad_conv1x1(
        dy, y, w1, x, scale=s, shift=t, relu=True)
    errs["dgrad_conv1x1"] = max(_rel_err(dx1, rdx), _rel_err(ds1, rds),
                                _rel_err(dt1, rdt), _rel_err(db, rdb))
    dw = pc.wgrad_conv1x1(dy, y, x, scale=s, shift=t, relu=True)
    errs["wgrad_conv1x1"] = _rel_err(dw, rdw)

    for name, err in errs.items():
        check(err <= KERNEL_TOL,
              f"{name}: {err:.3g} of the tensor's scale from its "
              f"reference (tolerance {KERNEL_TOL})")
    return {"interpret": pc._interpret(), "rows": m,
            "c_mid": c_mid, "c_out": c_out,
            "rel_err": {k: round(v, 5) for k, v in errs.items()}}


# ------------------------------------------------------------------- train
class _StepLog:
    """A listener, as a user attaches one: fetches the score after
    every step — after that step's donated buffers are gone — and notes
    when, and what had been traced by then."""

    def __init__(self):
        self.losses, self.times, self.traces = [], [], []

    def iteration_done(self, net, iteration):
        self.losses.append(float(net.score()))   # waits for the step
        self.times.append(time.perf_counter())
        self.traces.append(net._jit_cache.trace_counts())


def _resnet50(hw, n_classes):
    from deeplearning4j_tpu.zoo import ResNet50

    return ResNet50(num_classes=n_classes, input_shape=(hw, hw, 3),
                    updater="nesterovs", learning_rate=1e-2, seed=SEED,
                    compute_dtype="bfloat16",
                    helpers="fused").init_model()


def _batch(batch, hw, n_classes, seed):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(batch, hw, hw, 3)).astype(np.float32)
    y = np.eye(n_classes, dtype=np.float32)[
        rng.integers(0, n_classes, batch)]
    return x, y


def _fit(net, batch_fn, steps, platform, **tm_kwargs):
    """TrainingMaster(net, ...).fit(batch_fn, steps) with a _StepLog
    attached, and the checks every training run here must pass.
    Returns (tm, log, seconds of the first step, median of the rest)."""
    import jax

    from deeplearning4j_tpu.parallel.training_master import TrainingMaster

    log = _StepLog()
    net.listeners.append(log)
    tm = TrainingMaster(net, **tm_kwargs)
    t0 = time.perf_counter()
    tm.fit(batch_fn, steps)
    check(len(log.losses) == steps, f"{len(log.losses)} losses logged")
    check(all(np.isfinite(log.losses)), f"non-finite loss {log.losses}")
    check(log.traces[-1] == log.traces[0],
          f"retraced after the first step: {log.traces[0]} -> "
          f"{log.traces[-1]}")
    for leaf in jax.tree_util.tree_leaves(net.params):
        check(next(iter(leaf.devices())).platform == platform,
              f"a parameter lives on {leaf.devices()}, not on "
              f"{platform}")
    first = log.times[0] - t0
    steady = float(np.median(np.diff(log.times))) if steps > 1 else first
    return tm, log, first, steady


def phase_train(platform, batch, hw, n_classes, steps) -> dict:
    import jax

    from deeplearning4j_tpu.parallel.training_master import TrainingMaster
    from deeplearning4j_tpu.resilience import NonFiniteGuard
    from deeplearning4j_tpu.stats import InMemoryStatsStorage, StatsListener

    t0 = time.perf_counter()
    net = _resnet50(hw, n_classes)
    build_s = time.perf_counter() - t0
    # one batch, repeated: a few steps memorize it, so the loss must
    # come down whatever the noise of bf16
    x, y = _batch(batch, hw, n_classes, SEED)
    batch_fn = lambda step: (x, y)   # noqa: E731
    net.listeners.append(StatsListener(InMemoryStatsStorage(),
                                       frequency=4))
    guard = NonFiniteGuard("skip_step", check_every=1)
    with tempfile.TemporaryDirectory() as ckpt:
        tm, log, first, steady = _fit(
            net, batch_fn, steps, platform, guard=guard,
            checkpoint_dir=ckpt, checkpoint_every=max(2, steps // 2))
        saved = tm.list_checkpoints()
    losses = log.losses
    check(losses[-1] < losses[0], f"loss did not come down: {losses}")
    check(saved, "no checkpoint was written")
    check(guard.counters["checks"] >= steps
          and guard.counters["nonfinite"] == 0
          and guard.counters["skipped_steps"] == 0,
          f"guard counters {guard.counters}")

    # one k-step group: steps_per_dispatch=2 takes TrainingMaster.fit
    # through StepProgram.run_group, one dispatch for two steps
    k = 2
    t0 = time.perf_counter()
    TrainingMaster(net, steps_per_dispatch=k).fit(
        batch_fn, steps + k, start_step=steps)
    group_s = time.perf_counter() - t0
    check(net.iteration == steps + k, f"iteration {net.iteration}")
    check(len(losses) == steps + 1 and np.isfinite(losses[-1]),
          f"run_group loss {losses[steps:]}")
    groups = {key: n for key, n in net._jit_cache.trace_counts().items()
              if "engine_group" in key}
    check(list(groups.values()) == [1], f"group traces {groups}")
    compile_s = sum(ev["duration_s"]
                    for ev in net._jit_cache.compile_events())
    return {"losses": [round(v, 4) for v in losses[:steps]],
            "group_last_loss": round(losses[-1], 4),
            "compile_seconds": round(compile_s, 2),
            "model_build_seconds": round(build_s, 2),
            "first_step_seconds": round(first, 2),
            # wall time of each later step: a spike is a listener, the
            # guard or the checkpoint writer compiling or writing
            "later_step_seconds": [round(float(d), 3)
                                   for d in np.diff(log.times[:steps])],
            "group_dispatch_seconds": round(group_s, 2),
            "steady_step_seconds_observed": round(steady, 4),
            "images_per_second_observed": round(batch / steady, 1),
            "trace_counts": net._jit_cache.trace_counts(),
            "checkpoints": saved,
            "peak_bytes_in_use": (jax.devices()[0].memory_stats() or {})
            .get("peak_bytes_in_use")}


# ------------------------------------------------------------------- serve
def _prompts(vocab, page_size, max_ctx):
    """Mixed-length prompts; the last two share a 3-page prefix and
    differ in their tails."""
    rng = np.random.default_rng(SEED)
    lens = [3, 9, page_size, page_size + 5, 2 * page_size + 1,
            max_ctx // 2]
    mixed = [[int(t) for t in rng.integers(0, vocab, n)] for n in lens]
    prefix = [int(t) for t in rng.integers(0, vocab, 3 * page_size)]
    twins = [prefix + [1, 2, 3], prefix + [4, 5]]
    return mixed, twins


def phase_serve(compute_dtype, **model_cfg) -> dict:
    import jax

    from deeplearning4j_tpu.engine.decode_program import DecodeProgram
    from deeplearning4j_tpu.parallel.serving import ModelClient, ModelServer
    from deeplearning4j_tpu.serving.continuous import (
        DecodeEngine,
        sequential_decode,
    )
    from deeplearning4j_tpu.zoo.decoder import CausalTransformer

    max_new = 24
    model = CausalTransformer(seed=SEED, compute_dtype=compute_dtype,
                              **model_cfg).init()
    prog = DecodeProgram(model, max_slots=8, page_size=16)
    eng = DecodeEngine(program=prog)
    t0 = time.perf_counter()
    eng.kv = prog.warmup(eng.kv)
    jax.block_until_ready(eng.kv)
    warm_s = time.perf_counter() - t0
    warm_counts = prog.trace_stats()["trace_counts"]
    check(len(warm_counts) == 3
          and all(v == 1 for v in warm_counts.values()),
          f"warm-up traced {warm_counts}")

    mixed, twins = _prompts(model.vocab_size, prog.page_size,
                            model.max_ctx)
    server = ModelServer(port=0, decode_engine=eng,
                         model_name="decoder")
    eng.start()
    try:
        t0 = time.perf_counter()
        handles = [eng.submit(p, max_new) for p in mixed + twins[:1]]
        outs = [h.result(timeout_s=300) for h in handles]
        # the second twin joins after the first has finished, so its
        # prefix pages are in the trie whatever the placement order
        outs.append(eng.submit(twins[1], max_new).result(timeout_s=300))
        engine_s = time.perf_counter() - t0
        server.start()
        client = ModelClient(f"http://127.0.0.1:{server.port}",
                             breaker=None)
        http = client.generate(mixed[1], max_new_tokens=max_new,
                               model="decoder")
        check(prog.trace_stats()["trace_counts"] == warm_counts,
              f"traffic retraced: {prog.trace_stats()['trace_counts']}")
        # the decode step is lowered and compiled again on THIS thread
        # while the engine thread steps and fetches (nxt, ok) for a
        # live request
        live = eng.submit(mixed[-1], 2 * max_new)
        step = prog.lint_records()[0]
        cost = step.fn.lower(*step.example_args).compile().cost_analysis()
        live_out = live.result(timeout_s=300)
    finally:
        server.stop()
        eng.stop()
    stats = eng.stats()
    audit = eng._pool.audit()

    prompts = mixed + twins
    for prompt, got in zip(prompts, outs):
        want = sequential_decode(prog, prompt, max_new)[1]
        check(got == want, f"engine {got} != oracle {want} for a "
              f"{len(prompt)}-token prompt")
    check([int(t) for t in http["tokens"]]
          == sequential_decode(prog, mixed[1], max_new)[1],
          f"HTTP tokens {http['tokens']} differ from the oracle")
    check(live_out == sequential_decode(prog, mixed[-1], 2 * max_new)[1],
          "tokens decoded while the main thread compiled differ")
    check(stats["prefix_requests_hit"] >= 1,
          f"no prefix hit: {stats['prefix_requests_hit']}")
    check(audit["leaked"] == 0 and not audit["double_freed"],
          f"page audit {audit}")
    check(cost.get("flops", 0) > 0,
          f"no cost analysis on this backend: {cost}")
    tokens = sum(len(o) for o in outs)
    return {"compute_dtype": compute_dtype or "float32",
            "warmup_compile_seconds": round(warm_s, 2),
            "requests": len(outs) + 2, "tokens": tokens,
            "tokens_per_second_observed": round(tokens / engine_s, 1),
            "prefix_requests_hit": stats["prefix_requests_hit"],
            "cow_copies": stats["cow_copies"],
            "decode_steps": stats["steps"],
            "prefill_chunks": stats["prefill_chunks"],
            "trace_counts": warm_counts, "audit": audit,
            "decode_step_flops": cost["flops"],
            "first_tokens": outs[0][:8]}


def phase_latent(**model_cfg) -> dict:
    """The second model family through the same `DecodeProgram` and
    engine: latent page pool, absorbed and expanded attention, the
    expert layer with its counters; engine against oracle, bitwise."""
    import jax

    from deeplearning4j_tpu.engine.decode_program import DecodeProgram
    from deeplearning4j_tpu.serving.continuous import (
        DecodeEngine,
        sequential_decode,
    )
    from deeplearning4j_tpu.zoo.latent_moe import LatentMoETransformer

    max_new = 24
    model = LatentMoETransformer(seed=SEED, **model_cfg).init()
    prog = DecodeProgram(model, max_slots=8, page_size=16)
    eng = DecodeEngine(program=prog)
    eng.kv = prog.warmup(eng.kv)
    jax.block_until_ready(eng.kv)
    warm_counts = prog.trace_stats()["trace_counts"]
    mixed, twins = _prompts(model.vocab_size, prog.page_size,
                            model.max_ctx)
    eng.start()
    try:
        handles = [eng.submit(p, max_new) for p in mixed + twins[:1]]
        outs = [h.result(timeout_s=300) for h in handles]
        outs.append(eng.submit(twins[1], max_new).result(timeout_s=300))
    finally:
        eng.stop()
    stats = eng.stats()
    check(prog.trace_stats()["trace_counts"] == warm_counts,
          f"traffic retraced: {prog.trace_stats()['trace_counts']}")
    for prompt, got in zip(mixed + twins, outs):
        want = sequential_decode(prog, prompt, max_new)[1]
        check(got == want, f"engine {got} != oracle {want} for a "
              f"{len(prompt)}-token prompt")
    check(stats["prefix_requests_hit"] >= 1,
          f"no prefix hit: {stats['prefix_requests_hit']}")
    pairs = stats["moe_assignments"]
    check(0 < stats["moe_assignments_held"] < pairs,
          f"held {stats['moe_assignments_held']} of {pairs} pairs")
    return {"pool": list(prog.kv_shape), "pool_dtype": model.kv_dtype,
            "requests": len(outs), "decode_steps": stats["steps"],
            "moe_assignments": stats["moe_assignments"],
            "moe_assignments_held": stats["moe_assignments_held"],
            "prefix_requests_hit": stats["prefix_requests_hit"],
            "trace_counts": warm_counts, "first_tokens": outs[0][:8]}


# -------------------------------------------------------------------- mesh
# Why the three runs may differ at all: they are three programs. On four
# devices each holds 32 of the 128 rows, so every batch reduction (the
# loss mean, batch-norm statistics, the gradient sums) is a per-device
# partial sum plus an all-reduce or reduce-scatter, added in another
# order than on one device. In f32 that is 1e-5 of the loss at step 0
# (CPU rehearsal). The model computes in bf16, where a sum that rounds
# the other way flips a whole bf16 ulp (0.4%) of an activation, and 50
# layers pass it on: with identical parameters the CPU rehearsal (96x96,
# batch 32, bf16, four virtual devices) showed 0.9% of the loss at step
# 0 and 0.4-1.8% over the next three steps. 5% is about three times
# that; a wrong collective (a gradient summed over the shards instead of
# averaged, a shard's rows counted twice) changes the update fourfold
# and moves the next loss by far more.
MESH_VS_ONE_RTOL = 5e-2
# ZeRO-1 against the replicated mesh run is tighter: the two share the
# forward and backward pass, shard for shard, and differ only in how the
# f32 gradient is reduced before the update (reduce-scatter against
# all-reduce). Equal to every printed digit in the CPU rehearsals.
ZERO1_VS_REPLICATED_RTOL = 1e-2


def phase_mesh(platform, n_dev, batch, hw, n_classes, steps) -> dict:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.parallel.mesh import make_mesh

    devs = jax.devices()[:n_dev]
    check(len(devs) == n_dev, f"{len(devs)} devices, need {n_dev}")
    data = [_batch(batch, hw, n_classes, SEED + i) for i in range(steps)]
    batch_fn = data.__getitem__
    runs = {}
    facts = {}
    for name, kwargs in (
            ("one_device", dict(mesh=make_mesh(dp=1, devices=devs[:1]))),
            ("dp_replicated", dict(mesh=make_mesh(dp=n_dev, devices=devs))),
            ("dp_zero1", dict(mesh=make_mesh(dp=n_dev, devices=devs),
                              sharding="zero1"))):
        net = _resnet50(hw, n_classes)
        tm, log, first, steady = _fit(net, batch_fn, steps, platform,
                                      **kwargs)
        runs[name] = log.losses
        facts[name] = {"losses": [round(v, 4) for v in log.losses],
                       "first_step_seconds": round(first, 2),
                       "steady_step_seconds_observed": round(steady, 4)}
        if name == "one_device":
            continue
        want = set(devs)
        for leaf in jax.tree_util.tree_leaves(net.params):
            check(leaf.sharding.is_fully_replicated
                  and {s.device for s in leaf.addressable_shards} == want,
                  f"{name}: a parameter is not replicated on all "
                  f"{n_dev} devices: {leaf.sharding}")
        x, y = data[0]
        if name == "dp_zero1":
            holders = set()
            n_sharded = 0
            for leaf in jax.tree_util.tree_leaves(net.updater_states):
                if leaf.ndim and leaf.shape[0] % n_dev == 0:
                    shards = leaf.addressable_shards
                    on = {s.device for s in shards}
                    check(len(shards) == n_dev and on == want
                          and all(s.data.shape[0] * n_dev == leaf.shape[0]
                                  for s in shards),
                          f"updater-state leaf {leaf.shape} is not split "
                          f"over {n_dev} devices: {leaf.sharding}")
                    holders |= on
                    n_sharded += 1
            check(n_sharded > 0, "no updater-state leaf was sharded")
            facts[name]["sharded_state_leaves"] = n_sharded
            facts[name]["state_shard_devices"] = sorted(map(str, holders))
            rec = tm._harness.program.lint_record_zero1(x, y)
            fn, args = rec.fn, rec.example_args
        else:
            sh = NamedSharding(tm.mesh, P("dp"))
            fn, args = net.lint_program(
                {net.conf.network_inputs[0]: jax.device_put(x, sh)},
                [jax.device_put(y, sh)])
        # the same program once more through the AOT path, for its text
        with tm.mesh:
            text = fn.lower(*args).compile().as_text()
        found = [op for op in ("all-reduce", "reduce-scatter",
                               "all-gather") if op in text]
        check("all-reduce" in found or "reduce-scatter" in found,
              f"{name}: the compiled step holds no all-reduce or "
              f"reduce-scatter")
        facts[name]["collectives"] = found
    for name, ref_name, rtol in (
            ("dp_replicated", "one_device", MESH_VS_ONE_RTOL),
            ("dp_zero1", "one_device", MESH_VS_ONE_RTOL),
            ("dp_zero1", "dp_replicated", ZERO1_VS_REPLICATED_RTOL)):
        for step, (got, ref) in enumerate(zip(runs[name],
                                              runs[ref_name])):
            check(abs(got - ref) <= rtol * abs(ref),
                  f"step {step}: loss {got} of {name} against {ref} of "
                  f"{ref_name} (tolerance {rtol:.0%}); all losses: "
                  f"{runs}")
    return {"devices": [str(d) for d in devs], **facts}


# -------------------------------------------------------------------- main
def main(argv=None, sizes=FULL) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the dp-mesh / ZeRO-1 phase and "
                         "its one-device comparison, on four chips")
    args = ap.parse_args(argv)

    from deeplearning4j_tpu.nn.jit_cache import place_compile_cache

    cache_dir = place_compile_cache()
    import jax

    from benchmark.roofline import device_peaks
    from deeplearning4j_tpu import native

    t_start = time.perf_counter()
    dev = require_chip()
    peaks = device_peaks(str(dev.device_kind))
    peak_flops = peaks["flops"]
    report("start", platform=dev.platform, kind=str(dev.device_kind),
           count=len(jax.devices()), jax=jax.__version__,
           compile_cache_dir=cache_dir, native_available=native.available(),
           peak_flops=peak_flops, peak_bytes_per_s=peaks["bytes_per_s"])
    if args.chips == 4:
        check(len(jax.devices()) == 4,
              f"--chips 4 needs four devices, jax found "
              f"{len(jax.devices())}")
        run_phase("mesh", phase_mesh, platform=dev.platform, n_dev=4,
                  **sizes["mesh"])
    else:
        run_phase("runtime", phase_runtime, peak_flops=peak_flops,
                  **sizes["runtime"])
        run_phase("kernels", phase_kernels, **sizes["kernels"])
        run_phase("train", phase_train, platform=dev.platform,
                  **sizes["train"])
        for compute_dtype in (None, "bfloat16"):
            run_phase("serve", phase_serve, compute_dtype=compute_dtype,
                      **sizes["serve"])
        run_phase("latent", phase_latent, **sizes["latent"])
    report("done", seconds=round(time.perf_counter() - t_start, 1))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
