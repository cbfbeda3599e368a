"""Benchmark harness. Prints ONE JSON line:
{"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Flagship bench: ResNet50 ImageNet-shaped training throughput,
images/sec/chip (BASELINE.md config #2; the north-star metric), in the
standard bf16 mixed-precision policy (f32 master params, bf16 compute).
The reference publishes no numbers (BASELINE.md), so vs_baseline is the
ratio to this repo's first recorded measurement — it tracks progress
across rounds.

Hardening:
- every step's loss is a device scalar chained through donated params;
  the timed region ends with a host fetch of the final loss, which
  waits for all the work before it;
- the final loss must be finite;
- MFU > 1 is physically impossible and raises;
- device platform/kind and jax version are recorded so an environment
  artifact (e.g. libtpu version skew) can't masquerade as a speedup;
- the modes that report a per-chip rate or an MFU (flagship, ghostbn,
  vgg16, lstm, lenet, word2vec) exit non-zero when jax finds no TPU;
  the CPU A/B drills (engine, pipeline, mesh) run anywhere and say
  which platform they ran on.

Measurement notes (see PERF.md for the profiled step breakdown):
- batch resident on device: a production input pipeline double-buffers
  h2d transfers (DevicePrefetchIterator), so the bench times the step,
  not the copy.
- per-step dispatch, no lax.scan over steps: in rounds 1-5 (on a setup
  that no longer exists; not re-measured) scan wrapping cost ~11
  ms/step extra device time on ResNet50.

One process for each chip: this script starts no child process.
"""

import json
import time

import numpy as np

# First recorded measurements (one v5e chip). Update only to rebase.
BASELINES = {
    "resnet50_train_images_per_sec_per_chip": 1153.0,  # 2026-07-29, round 1
    "lenet_mnist_train_images_per_sec": 185061.6,    # 2026-07-29, round 1
}

def _spread(per_step_ms):
    """Variance record for the emitted JSON: per-timed-loop step times.
    The headline uses min (on a shared host, transients only ever slow
    a loop down), but the full spread is emitted so consumers can see
    the noise band."""
    xs = sorted(per_step_ms)
    return {
        "min": round(xs[0], 2),
        "median": round(float(np.median(xs)), 2),
        "max": round(xs[-1], 2),
        "n": len(xs),
        "headline": "min",
    }


# Legacy hand-derived constants: ResNet50 fwd ~= 4.09 GFLOPs/image
# @224; train ~= 3x fwd. Kept so the BENCH_r*.json `approx_mfu`
# trajectory stays comparable across rounds; the headline MFU now
# comes from XLA cost analysis (observability/perf.py CostModel,
# emitted as `mfu_cost_model`), and these constants double as the
# analytic fallback for backends whose cost analysis returns nothing.
RESNET50_TRAIN_FLOPS_PER_IMAGE = 3 * 4.09e9
VGG16_TRAIN_FLOPS_PER_IMAGE = 3 * 15.5e9
# peak table lives with the cost model (one source of truth)
from deeplearning4j_tpu.observability.perf import (  # noqa: E402
    CostModel,
    device_peaks,
)


def make_flagship_program(batch=128, hw=224, n_classes=1000, unroll=4,
                          compute_dtype="bfloat16", helpers="fused",
                          bn_stat_sample=1):
    """Build the flagship k-step train program WITHOUT compiling it:
    (jit_k, example_args, net, x). The bench AOT-compiles and times it;
    `dl4j-analyze --programs` lowers a reduced-dims instance and lints
    the jaxpr dtypes + alias map against the flagship's declared bf16
    policy (the compile takes minutes on CPU, the lowering seconds).

    Runs the fused helper tier (nn/helpers) and `unroll` grad-over-flat
    train steps per dispatch — the shape of a real training loop, which
    syncs with the host every few steps, not every step."""
    import functools

    import jax
    import jax.numpy as jnp

    from __graft_entry__ import _flagship

    net, _, _ = _flagship(batch=batch, hw=hw, n_classes=n_classes,
                          compute_dtype=compute_dtype,
                          helpers=helpers, bn_stat_sample=bn_stat_sample)
    rng = np.random.default_rng(0)
    x = jax.device_put(jnp.asarray(
        rng.normal(size=(batch, hw, hw, 3)).astype(np.float32)))
    y = jax.device_put(jnp.asarray(
        np.eye(n_classes, dtype=np.float32)[
            rng.integers(0, n_classes, batch)]))
    _ = float(jnp.sum(x[0, 0, 0]))   # force staging complete

    chain = net._flat_chain_obj()
    assert chain is not None, "flagship must be flat-chain eligible"
    from deeplearning4j_tpu.nn.updater import schedule_lr

    cd = net.compute_dtype

    def one_step(flat, uflat, states, step):
        from deeplearning4j_tpu.nn.dtype import cast_floating

        def loss_flat(fl):
            params = cast_floating(chain.unravel(fl), cd)
            loss, (ns, _) = net._loss_fn(
                params, states, {"input": x.astype(cd) if cd is not None
                                 else x}, [y], None, None,
                None, rnn_carries=None)
            return loss.astype(net.dtype), ns

        (loss, ns), g = jax.value_and_grad(loss_flat, has_aux=True)(flat)
        lr = schedule_lr(net.conf, step)
        deltas, new_u = chain.updater.update(g, uflat, flat, lr, step)
        return flat + deltas, new_u, ns, loss

    def k_steps_fn(flat, uflat, states, step):
        loss = None
        for i in range(unroll):
            flat, uflat, states, loss = one_step(flat, uflat, states,
                                                 step + i)
        return flat, uflat, states, loss

    flat = chain.ravel(net.params)
    uflat = chain.ravel_upd(net.updater_states)
    jit_k = functools.partial(jax.jit, donate_argnums=(0, 1, 2))(
        k_steps_fn)
    step0 = jnp.asarray(0, jnp.int32)
    return jit_k, (flat, uflat, net.states, step0), net, x


def bench_resnet50(batch=128, hw=224, iters=32, unroll=4,
                   compute_dtype="bfloat16", bn_stat_sample=1):
    """Steady-state training-step throughput, batch resident on device
    (the program built by `make_flagship_program`, AOT-compiled)."""
    import jax
    import jax.numpy as jnp

    jit_k, args, net, x = make_flagship_program(
        batch=batch, hw=hw, unroll=unroll, compute_dtype=compute_dtype,
        bn_stat_sample=bn_stat_sample)
    flat, uflat, states, step0 = args
    # AOT path (lower -> compile -> call): ONE compile serves both the
    # bench loop and the XLA cost analysis — the per-program flops /
    # bytes-accessed the CostModel turns into exact MFU, replacing the
    # hand-derived flops constant as the headline (legacy `approx_mfu`
    # still emitted for trajectory comparability).
    compiled = jit_k.lower(flat, uflat, states, step0).compile()
    cost_model = CostModel(device=jax.devices()[0])
    cost_model.register_compiled(
        "resnet50_k_steps", compiled,
        analytic_flops=RESNET50_TRAIN_FLOPS_PER_IMAGE * batch * unroll)
    k_steps = compiled
    flat, uflat, states, loss = k_steps(flat, uflat, states, step0)
    _ = float(loss)   # warmup/compile barrier

    assert iters % unroll == 0
    # 3 timed loops; headline = fastest (a shared host's transients
    # only ever ADD time), full spread emitted via _spread.
    dts = []
    for _ in range(3):
        t0 = time.perf_counter()
        for it in range(iters // unroll):
            flat, uflat, states, loss = k_steps(
                flat, uflat, states,
                jnp.asarray((it + 1) * unroll, jnp.int32))
        final_loss = float(loss)   # host fetch: true end-of-work barrier
        dts.append(time.perf_counter() - t0)
    assert np.isfinite(final_loss), f"non-finite loss {final_loss}"
    best_dt = min(dts)
    # seconds per compiled call (one call = `unroll` train steps)
    perf_report = cost_model.perf_report(
        "resnet50_k_steps",
        seconds_per_call=best_dt / (iters // unroll),
        items_per_call=batch * unroll)
    return (batch * iters / best_dt, best_dt / iters, final_loss,
            [d / iters * 1e3 for d in dts], perf_report)


def bench_lstm(batch=64, seq_len=256, vocab=98, iters=30, remat=False):
    """BASELINE config #3: GravesLSTM char-RNN tokens/sec
    (ref zoo/model/TextGenerationLSTM.java; LSTMHelpers.java:182,448).
    Run with `python bench.py lstm [batch] [remat]`; remat recomputes
    gates in BPTT (LSTM.bptt_remat — the cuDNN-LSTM tradeoff)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.zoo import TextGenerationLSTM

    zm = TextGenerationLSTM(num_classes=vocab,
                            input_shape=(seq_len, vocab),
                            compute_dtype="bfloat16")
    zm.bptt_remat = remat
    net = zm.init_model()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, (batch, seq_len))
    x = jax.device_put(jnp.asarray(
        np.eye(vocab, dtype=np.float32)[ids]))
    y = jax.device_put(jnp.asarray(
        np.eye(vocab, dtype=np.float32)[np.roll(ids, -1, 1)]))
    _ = float(jnp.sum(x[0, 0]))

    loss, _ = net._train_step(x, y)
    _ = float(loss)
    dts = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            loss, _ = net._train_step(x, y)
        final_loss = float(loss)
        dts.append(time.perf_counter() - t0)
    assert np.isfinite(final_loss)
    dt = min(dts)
    return (batch * seq_len * iters / dt, dt / iters, final_loss,
            [d / iters * 1e3 for d in dts])


def bench_lenet(batch=4096, iters=40):
    """BASELINE config #1: LeNet MNIST-shaped training throughput
    (ref zoo/model/LeNet.java). Run with `python bench.py lenet`."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.zoo import LeNet

    net = LeNet(num_classes=10, input_shape=(28, 28, 1)).init_model()
    rng = np.random.default_rng(0)
    x = jax.device_put(jnp.asarray(
        rng.normal(size=(batch, 28, 28, 1)).astype(np.float32)))
    y = jax.device_put(jnp.asarray(
        np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)]))
    _ = float(jnp.sum(x[0, 0]))
    loss = net.fit_batch((x, y))
    _ = float(loss)
    dts = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = net.fit_batch((x, y))
        final_loss = float(loss)
        dts.append(time.perf_counter() - t0)
    assert np.isfinite(final_loss)
    dt = min(dts)
    return (batch * iters / dt, dt / iters, final_loss,
            [d / iters * 1e3 for d in dts])


def bench_engine(k=8, iters=512, batch=256, n_in=64, n_out=10):
    """Engine dispatch amortization: the StepProgram's k-step lax.scan
    group (ONE dispatch per k steps) vs k=1 per-step dispatch, same
    net, same data stream, same rng chain (engine/step_program.py).
    Dispatch-bound regime by design: a small MLP where per-dispatch
    overhead dominates device compute, so the amortization is the
    signal, not the noise. Run with `python bench.py engine [k]`;
    `k=1` emits the ungrouped baseline (the perf_gate pair quoted in
    PERF.md compares the two artifacts)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu import (
        MultiLayerNetwork,
        NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.engine import StepProgram
    from deeplearning4j_tpu.nn.conf import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer

    conf = (NeuralNetConfiguration.Builder().seed(7).updater("adam")
            .learning_rate(1e-3).activation("relu")
            .weight_init("xavier").list()
            .layer(DenseLayer(n_out=128))
            .layer(OutputLayer(n_out=n_out, loss="mcxent"))
            .set_input_type(InputType.feed_forward(n_in)).build())
    net = MultiLayerNetwork(conf).init()
    program = StepProgram(net)
    rng = np.random.default_rng(0)
    import jax

    x = jax.device_put(jnp.asarray(
        rng.normal(size=(batch, n_in)).astype(np.float32)))
    y = jax.device_put(jnp.asarray(
        np.eye(n_out, dtype=np.float32)[rng.integers(0, n_out, batch)]))
    _ = float(jnp.sum(x[0]))
    assert iters % k == 0
    if k > 1:
        xs = jnp.broadcast_to(x, (k,) + x.shape)
        ys = jnp.broadcast_to(y, (k,) + y.shape)
        program.run_group(xs, ys)          # warmup/compile
        run_once = lambda: program.run_group(xs, ys)
    else:
        program.run(x, y)                  # warmup/compile
        run_once = lambda: program.run(x, y)
    _ = float(net._score)
    dts = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters // k):
            run_once()
        final_loss = float(net._score)   # host fetch: true barrier
        dts.append(time.perf_counter() - t0)
    assert np.isfinite(final_loss)
    dt = min(dts)
    return (batch * iters / dt, dt / iters, final_loss,
            [d / iters * 1e3 for d in dts])


def bench_pipeline(pipeline: bool, steps=48, etl_ms=12.0, batch=512,
                   n_in=256, hidden=512):
    """Input-pipeline A/B (`python bench.py pipeline` runs BOTH arms
    and writes BENCH_pipeline_{off,on}.json): one TrainingMaster fit —
    the engine choke point every entry point shares — over a
    deliberately slow host iterator (etl_ms of synthetic ETL per
    batch), with a StepPhaseProfiler attached. The pipeline arm's
    producer thread runs fetch + h2d staging ahead of the compute, so
    `data_wait`+`h2d` collapse while `device_compute` holds. On the
    CPU box the honest claim is ETL/dispatch-copy overlap (the ETL
    stall must fit under the step's compute to be hidden); the
    flagship h2d re-measure is queued for the next hardware session.
    Gate: `python tools/perf_gate.py --metric pipeline`."""
    import time as _time

    from deeplearning4j_tpu import (
        MultiLayerNetwork,
        NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.conf import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.observability.perf import StepPhaseProfiler
    from deeplearning4j_tpu.parallel.training_master import (
        TrainingMaster,
    )

    conf = (NeuralNetConfiguration.Builder().seed(7).updater("adam")
            .learning_rate(1e-3).activation("tanh")
            .weight_init("xavier").list()
            .layer(DenseLayer(n_out=hidden))
            .layer(DenseLayer(n_out=hidden))
            .layer(OutputLayer(n_out=10, loss="mcxent"))
            .set_input_type(InputType.feed_forward(n_in)).build())
    net = MultiLayerNetwork(conf).init()

    def slow_batch(step):
        _time.sleep(etl_ms / 1e3)   # synthetic ETL (decode/augment)
        rng = np.random.default_rng(step)
        x = rng.normal(size=(batch, n_in)).astype(np.float32)
        y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)]
        return x, y

    tm = TrainingMaster(net, pipeline=pipeline)
    tm.fit(slow_batch, 2)                 # compile warm-up, unprofiled
    # a sync a step: this drill compares shares against device_compute
    tm.phase_profiler = StepPhaseProfiler(sync_every=1)
    t0 = time.perf_counter()
    tm.fit(slow_batch, 2 + steps, start_step=2)
    dt = time.perf_counter() - t0
    stats = tm.training_stats()
    return steps / dt, stats["phases"], stats["pipeline"]


def bench_mesh(n_devices=None, steps=64, batch=512, n_in=512,
               hidden=2048, n_out=64):
    """Sharded scale-out A/B + scaling curve (`python bench.py mesh
    [n]` writes BENCH_mesh_{off,on}.json): the SAME dp-sharded batch
    stream through the unsharded (replicated optimizer state)
    StepProgram vs the ZeRO-1 mesh-sharded one (arXiv 2004.13336) on a
    CPU device mesh, plus an img/s-vs-n_devices sweep for the zero1
    arm — the scaling-efficiency headline shape the MULTICHIP bench
    reruns on real hardware. The model is deliberately update-heavy
    (fat hidden layers) because the replicated arm pays the FULL
    weight update on every replica while zero1 pays 1/n of it; the
    per-replica optimizer-state bytes come from real shard shapes
    (`MeshManager.memory_facts`). Gate:
    `python tools/perf_gate.py --metric mesh`."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import (
        MultiLayerNetwork,
        NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.engine import MeshManager, StepProgram
    from deeplearning4j_tpu.nn.conf import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer

    all_devs = list(jax.devices())
    n_devices = n_devices or len(all_devs)

    def build(seed=7):
        conf = (NeuralNetConfiguration.Builder().seed(seed)
                .updater("adam").learning_rate(1e-3).activation("tanh")
                .weight_init("xavier").list()
                .layer(DenseLayer(n_out=hidden))
                .layer(DenseLayer(n_out=hidden))
                .layer(OutputLayer(n_out=n_out, loss="mcxent"))
                .set_input_type(InputType.feed_forward(n_in)).build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    x_host = rng.normal(size=(batch, n_in)).astype(np.float32)
    y_host = np.eye(n_out, dtype=np.float32)[
        rng.integers(0, n_out, batch)]

    def run_arm(n, zero1):
        net = build()
        mgr = MeshManager(devices=all_devs[:n])
        tree = jax.tree_util.tree_map
        net.params = mgr.replicate_tree(tree(np.asarray, net.params))
        stage = mgr.shard_tree if zero1 else mgr.replicate_tree
        net.updater_states = stage(tree(np.asarray,
                                        net.updater_states))
        net.states = mgr.replicate_tree(tree(np.asarray, net.states))
        prog = StepProgram(net)
        if zero1:
            prog.attach_mesh(mgr)
        xb = jax.device_put(jnp.asarray(x_host), mgr.batch_sharding())
        yb = jax.device_put(jnp.asarray(y_host), mgr.batch_sharding())
        prog.run(xb, yb)                 # warmup/compile
        _ = float(net._score)
        dts = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(steps):
                prog.run(xb, yb)
            _ = float(net._score)        # host fetch: true barrier
            dts.append(time.perf_counter() - t0)
        assert np.isfinite(float(net._score))
        mem = mgr.memory_facts(net.updater_states)
        return batch * steps / min(dts), mem, \
            [d / steps * 1e3 for d in dts]

    ips_off, mem_off, ms_off = run_arm(n_devices, zero1=False)
    ips_on, mem_on, ms_on = run_arm(n_devices, zero1=True)
    # scaling sweep (zero1): img/s and per-replica optimizer bytes
    # per device count — the curve the 8-chip MULTICHIP bench re-runs
    sweep = []
    n = 1
    while n <= n_devices:
        ips_n, mem_n, _ = run_arm(n, zero1=True)
        sweep.append({"n_devices": n,
                      "images_per_sec": round(ips_n, 1),
                      "replica_optimizer_bytes":
                          mem_n["replica_bytes"],
                      "scaling_efficiency": None})
        n *= 2
    base = sweep[0]["images_per_sec"]
    for entry in sweep:
        entry["scaling_efficiency"] = round(
            entry["images_per_sec"] / (base * entry["n_devices"]), 3)
    return {"off": (ips_off, mem_off, ms_off),
            "on": (ips_on, mem_on, ms_on), "sweep": sweep,
            "n_devices": n_devices}


def bench_word2vec(vocab=5000, n_words=2_000_000, dim=128, window=5,
                   k_neg=5, epochs=5):
    """Secondary benchmark: Word2Vec skip-gram + negative sampling
    (ref SkipGram.java:224 hot loop / native AggregateSkipGram role).
    Dense tier: native single-pass epoch builder + slab-scan device
    updates. Run with `python bench.py word2vec`."""
    from deeplearning4j_tpu.nlp.sequence_vectors import SequenceVectors

    rng = np.random.default_rng(0)
    p = 1.0 / np.arange(1, vocab + 1) ** 1.1
    p /= p.sum()
    words = np.array([f"w{i}" for i in range(vocab)])
    corpus = rng.choice(vocab, size=n_words, p=p)
    seqs = [list(words[corpus[i:i + 1000]])
            for i in range(0, n_words, 1000)]
    sv = SequenceVectors(layer_size=dim, window=window, negative=k_neg,
                         epochs=1, seed=1, mode="dense")
    sv.build_vocab(seqs)
    sv.fit(seqs)          # warm: compiles the slab shapes
    _ = sv.syn0           # materialize host copy (excluded d2h)
    _ = sv.syn1neg
    sv.epochs = epochs
    dts = []
    for _ in range(2):   # 2 reps (each is `epochs` full epochs)
        t0 = time.perf_counter()
        sv.fit(seqs)
        # barrier: a host scalar fetch waits for the whole fit
        _ = float(np.asarray(sv._syn0_dev[0, 0]))
        dts.append(time.perf_counter() - t0)
    dt = min(dts)
    # stability sanity: the whole table must be finite (a summed
    # duplicate scatter NaN'd the zipf head words in an early build)
    assert np.all(np.isfinite(sv.syn0)), "non-finite embeddings"
    assert np.isfinite(sv.similarity("w0", "w1"))
    return n_words * epochs / dt, dt, dts


def bench_vgg16(batch=32, hw=224, iters=12):
    """BASELINE config #4 at full fidelity: canonical Keras VGG16
    (138.4M params) imported from HDF5, frozen-base vs full fine-tune
    step times at 224x224 with TrainedModels.VGG16 preprocessing.
    Run with `python bench.py vgg16`. Generates a random-weight VGG16
    .h5 via tf.keras on first use (cached in /tmp)."""
    import os

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.normalizers import (
        VGG16ImagePreProcessor,
    )
    from deeplearning4j_tpu.modelimport.keras import KerasModelImport
    from deeplearning4j_tpu.nn.transferlearning import TransferLearning

    h5 = "/tmp/vgg16_224_bench.h5"
    if not os.path.exists(h5):
        import tensorflow as tf

        tf.keras.applications.VGG16(weights=None, classes=1000).save(h5)
    rng = np.random.default_rng(0)
    mean = np.asarray(VGG16ImagePreProcessor.MEAN_RGB, np.float32)
    x = jax.device_put(jnp.asarray(
        rng.uniform(0, 255, (batch, hw, hw, 3)).astype(np.float32)
        - mean))
    y = jax.device_put(jnp.asarray(
        np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, batch)]))
    _ = float(jnp.sum(x[0, 0, 0]))

    def run(net):
        name = net.conf.network_inputs[0]
        net._train_step({name: x}, [y])
        _ = float(net.score())
        dts = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(iters):
                net._train_step({name: x}, [y])
            _ = float(net.score())
            dts.append((time.perf_counter() - t0) / iters)
        assert np.isfinite(float(net.score()))
        return min(dts), [d * 1e3 for d in dts]

    frozen = (TransferLearning.GraphBuilder(
        KerasModelImport.import_keras_model_and_weights(h5))
        .set_feature_extractor("block5_pool").build())
    frozen.compute_dtype = jnp.bfloat16
    dt_frozen = run(frozen)
    full = KerasModelImport.import_keras_model_and_weights(h5)
    full.compute_dtype = jnp.bfloat16
    dt_full = run(full)
    return dt_frozen, dt_full, batch


def _require_chip(dev, mode):
    """The modes that print a per-chip rate or an MFU measure the chip
    or nothing: a CPU timing under those names would be read as a
    device number."""
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py {mode}: this mode reports a per-chip metric and "
            f"runs on a TPU only; jax found {dev.platform!r} "
            f"({dev.device_kind}). Not measured.")


def main():
    import sys

    import jax

    from deeplearning4j_tpu.nn.jit_cache import place_compile_cache

    place_compile_cache()
    dev = jax.devices()[0]
    if len(sys.argv) > 1 and sys.argv[1] == "engine":
        ek = int(sys.argv[2]) if len(sys.argv) > 2 else 8
        ips, step_s, loss, step_ms = bench_engine(k=ek)
        print(json.dumps({
            "metric": "engine_step_program_examples_per_sec",
            "value": round(ips, 1),
            "unit": "examples/sec",
            "vs_baseline": 1.0,
            "steps_per_dispatch": ek,
            "step_time_ms": round(step_s * 1e3, 3),
            "step_ms_spread": _spread(step_ms),
            "final_loss": round(loss, 3),
            "config": f"mlp 64-128-10 batch=256 adam k={ek} "
                      "(dispatch-bound regime)",
            "device": str(dev.device_kind),
            "platform": str(dev.platform),
            "jax": jax.__version__,
        }))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "pipeline":
        for arm, on in (("off", False), ("on", True)):
            sps, phases, pipe = bench_pipeline(on)
            shares = {p: round(v["share"], 3)
                      for p, v in phases["phases"].items()}
            doc = {
                "metric": "pipeline_train_steps_per_sec",
                "value": round(sps, 2),
                "unit": "steps/sec",
                "vs_baseline": 1.0,
                "pipeline": arm,
                "phase_shares": shares,
                "coverage": round(phases["coverage"], 3),
                "pipeline_facts": pipe,
                "config": "mlp 256-512-512-10 batch=512 adam, 12ms "
                          "synthetic ETL/batch (CPU: ETL/dispatch-copy"
                          " overlap; flagship h2d re-measure queued "
                          "for hardware)",
                "device": str(dev.device_kind),
                "platform": str(dev.platform),
                "jax": jax.__version__,
            }
            with open(f"BENCH_pipeline_{arm}.json", "w") as f:
                json.dump(doc, f)
            print(json.dumps(doc))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "mesh":
        mn = int(sys.argv[2]) if len(sys.argv) > 2 else None
        res = bench_mesh(n_devices=mn)
        for arm in ("off", "on"):
            ips, mem, ms = res[arm]
            doc = {
                "metric": "mesh_train_images_per_sec",
                "value": round(ips, 1),
                "unit": "images/sec",
                "vs_baseline": 1.0,
                "sharding": "zero1" if arm == "on" else "replicated",
                "n_devices": res["n_devices"],
                "replica_optimizer_bytes": mem["replica_bytes"],
                "full_optimizer_bytes": mem["full_bytes"],
                "replica_optimizer_fraction":
                    round(mem["replica_fraction"], 4),
                "step_ms_spread": _spread(ms),
                "scaling_curve": (res["sweep"] if arm == "on"
                                  else None),
                "config": "mlp 512-2048-2048-64 batch=512 adam "
                          "(update-heavy: replicated arm pays the "
                          "full weight update per replica, zero1 "
                          "pays 1/n)",
                "device": str(dev.device_kind),
                "platform": str(dev.platform),
                "jax": jax.__version__,
            }
            with open(f"BENCH_mesh_{arm}.json", "w") as f:
                json.dump(doc, f)
            print(json.dumps(doc))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "word2vec":
        _require_chip(dev, "word2vec")
        wps, dt, dts = bench_word2vec()
        print(json.dumps({
            "metric": "word2vec_sgns_words_per_sec_per_chip",
            "value": round(wps, 1),
            "unit": "words/sec/chip",
            "vs_baseline": 1.0,
            "total_s": round(dt, 1),
            "rep_ms_spread": _spread([d * 1e3 for d in dts]),
            "config": "vocab=5k zipf dim=128 window=5 K=5 "
                      "5 epochs x 2M words, dense tier",
            "device": str(dev.device_kind),
            "platform": str(dev.platform),
            "jax": jax.__version__,
        }))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "vgg16":
        _require_chip(dev, "vgg16")
        vb = int(sys.argv[2]) if len(sys.argv) > 2 else 32
        (dt_frozen, frozen_ms), (dt_full, full_ms), b = bench_vgg16(
            batch=vb, iters=max(4, 256 // vb))
        vgg_mfu = (b / dt_full) * VGG16_TRAIN_FLOPS_PER_IMAGE \
            / device_peaks(dev)[0]
        print(json.dumps({
            "metric": "vgg16_finetune_224_images_per_sec_per_chip",
            "value": round(b / dt_full, 1),
            "unit": "images/sec/chip",
            "vs_baseline": 1.0,
            "full_step_ms": round(dt_full * 1e3, 1),
            "full_step_ms_spread": _spread(full_ms),
            "frozen_step_ms": round(dt_frozen * 1e3, 1),
            "frozen_step_ms_spread": _spread(frozen_ms),
            "frozen_images_per_sec": round(b / dt_frozen, 1),
            "approx_mfu": round(vgg_mfu, 3),
            "config": f"batch={b} bf16 224x224 canonical keras VGG16 "
                      "(b256+: ~30% MFU, see PERF.md)",
            "device": str(dev.device_kind),
            "platform": str(dev.platform),
            "jax": jax.__version__,
        }))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "lenet":
        _require_chip(dev, "lenet")
        ips, step_s, loss, step_ms = bench_lenet()
        base = BASELINES.get("lenet_mnist_train_images_per_sec")
        print(json.dumps({
            "metric": "lenet_mnist_train_images_per_sec",
            "value": round(ips, 1),
            "unit": "images/sec",
            "vs_baseline": round(ips / base, 3) if base else 1.0,
            "step_time_ms": round(step_s * 1e3, 2),
            "step_ms_spread": _spread(step_ms),
            "final_loss": round(loss, 3),
            "config": "batch=4096 f32 28x28",
            "device": str(dev.device_kind),
            "platform": str(dev.platform),
            "jax": jax.__version__,
        }))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "lstm":
        _require_chip(dev, "lstm")
        b = int(sys.argv[2]) if len(sys.argv) > 2 else 64
        remat = len(sys.argv) > 3 and sys.argv[3] == "remat"
        tps, step_s, loss, step_ms = bench_lstm(batch=b, remat=remat)
        print(json.dumps({
            "metric": "lstm_char_rnn_tokens_per_sec_per_chip",
            "value": round(tps, 1),
            "unit": "tokens/sec/chip",
            "vs_baseline": 1.0,
            "step_time_ms": round(step_s * 1e3, 1),
            "step_ms_spread": _spread(step_ms),
            "final_loss": round(loss, 3),
            "config": f"batch={b} seq=256 vocab=98 2xLSTM(256)" + (" bptt_remat" if remat else ""),
            "device": str(dev.device_kind),
            "platform": str(dev.platform),
            "jax": jax.__version__,
        }))
        return
    ghost_k = 1
    if len(sys.argv) > 1 and sys.argv[1] == "ghostbn":
        ghost_k = int(sys.argv[2]) if len(sys.argv) > 2 else 4
    _require_chip(dev, "ghostbn" if ghost_k > 1 else "flagship")
    ips, step_s, loss, step_ms, perf_report = bench_resnet50(
        bn_stat_sample=ghost_k)
    key = ("resnet50_train_images_per_sec_per_chip" if ghost_k == 1 else
           "resnet50_ghostbn_train_images_per_sec_per_chip")
    base = BASELINES.get(key)
    vs = 1.0 if not base else ips / base
    peak = device_peaks(dev)[0]
    # legacy constant-derived MFU (trajectory comparability) ...
    mfu = ips * RESNET50_TRAIN_FLOPS_PER_IMAGE / peak
    # ... and the cost-model headline (XLA-counted flops, exact)
    mfu_cm = perf_report["mfu"]
    if mfu > 1.0 or mfu_cm > 1.0:
        raise SystemExit(
            f"MFU {mfu:.3f}/{mfu_cm} > 1.0 is physically impossible: "
            "the harness or environment is broken; refusing to record")
    out = {
        "metric": key,
        "value": round(ips, 1),
        "unit": "images/sec/chip",
        "vs_baseline": round(vs, 3),
        "step_time_ms": round(step_s * 1e3, 1),
        "step_ms_spread": _spread(step_ms),
        # the flagship groups `unroll` steps into one compiled dispatch
        # (bench_resnet50's k_steps_fn — the engine StepProgram's
        # k-group role); recorded so rounds are comparable on dispatch
        # amortization, not just throughput
        "steps_per_dispatch": 4,
        "approx_mfu": round(mfu, 3),
        "mfu_cost_model": round(mfu_cm, 3),
        "final_loss": round(loss, 3),
        "config": "batch=128 bf16-mixed-precision 224x224"
                  + (f" ghost-bn stat_sample={ghost_k}"
                     if ghost_k > 1 else ""),
        "device": str(dev.device_kind),
        "platform": str(dev.platform),
        "jax": jax.__version__,
    }
    out["perf"] = {
        "source": perf_report["source"],
        "flops_per_image": round(perf_report["flops_per_item"], 1),
        "bytes_accessed": perf_report["bytes_accessed"],
        "arithmetic_intensity": round(
            perf_report.get("arithmetic_intensity") or 0.0, 2),
        "roofline_bound": perf_report.get("bound"),
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
