"""The representative compiled-program set `dl4j-analyze --programs`
lints.

One small instance of every registered compiled-program family, built
the same way production builds them (same cache paths, same policy
registration) but at CPU-lintable dims:

  engine_single / _group_k4   StepProgram on a bf16 mixed-precision MLP
  engine_graph                StepProgram on a ComputationGraph (the
                              flat-chain train program)
  engine_tbptt                the train_c program with donated carries
  engine_resnet50 / _group_k2 StepProgram on zoo ResNet50 built as the
                              training cell builds it (Nesterov, bf16
                              compute, fused helper tier) at reduced
                              dims: the single step and the k-step
                              scan group
  engine_zero1                the ZeRO-1 mesh-sharded step over the
                              CPU device mesh, example args staged
                              sharded — the prog-unsharded-optimizer-
                              state record (the CLI forces 8 virtual
                              CPU devices so the dp axis is real)
  serving_predict / buckets   ParallelInference warmup + a short driven
                              load, so bucket fill is MEASURED
  decode_step / decode_prefill  the continuous-batching decode engine
                              (engine/decode_program.py): the shared
                              [max_slots] decode step and one pow2
                              prefill bucket, KV-cache donation
                              DECLARED so prog-unhonored-donation
                              verifies no silent per-token copy of the
                              [n_layers, 2, max_slots, max_ctx, ...]
                              buffer
  clustering_kmeans_lloyd     the donated Lloyd iteration
  clustering_tsne_step        the donated embedding step (the program
                              whose dropped donation the first audit
                              run caught)

Everything here imports jax — it is loaded lazily by the runner ONLY
in `--programs` mode, so the default AST-only CLI keeps its zero-
dependency contract. The CLI pins JAX_PLATFORMS=cpu before anything
imports jax; the whole set builds + lints in well under 60s on CPU.
"""

from __future__ import annotations

import os
from typing import List

from deeplearning4j_tpu.analysis.program_lint import ProgramRecord


def _engine_records() -> List[ProgramRecord]:
    import jax.numpy as jnp

    from deeplearning4j_tpu import (
        MultiLayerNetwork,
        NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.engine import StepProgram
    from deeplearning4j_tpu.nn.conf import InputType
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.layers import (
        LSTM,
        DenseLayer,
        OutputLayer,
        RnnOutputLayer,
    )

    records: List[ProgramRecord] = []

    # single step + k-group on the bf16 mixed-precision MLP
    conf = (NeuralNetConfiguration.Builder().seed(7).updater("adam")
            .learning_rate(1e-3).activation("relu")
            .weight_init("xavier").list()
            .layer(DenseLayer(n_out=32))
            .layer(OutputLayer(n_out=8, loss="mcxent"))
            .set_input_type(InputType.feed_forward(16)).build())
    net = MultiLayerNetwork(conf, compute_dtype="bfloat16").init()
    records += StepProgram(net).lint_records(
        jnp.zeros((8, 16), jnp.float32), jnp.zeros((8, 8), jnp.float32),
        k=4)

    # ComputationGraph variant (flat-chain train program)
    gconf = (NeuralNetConfiguration.Builder().seed(5).updater("adam")
             .learning_rate(1e-3).activation("relu")
             .weight_init("xavier").graph_builder()
             .add_inputs("in")
             .add_layer("d1", DenseLayer(n_out=16), "in")
             .add_layer("out", OutputLayer(n_out=4, loss="mcxent"),
                        "d1")
             .set_outputs("out")
             .set_input_types(**{"in": InputType.feed_forward(8)})
             .build())
    g = ComputationGraph(gconf, compute_dtype="bfloat16").init()
    records += StepProgram(g).lint_records(
        jnp.zeros((8, 8), jnp.float32), jnp.zeros((8, 4), jnp.float32))

    # truncated-BPTT LSTM (the train_c program with donated carries)
    rconf = (NeuralNetConfiguration.Builder().seed(3).updater("adam")
             .learning_rate(1e-3).weight_init("xavier").list()
             .layer(LSTM(n_out=16))
             .layer(RnnOutputLayer(n_out=4, loss="mcxent"))
             .set_input_type(InputType.recurrent(8))
             .backprop_type("truncated_bptt")
             .t_bptt_forward_length(4).t_bptt_backward_length(4)
             .build())
    rnet = MultiLayerNetwork(rconf, compute_dtype="bfloat16").init()
    records += StepProgram(rnet).lint_records(
        jnp.zeros((2, 4, 8), jnp.float32),
        jnp.zeros((2, 4, 4), jnp.float32))
    return records


def _serving_records() -> List[ProgramRecord]:
    import numpy as np

    from deeplearning4j_tpu import (
        MultiLayerNetwork,
        NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.conf import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.parallel.inference import ParallelInference

    conf = (NeuralNetConfiguration.Builder().seed(11).updater("sgd")
            .learning_rate(0.05).activation("tanh")
            .weight_init("xavier").list()
            .layer(DenseLayer(n_out=32))
            .layer(OutputLayer(n_out=8, loss="mcxent"))
            .set_input_type(InputType.feed_forward(16)).build())
    net = MultiLayerNetwork(conf, compute_dtype="bfloat16").init()
    pi = ParallelInference(net, batch_limit=8, queue_limit=16,
                           max_wait_ms=1.0, warmup=True,
                           pipeline_depth=0)
    try:
        # drive a short load so bucket fill is measured, not assumed
        for rows in (8, 8, 4):
            pi.output(np.zeros((rows, 16), np.float32), timeout_s=60.0)
        return pi.lint_records()
    finally:
        pi.shutdown()


def _clustering_records() -> List[ProgramRecord]:
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.clustering import kmeans, tsne

    rng = np.random.default_rng(0)
    pts = jnp.asarray(rng.normal(size=(32, 4)).astype(np.float32))
    records = [ProgramRecord(
        name="clustering_kmeans_lloyd", fn=kmeans._lloyd_step,
        example_args=(pts, pts[:4]),
        example_kwargs={"metric": "euclidean"},
        precision_policy="f32",
        source="deeplearning4j_tpu/clustering/kmeans.py")]

    n, k, blk, c = 6, 3, 4, 2
    n_pad = -(-n // blk) * blk      # 8: pad-mismatch donation case
    y = jnp.zeros((n_pad, c), jnp.float32)
    records.append(ProgramRecord(
        name="clustering_tsne_step", fn=tsne._chunked_step,
        example_args=(y, jnp.zeros_like(y),
                      jnp.zeros((n, k), jnp.int32),
                      jnp.full((n, k), 1e-3, jnp.float32),
                      jnp.zeros((n, k), bool),
                      jnp.float32(4.0), jnp.float32(0.5),
                      jnp.float32(100.0)),
        example_kwargs={"row_block": blk, "n_real": n},
        precision_policy="f32",
        source="deeplearning4j_tpu/clustering/tsne.py"))
    return records


def _resnet50_records() -> List[ProgramRecord]:
    """The program the training cell times: zoo ResNet50 with the
    constructor of the benchmark's `resnet50-imagenet` configuration,
    through StepProgram, at a size the CPU lowers in seconds."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.engine import StepProgram
    from deeplearning4j_tpu.zoo import ResNet50

    net = ResNet50(num_classes=8, input_shape=(32, 32, 3),
                   updater="nesterovs", learning_rate=0.01,
                   compute_dtype="bfloat16",
                   helpers="fused").init_model()
    return StepProgram(net).lint_records(
        jnp.zeros((2, 32, 32, 3), jnp.float32),
        jnp.zeros((2, 8), jnp.float32), k=2, name="engine_resnet50")


def build_default_records() -> List[ProgramRecord]:
    """Build the whole representative set. Pins JAX_PLATFORMS=cpu when
    nothing chose a platform yet — the lint must behave identically on
    a TPU host and in CI."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    records: List[ProgramRecord] = []
    records += _engine_records()
    records += _mesh_records()
    records += _serving_records()
    records += _decode_records()
    records += _clustering_records()
    records += _resnet50_records()
    return records


def _decode_records() -> List[ProgramRecord]:
    """The continuous-batching decode programs at CPU-lintable dims —
    paged decode step, chunked prefill, and the copy-on-write page
    copy — built through the same JitCache paths DecodeEngine runs
    (policy registered, donation of the physical page pool DECLARED so
    prog-unhonored-donation checks the executable alias map)."""
    from deeplearning4j_tpu.engine.decode_program import DecodeProgram
    from deeplearning4j_tpu.zoo.decoder import CausalTransformer

    model = CausalTransformer(vocab_size=64, d_model=32, n_heads=4,
                              n_layers=2, max_ctx=64, seed=17).init()
    prog = DecodeProgram(model, max_slots=4, page_size=16)
    return prog.lint_records()


def _mesh_records() -> List[ProgramRecord]:
    """The ZeRO-1 mesh-sharded StepProgram (engine/sharding.py) over
    the CPU device mesh, with example args staged exactly as the live
    path stages them (optimizer state SHARDED) — the record
    `prog-unsharded-optimizer-state` verifies. Empty when the platform
    exposes a single device (the rule is vacuous without a dp axis;
    the CLI forces 8 virtual CPU devices)."""
    import jax
    import jax.numpy as jnp

    if len(jax.devices()) < 2:
        return []

    from deeplearning4j_tpu import (
        MultiLayerNetwork,
        NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.engine import MeshManager, StepProgram
    from deeplearning4j_tpu.nn.conf import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer

    n_dev = len(jax.devices())
    conf = (NeuralNetConfiguration.Builder().seed(13).updater("adam")
            .learning_rate(1e-3).activation("relu")
            .weight_init("xavier").list()
            .layer(DenseLayer(n_out=4 * n_dev))
            .layer(OutputLayer(n_out=n_dev, loss="mcxent"))
            .set_input_type(InputType.feed_forward(2 * n_dev))
            .build())
    net = MultiLayerNetwork(conf).init()
    mgr = MeshManager()
    net.params = mgr.replicate_tree(net.params)
    net.updater_states = mgr.shard_tree(net.updater_states)
    net.states = mgr.replicate_tree(net.states)
    prog = StepProgram(net).attach_mesh(mgr)
    return [prog.lint_record_zero1(
        jnp.zeros((2 * n_dev, 2 * n_dev), jnp.float32),
        jnp.zeros((2 * n_dev, n_dev), jnp.float32))]
