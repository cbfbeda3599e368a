"""ParallelWrapper: data-parallel training over a device mesh.

Parity: deeplearning4j-scaleout-parallelwrapper/.../ParallelWrapper.java:54
(fit loop :211-260, param averaging via Nd4j.averageAndPropagate :320,
updater-state averaging :332-365) and its SHARED_GRADIENTS mode (:60-64).

TPU-native design: the reference spawns one trainer thread + model replica
per device and periodically averages parameters over PCIe. Here the
"replicas" are one jit-compiled step over a `Mesh` whose dp axis shards
the batch; the gradient all-reduce is inserted by XLA (GSPMD) because the
loss is a mean over the globally-sharded batch while params are
replicated — it rides ICI and is fused into the step. Both reference
modes collapse to this:

- SHARED_GRADIENTS (per-step gradient exchange) == the default here.
  Threshold compression (EncodingHandler.java:64) is unnecessary on ICI.
- AVERAGING every k steps (local SGD) == `averaging_frequency=k`, done
  with an explicit shard_map: each dp group keeps private params for k
  local steps, then `pmean`s params + updater state (the reference's
  averageUpdatersState, ParallelWrapper.java:332-365).

Tensor parallelism (`tp` mesh axis > 1) shards weight matrices per
sharding.py rules — a capability with no reference counterpart.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.engine import StepHarness, make_loss_and_apply
from deeplearning4j_tpu.parallel.mesh import make_mesh
from deeplearning4j_tpu.parallel.sharding import (
    param_shardings,
    shard_batch,
)


def _require_local_sgd(averaging_frequency: int, threshold: float):
    """Shared validation: threshold compression only exists at the
    local-SGD rendezvous."""
    if threshold > 0.0 and max(1, averaging_frequency) <= 1:
        raise ValueError(
            "threshold_compression requires averaging_frequency > 1 "
            "(it encodes the k-step delta at the local-SGD rendezvous; "
            "the per-step GSPMD all-reduce path has no host-visible "
            "exchange to encode)")


def _disable_flat_chain(net):
    """The grad-over-flat carry (updater/flat_chain.py) concatenates
    every parameter into ONE flat vector — under a tp-sharded or
    GSPMD-driven net that forces a full all-gather of the model each
    step (it deadlocked the virtual-mesh dryrun); mesh-driven training
    always uses the per-layer tree path."""
    if hasattr(net, "_flat_chain"):
        net._materialize_flat()
        net._flat_chain = None


class ParallelWrapper:
    """Data/tensor-parallel trainer around a MultiLayerNetwork/ComputationGraph.

    Usage (mirrors the reference Builder):
        pw = ParallelWrapper(net, workers=8)           # dp=8
        pw = ParallelWrapper(net, workers=4, tp=2)     # dp=4 x tp=2
        pw.fit(iterator)
    """

    def __init__(self, net, workers: Optional[int] = None, tp: int = 1,
                 averaging_frequency: int = 1, average_updaters: bool = True,
                 mesh: Optional[Mesh] = None, prefetch_buffer: int = 2,
                 threshold_compression: float = 0.0,
                 guard=None, watchdog=None, snapshot_every: int = 0,
                 phase_profiler=None,
                 steps_per_dispatch: int = 1,
                 pipeline: Optional[bool] = None,
                 sharding: Optional[str] = None):
        """`guard`/`watchdog` (resilience/supervisor.py) give fit() the
        same self-healing hooks as TrainingMaster: the NonFiniteGuard
        checks loss+params after (sampled) steps and skips or aborts on
        non-finite state; the StepWatchdog heartbeats per batch and
        escalates a hung step/collective. `rollback` policy needs a
        rollback target: pass `snapshot_every=N` and an in-memory
        device snapshot of the pre-step state is refreshed every N
        guarded steps (resilience.PeriodicSnapshotter) — a poisoned
        step rewinds to the newest snapshot, losing at most N-1 good
        steps (no checkpoint directory required)."""
        self.net = net
        self.threshold_compression = float(threshold_compression)
        _require_local_sgd(averaging_frequency,
                           self.threshold_compression)
        self._snapshotter = None
        if guard is not None and guard.policy == "rollback":
            if snapshot_every <= 0:
                raise ValueError(
                    "NonFiniteGuard(policy='rollback') under "
                    "ParallelWrapper needs snapshot_every=N > 0 (an "
                    "in-memory rollback target; TrainingMaster uses "
                    "checkpoints instead)")
            from deeplearning4j_tpu.resilience.supervisor import (
                PeriodicSnapshotter,
            )

            self._snapshotter = PeriodicSnapshotter(
                guard, every=snapshot_every)
        if mesh is None:
            n = len(jax.devices())
            workers = workers if workers is not None else max(1, n // tp)
            mesh = make_mesh(dp=workers, tp=tp)
        self.mesh = mesh
        self.dp = mesh.shape["dp"]
        self.averaging_frequency = max(1, averaging_frequency)
        self.average_updaters = average_updaters
        self.prefetch_buffer = prefetch_buffer
        # `steps_per_dispatch=k > 1`: batches (MASKS INCLUDED — the
        # PR 9 gap that forced fm/lm nets onto the k=1 path) group into
        # k-windows run through the engine's lax.scan group program in
        # ONE dispatch; byte-identical to k sequential steps.
        self.steps_per_dispatch = max(1, int(steps_per_dispatch))
        if self.steps_per_dispatch > 1 and self.averaging_frequency > 1:
            raise ValueError(
                "steps_per_dispatch > 1 and averaging_frequency > 1 "
                "are mutually exclusive groupings (the local-SGD "
                "rendezvous already scans its k steps in one dispatch)")
        # harness-owned input pipeline (engine/pipeline.py): async ETL
        # + device staging ahead of the compute. Default (None): ON for
        # single-process jobs; pipeline=False opts out.
        self.pipeline = pipeline
        self._sharded = False
        self._local_step = None
        # ONE supervisor (engine/): guard-verdict dispatch, watchdog
        # lifecycle, the StepAccumulator per-step telemetry batches
        # through, and the phase profiler (every step funnels through
        # _run_guarded, so dispatch/host_sync phases land there;
        # data_wait/h2d are not visible at this altitude)
        self._harness = StepHarness(
            net, guard=guard, watchdog=watchdog,
            snapshotter=self._snapshotter,
            phase_profiler=phase_profiler)
        self.guard = self._harness.guard
        self.watchdog = self._harness.watchdog
        self._obs_acc = self._harness.acc
        self.phase_profiler = self._harness.phase_profiler
        # ZeRO-1 (engine/sharding.py): optimizer state sharded over
        # this wrapper's dp axis, update reduce-scattered/shard-local/
        # all-gathered inside the one compiled step — equal to the
        # replicated program within a few ulp (pinned in test_mesh.py)
        if sharding not in (None, "replicated", "zero1"):
            raise ValueError(
                f"sharding must be None|'replicated'|'zero1': {sharding}")
        self.zero1 = sharding == "zero1"
        self._mesh_mgr = None
        if self.zero1:
            if self.mesh.shape["tp"] != 1:
                raise NotImplementedError(
                    "sharding='zero1' requires tp == 1 (the ZeRO "
                    "update shards the dp axis of replicated params)")
            if self.averaging_frequency > 1:
                raise ValueError(
                    "sharding='zero1' and averaging_frequency > 1 are "
                    "incompatible (local SGD keeps per-shard params)")
            from deeplearning4j_tpu.engine.mesh import MeshManager

            self._mesh_mgr = MeshManager(mesh=self.mesh)
            self._harness.program.attach_mesh(self._mesh_mgr)

    # ------------------------------------------------------------------
    def _ensure_sharded(self):
        """Place the net's params/updater state onto the mesh (replicated
        over dp, tp-sharded per rules)."""
        if self._sharded:
            return
        ins = getattr(self.net.conf, "network_inputs", None)
        outs = getattr(self.net.conf, "network_outputs", None)
        self._multi_io = ins is not None and (len(ins) > 1 or len(outs) > 1)
        if self._multi_io and self.averaging_frequency > 1:
            raise NotImplementedError(
                "averaging_frequency > 1 supports single-input/single-"
                "output graphs only")
        if self.net.params is None:
            self.net.init()
        _disable_flat_chain(self.net)
        put = lambda tree: jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, s),
            tree, param_shardings(self.mesh, tree))
        self.net.params = put(self.net.params)
        if self._mesh_mgr is not None:
            # ZeRO-1: optimizer state placed SHARDED over dp (1/n per
            # replica) instead of replicated
            self.net.updater_states = self._mesh_mgr.shard_tree(
                jax.tree_util.tree_map(np.asarray,
                                       self.net.updater_states))
        else:
            self.net.updater_states = put(self.net.updater_states)
        self.net.states = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, NamedSharding(self.mesh, P())),
            self.net.states)
        self._sharded = True

    def _pad_batch(self, x):
        b = np.asarray(x).shape[0]
        rem = (-b) % self.dp
        if rem == 0:
            return np.asarray(x), 0
        pad = np.zeros((rem,) + tuple(x.shape[1:]), x.dtype)
        return np.concatenate([np.asarray(x), pad], axis=0), rem

    def _pad_with_masks(self, x, y, fm, lm):
        return _pad_batch_with_masks(self.dp, x, y, fm, lm)

    def _run_guarded(self, thunk) -> bool:
        """Run one training step/group under the shared harness's
        guard dispatch (engine.StepHarness.guarded); False means the
        step was rejected and the pre-step (skip_step) or
        newest-snapshot (rollback) state restored (callers skip
        listeners for rejected steps). Every ParallelWrapper
        step/group funnels through here: the one emission site covers
        single-step, local-SGD, and multi-io paths alike (batched;
        fit() flushes at loop end)."""
        return self._harness.guarded(thunk, context="detected")

    # ------------------------------------------------------------------
    def fit(self, data, epochs: int = 1):
        """Train. `data` is any iterator/list of batches the wrapped net
        accepts (ref fit loop: ParallelWrapper.java:211-260).

        averaging_frequency == 1 (default): one GSPMD step per batch,
        per-step gradient all-reduce (SHARED_GRADIENTS semantics).
        averaging_frequency == k > 1: batches are grouped k at a time and
        run through LocalStepTrainer — each dp shard takes k local SGD
        steps on its own data, then params (+ updater state) are pmean'd
        (AVERAGING semantics, ParallelWrapper.java:320,332-365).
        """
        self._ensure_sharded()
        net = self.net
        batches = data if hasattr(data, "__iter__") else [data]
        k = self.averaging_frequency
        if k > 1 and self._local_step is None:
            self._local_step = LocalStepTrainer(
                net, self.mesh, average_updaters=self.average_updaters,
                threshold=self.threshold_compression)
        # harness-owned input pipeline: AsyncDataSetIterator ->
        # DevicePrefetchIterator staging (pad + dp-shard on the way
        # through), so data_wait/h2d overlap device_compute. The
        # local-SGD and multi-io paths restack on host, so they take
        # the async ETL overlap only (host_only).
        pre_staged = False
        if self._pipeline_enabled():
            # zero1 stages on the consumer thread (host_only): staging
            # batch k+1 while a donated SHARDED-state execution is in
            # flight corrupts the heap in this jaxlib's CPU runtime
            # (reproducibly, only with a warm persistent compile
            # cache); the async-ETL overlap is kept, the device copy
            # moves next to the dispatch
            host_only = (k > 1 or getattr(self, "_multi_io", False)
                         or self.zero1)
            batches = self._harness.build_iterator_pipeline(
                batches, depth=self.prefetch_buffer,
                stage=None if host_only else self._stage_batch,
                host_only=host_only,
                meta={"mesh": dict(self.mesh.shape)})
            pre_staged = not host_only
        else:
            # one shared session lifecycle (engine/): watchdog
            # start/stop, accumulator flush, attached-iterator close
            self._harness.attach_data(batches)
        with self._harness.session():
            self._fit_loop(batches, epochs, k, self.watchdog,
                           pre_staged)
        return self

    def _pipeline_enabled(self) -> bool:
        if self.pipeline is not None:
            return bool(self.pipeline)
        return jax.process_count() == 1

    def _stage_batch(self, batch):
        """Pipeline staging for ONE batch: pad + dp-shard exactly as
        the synchronous loop would, so the consumer receives
        (x, y, fm, lm) device arrays in the same layout and the
        compiled step's byte-level evolution is unchanged."""
        net = self.net
        x, y, fm, lm = self._pad_with_masks(*_as_batch(batch))
        return (shard_batch(self.mesh, jnp.asarray(x, net.dtype)),
                shard_batch(self.mesh, jnp.asarray(y, net.dtype)),
                None if fm is None
                else shard_batch(self.mesh, jnp.asarray(fm)),
                None if lm is None
                else shard_batch(self.mesh, jnp.asarray(lm)))

    def _fit_loop(self, batches, epochs, k, wd, pre_staged=False):
        net = self.net
        k2 = self.steps_per_dispatch
        with self.mesh:
            for _ in range(epochs):
                if hasattr(batches, "reset"):
                    batches.reset()
                group = []      # local-SGD rendezvous window (host)
                window = []     # run_group k-window (staged or host)
                for batch in batches:
                    if wd is not None:
                        wd.beat("batch")
                    if getattr(self, "_multi_io", False):
                        if self._run_guarded(
                                lambda b=batch: self._fit_multi_io(b)):
                            for listener in net.listeners:
                                listener.iteration_done(net,
                                                        net.iteration)
                        continue
                    if pre_staged:
                        # the pipeline already padded + dp-sharded
                        x, y, fm, lm = batch
                    else:
                        x, y, fm, lm = self._pad_with_masks(
                            *_as_batch(batch))
                    if k > 1:
                        group.append((x, y, fm, lm))
                        if len(group) == k:
                            g = group
                            group = []
                            self._run_guarded(
                                lambda: self._local_step.run(g))
                        continue
                    if k2 > 1:
                        entry = (x, y, fm, lm)
                        if window and not _window_compatible(
                                window[-1], entry):
                            # shape break: dispatch the shorter window
                            # (compiled once per distinct k)
                            self._run_window(window)
                            window = []
                        window.append(entry)
                        if len(window) == k2:
                            self._run_window(window)
                            window = []
                        continue
                    if pre_staged:
                        xb, yb, fmb, lmb = x, y, fm, lm
                    else:
                        xb = shard_batch(self.mesh,
                                         jnp.asarray(x, net.dtype))
                        yb = shard_batch(self.mesh,
                                         jnp.asarray(y, net.dtype))
                        fmb = (None if fm is None else
                               shard_batch(self.mesh, jnp.asarray(fm)))
                        lmb = (None if lm is None else
                               shard_batch(self.mesh, jnp.asarray(lm)))
                    program = self._harness.program
                    program.require_sgd("ParallelWrapper")

                    def one_step(xb=xb, yb=yb, fmb=fmb, lmb=lmb):
                        # the shared StepProgram owns the graph-input /
                        # TBPTT dispatch; the sharded batch dim flows
                        # through unchanged (GSPMD inserts the grad
                        # all-reduce into the same compiled step)
                        program.run(xb, yb, fmb, lmb)

                    if self._run_guarded(one_step):
                        for listener in net.listeners:
                            listener.iteration_done(net, net.iteration)
                if group:
                    # trailing group smaller than k: run it as a shorter
                    # local-step stack (compiled once per distinct size)
                    g = group
                    self._run_guarded(lambda: self._local_step.run(g))
                if window:
                    self._run_window(window)
                net.epoch += 1

    def _run_window(self, window) -> bool:
        """One `run_group` dispatch over a k-window, MASKS STACKED
        ALONGSIDE FEATURES — the carried-forward PR 9 gap: fm/lm
        batches previously had no grouped path in ParallelWrapper.
        Mask-less batches sharing a window with masked ones get
        all-ones masks (exactly LocalStepTrainer.run's equalization),
        and the stack is staged [k, ...] with the step dim replicated
        and the batch dim dp-sharded. run_group(k) is byte-identical
        to k sequential steps (pinned in test_pipeline.py for a masked
        net)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        net = self.net
        program = self._harness.program
        program.require_sgd("ParallelWrapper")
        any_fm = any(w[2] is not None for w in window)
        any_lm = any(w[3] is not None for w in window)
        xs, ys, fms, lms = [], [], [], []
        for x, y, fm, lm in window:
            x = jnp.asarray(x, net.dtype)
            y = jnp.asarray(y, net.dtype)
            if any_fm and fm is None:
                fm = jnp.ones((x.shape[0],) + (() if x.ndim == 2
                                               else (x.shape[1],)),
                              jnp.float32)
            if any_lm and lm is None:
                lm = jnp.ones((x.shape[0],) if y.ndim == 2
                              else (x.shape[0], y.shape[1]),
                              jnp.float32)
            xs.append(x)
            ys.append(y)
            if any_fm:
                fms.append(jnp.asarray(fm))
            if any_lm:
                lms.append(jnp.asarray(lm))

        def stack(parts):
            # device-side stack when the pipeline pre-staged the
            # batches (no host np.stack copy of the k-window)
            out = jnp.stack(parts)
            return jax.device_put(
                out, NamedSharding(
                    self.mesh, P(*([None, "dp"][:min(2, out.ndim)]))))

        xs = stack(xs)
        ys = stack(ys)
        fms = stack(fms) if any_fm else None
        lms = stack(lms) if any_lm else None
        ok = self._run_guarded(
            lambda: program.run_group(xs, ys, fms, lms))
        if ok:
            for listener in net.listeners:
                listener.iteration_done(net, net.iteration)
        return ok

    def _fit_multi_io(self, batch):
        """Multi-input/multi-output graph batch: shard every input,
        label, and mask over dp (batch must be dp-divisible — ragged
        padding is only automated on the single-io path)."""
        from deeplearning4j_tpu.nn.graph import _as_multi

        net = self.net
        ins, labs, fms, lms = _as_multi(batch)
        b = np.asarray(ins[0]).shape[0]
        if b % self.dp:
            raise ValueError(
                f"multi-input batch size {b} must be divisible by "
                f"dp={self.dp} (pad the batch or mask rows yourself)")
        names = net.conf.network_inputs
        sb = lambda a: shard_batch(self.mesh, jnp.asarray(a, net.dtype))
        inputs = {n: sb(x) for n, x in zip(names, ins)}
        labels = [sb(y) for y in labs]
        fmasks = None
        if fms is not None:
            fmasks = {n: (None if m is None else sb(m))
                      for n, m in zip(names, fms)}
        lmasks = None
        if lms is not None:
            lmasks = [None if m is None else sb(m) for m in lms]
        net._train_step(inputs, labels, fmasks, lmasks)

    def output(self, x):
        self._ensure_sharded()
        with self.mesh:
            return self.net.output(shard_batch(self.mesh, jnp.asarray(x)))


def _as_batch(batch):
    from deeplearning4j_tpu.nn.multilayer import _as_batch as f
    return f(batch)


def _window_compatible(a, b) -> bool:
    """Two batches may share a run_group k-window when their feature/
    label shapes match (the scan stacks them) and any masks BOTH carry
    agree in shape (a missing mask is synthesized as ones)."""
    for i in (0, 1):
        if tuple(np.shape(a[i])) != tuple(np.shape(b[i])):
            return False
    for i in (2, 3):
        if a[i] is not None and b[i] is not None \
                and tuple(np.shape(a[i])) != tuple(np.shape(b[i])):
            return False
    return True


def _pad_batch_with_masks(dp, x, y, fm, lm):
    """Pad one batch's leading dim to a dp multiple (static shapes for
    XLA), masking padded rows out of the loss. Returns (x, y, fm, lm).
    Shared by ParallelWrapper and StaleGradientTrainer."""
    x = np.asarray(x)
    npad = (-x.shape[0]) % dp
    if npad:
        x = np.concatenate(
            [x, np.zeros((npad,) + x.shape[1:], x.dtype)], 0)
        y2 = np.asarray(y)
        y = np.concatenate(
            [y2, np.zeros((npad,) + y2.shape[1:], y2.dtype)], 0)
        if lm is None:
            lm = np.ones(
                (x.shape[0],) if y2.ndim == 2
                else (x.shape[0], y2.shape[1]), np.float32)
            lm[-npad:] = 0.0
        else:
            lm2 = np.asarray(lm)
            lm = np.concatenate(
                [lm2, np.zeros((npad,) + lm2.shape[1:], lm2.dtype)], 0)
        if fm is not None:
            fm2 = np.asarray(fm)
            fm = np.concatenate(
                [fm2, np.zeros((npad,) + fm2.shape[1:], fm2.dtype)], 0)
    return x, y, fm, lm


# the step math lives with the engine now (ONE source for the single
# step, the k-step group, and both shard_map trainers below); the old
# private name stays importable for downstream callers
_make_loss_and_apply = make_loss_and_apply


class LocalStepTrainer:
    """True `averagingFrequency=k` local-SGD semantics via shard_map:
    each dp shard carries its own params for k local steps (gradients of
    its LOCAL minibatch only — no cross-shard gradient exchange), then
    params (and optionally updater state + BN running stats) are pmean'd
    over dp — the reference's AVERAGING mode
    (ParallelWrapper.java:320, averageUpdatersState :332-365), compiled
    as one XLA program per group size.

    This trades gradient freshness for k× fewer collectives; on ICI the
    per-step all-reduce is nearly free, so this exists for semantic
    parity and for DCN-spanning meshes where collectives are expensive.

    Constraints: tp must be 1 (params are replicated inside the shard_map)
    and the wrapped net must not be in TBPTT carry mode.
    """

    def __init__(self, net, mesh: Mesh, average_updaters: bool = True,
                 threshold: float = 0.0, per_step_losses: bool = False,
                 program=None):
        """`threshold > 0` enables threshold compression of the k-step
        parameter delta at each rendezvous (the reference's
        EncodingHandler.java:57-73 role, composed with local SGD): each
        shard sends sign(delta+residual)*threshold only where
        |delta+residual| >= threshold and keeps the remainder in a
        per-shard residual accumulator, so successive rendezvous
        eventually deliver everything. `wire_stats()` reports the
        resulting bytes-on-wire vs a dense exchange. The residual is
        in-memory state: a killed-and-resumed job loses its pending
        (sub-threshold) delta mass, exactly like the reference's
        in-memory residual accumulator — checkpoints capture the
        delivered params only."""
        if mesh.shape["tp"] != 1:
            raise NotImplementedError(
                "averaging_frequency > 1 requires tp == 1 (local-SGD "
                "shards carry full param replicas)")
        if getattr(net.conf, "backprop_type", None) == "truncated_bptt":
            raise NotImplementedError(
                "averaging_frequency > 1 does not support truncated "
                "BPTT (the local-step scan carries no RNN state); use "
                "averaging_frequency=1")
        self.net = net
        self.mesh = mesh
        self.average_updaters = average_updaters
        self.threshold = float(threshold)
        # per_step_losses=True compiles the group program to ALSO
        # return the k dp-averaged inner-step losses (read back via
        # `last_step_losses`) so a guard can localize a poisoned inner
        # step; off by default — the compiled program is unchanged
        self.per_step_losses = bool(per_step_losses)
        self.last_step_losses = None
        # compilation is ENGINE-owned (PR 9 follow-on): the shard_map
        # programs live in the net's JitCache through
        # StepProgram.trainer_program — recompile forensics, precision
        # policy registration, and the mesh arc see one owner
        from deeplearning4j_tpu.engine import StepProgram

        self._program = program or StepProgram(net)
        self._residual = None
        self._sent_nnz = []          # per-rendezvous device scalars
        self._param_entries = None
        self._n_rendezvous = 0

    # -------------------------------------------------------------- build
    def _build(self, k: int, with_fm: bool, with_lm: bool,
               trace_key: str = "local_sgd"):
        from deeplearning4j_tpu.nn.updater import schedule_lr

        net = self.net
        conf = net.conf
        avg_upd = self.average_updaters
        loss_for_grad, apply_updates = _make_loss_and_apply(net)

        thr = self.threshold

        def worker(params, upd_states, states, residual, step0, xs, ys,
                   fms, lms, rng, lr_scale):
            net._jit_cache.record_trace(trace_key)
            # decorrelate dropout across shards
            rng = jax.random.fold_in(rng, jax.lax.axis_index("dp"))
            keys = jax.random.split(rng, k)

            def one(carry, sl):
                params, upd_states, states, step = carry
                x, y, fm, lm, key = sl
                (loss, new_states), grads = jax.value_and_grad(
                    loss_for_grad, has_aux=True)(
                        params, states, x, y, key, fm, lm)
                grads = net._clip_grads(grads)
                lr = schedule_lr(conf, step) * lr_scale
                params, upd_states = apply_updates(
                    params, upd_states, grads, lr, step)
                return (params, upd_states, new_states, step + 1), loss

            params0 = params
            (params, upd_states, states, _), losses = jax.lax.scan(
                one, (params, upd_states, states, step0),
                (xs, ys, fms, lms, keys))
            # rendezvous: average over dp
            pmean = lambda t: jax.tree_util.tree_map(
                lambda a: jax.lax.pmean(a, "dp"), t)
            if thr > 0.0:
                # threshold-encode the k-step delta with residual carry
                # (EncodingHandler.java:57-73 role): only +-thr spikes
                # cross the wire; the remainder waits in `residual`
                def encode(p0, p1, res):
                    acc = (p1 - p0) + res[0]
                    send = jnp.where(jnp.abs(acc) >= thr,
                                     jnp.sign(acc) * thr, 0.0)
                    return send, (acc - send)[None]
                flat0, treedef = jax.tree_util.tree_flatten(params0)
                flat1 = jax.tree_util.tree_leaves(params)
                flatr = jax.tree_util.tree_leaves(residual)
                sends, new_res = [], []
                nnz = jnp.zeros((), jnp.float32)
                for p0, p1, res in zip(flat0, flat1, flatr):
                    send, r = encode(p0, p1, res)
                    sends.append(send)
                    new_res.append(r)
                    nnz = nnz + jnp.count_nonzero(
                        send).astype(jnp.float32)
                avg = [jax.lax.pmean(sv, "dp") for sv in sends]
                params = jax.tree_util.tree_unflatten(
                    treedef, [p0 + a for p0, a in zip(flat0, avg)])
                residual = jax.tree_util.tree_unflatten(
                    treedef, new_res)
                nnz = jax.lax.psum(nnz, "dp")
            else:
                params = pmean(params)
                nnz = jnp.zeros((), jnp.float32)
            states = pmean(states)
            if avg_upd:
                upd_states = pmean(upd_states)
            out = (params, upd_states, states,
                   jax.lax.pmean(jnp.mean(losses), "dp"),
                   residual, nnz)
            if step_losses:
                # [k] dp-averaged inner-step losses: a NaN shard
                # propagates through the pmean, so the host can point
                # at the exact poisoned inner step
                out += (jax.lax.pmean(losses, "dp"),)
            return out

        step_losses = self.per_step_losses
        rep = P()             # replicated at entry/exit
        xspec = P(None, "dp")  # [k, batch, ...]: batch dim sharded
        fspec = xspec if with_fm else rep
        lspec = xspec if with_lm else rep
        rspec = P("dp")       # per-shard residual, [dp, ...] outside
        outs = (rep, rep, rep, rep, rspec, rep)
        if step_losses:
            outs += (rep,)
        return jax.jit(jax.shard_map(
            worker, mesh=self.mesh,
            in_specs=(rep, rep, rep, rspec, rep, xspec, xspec, fspec,
                      lspec, rep, rep),
            out_specs=outs,
            check_vma=False),
            donate_argnums=(0, 1, 2, 3))

    def _init_residual(self):
        """Per-shard residual accumulators, zero-initialized with a
        [dp, ...] layout sharded over dp (each shard owns its own)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        if self.threshold <= 0.0:
            return {}      # no compression: no residual state to carry
        dp = self.mesh.shape["dp"]
        params = self.net.params
        if self._param_entries is None:
            self._param_entries = sum(
                int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(params))
        sh = NamedSharding(self.mesh, P("dp"))

        def zeros():
            return jax.tree_util.tree_map(
                lambda a: jnp.zeros((dp,) + a.shape, a.dtype), params)

        return jax.jit(zeros, out_shardings=sh)()

    def wire_stats(self):
        """Bytes-on-wire accounting for the rendezvous exchanges (the
        WiredEncodingHandler.java:40-57 role): dense = full param
        all-reduce per rendezvous; compressed = 4 bytes per threshold
        spike (the reference's integer wire format encodes sign in the
        index). The updater-state and BN-state averages stay DENSE in
        both modes and are counted in both totals, so the ratio
        reflects the whole rendezvous, not just the params."""
        n = self._n_rendezvous
        if self._param_entries is None or self.threshold <= 0.0 or not n:
            return {"threshold": self.threshold, "rendezvous": n,
                    "bytes_dense": None, "bytes_compressed": None,
                    "compression_ratio": None}
        aux_entries = sum(
            int(np.prod(a.shape))
            for a in jax.tree_util.tree_leaves(self.net.states))
        if self.average_updaters:
            aux_entries += sum(
                int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(
                    self.net.updater_states))
        dp = self.mesh.shape["dp"]
        sent = float(sum(float(v) for v in self._sent_nnz))
        dense_params = float(self._param_entries) * 4.0 * n * dp
        aux = float(aux_entries) * 4.0 * n * dp
        comp = sent * 4.0 + aux
        dense = dense_params + aux
        return {"threshold": self.threshold, "rendezvous": n,
                "bytes_dense": dense, "bytes_compressed": comp,
                "compression_ratio": comp / dense if dense else None}

    # ---------------------------------------------------------------- run
    def run(self, group):
        """Run one k-step local-SGD group. `group` is a list of
        (x, y, fm, lm) host batches (batch dims already dp-padded)."""
        net = self.net
        k = len(group)
        # equalize batch sizes across the group (fully-masked pad rows)
        bmax = max(np.asarray(g[0]).shape[0] for g in group)
        any_fm = any(g[2] is not None for g in group)
        any_lm = any(g[3] is not None for g in group)
        xs, ys, fms, lms = [], [], [], []
        for x, y, fm, lm in group:
            x, y = np.asarray(x), np.asarray(y)
            if any_lm and lm is None:
                lm = np.ones((x.shape[0],) if y.ndim == 2
                             else (x.shape[0], y.shape[1]), np.float32)
            if any_fm and fm is None:
                fm = np.ones((x.shape[0],) + (() if x.ndim == 2
                                              else (x.shape[1],)),
                             np.float32)
            n = bmax - x.shape[0]
            if n:
                pad = lambda a: np.concatenate(
                    [a, np.zeros((n,) + a.shape[1:], a.dtype)], 0)
                x, y = pad(x), pad(y)
                if lm is None:
                    lm = np.ones((x.shape[0],) if y.ndim == 2
                                 else (x.shape[0], y.shape[1]), np.float32)
                    lm[-n:] = 0.0
                else:
                    lm = pad(lm)
                if fm is not None:
                    fm = pad(fm)
            xs.append(x); ys.append(y); fms.append(fm); lms.append(lm)
        # equalization padding may have created masks for only some
        # batches; fill the rest with ones so stacking is uniform
        if any(m is not None for m in lms):
            lms = [np.ones((x.shape[0],) if y.ndim == 2
                           else (x.shape[0], y.shape[1]), np.float32)
                   if lm is None else lm
                   for x, y, lm in zip(xs, ys, lms)]
        any_lm = any(m is not None for m in lms)
        xs = jnp.asarray(np.stack(xs), net.dtype)
        ys = jnp.asarray(np.stack(ys), net.dtype)
        fms = jnp.asarray(np.stack(fms)) if any_fm else None
        lms = jnp.asarray(np.stack(lms)) if any_lm else None

        is_graph = hasattr(net.conf, "network_inputs")
        if is_graph:
            name = net.conf.network_inputs[0]
            xs_in = {name: xs}
            ys_in = [ys]
            fms_in = None if fms is None else {name: fms}
            lms_in = None if lms is None else [lms]
        else:
            xs_in, ys_in, fms_in, lms_in = xs, ys, fms, lms
        return self.run_arrays(xs_in, ys_in, fms_in, lms_in, k=k)

    def run_arrays(self, xs_in, ys_in, fms_in=None, lms_in=None, k=None):
        """Run one k-step local-SGD group on pre-staged arrays with a
        leading [k, ...] step dim. Device-resident arrays can be passed
        repeatedly without re-staging, which amortizes host->device
        transfer and per-dispatch latency over k steps."""
        net = self.net
        is_graph = hasattr(net.conf, "network_inputs")
        if k is None:
            lead = (next(iter(xs_in.values())) if is_graph else xs_in)
            k = int(lead.shape[0])

        # engine-owned compilation: the JitCache key carries the
        # frozen signature (freeze/unfreeze between fits takes effect)
        # and the program registers its precision policy + forensics
        # trace like every other engine program
        with_fm = fms_in is not None
        with_lm = lms_in is not None
        fn = self._program.trainer_program(
            "engine_local_sgd",
            lambda tk: self._build(k, with_fm, with_lm, tk),
            k, with_fm, with_lm, self.per_step_losses,
            self.threshold > 0.0)
        net._rng, sub = jax.random.split(net._rng)
        if self._residual is None:
            self._residual = self._init_residual()
        out = fn(
                net.params, net.updater_states, net.states,
                self._residual,
                jnp.asarray(net.iteration, jnp.int32),
                xs_in, ys_in, fms_in, lms_in, sub,
                jnp.asarray(net._lr_score_factor, jnp.float32))
        if self.per_step_losses:
            (net.params, net.updater_states, net.states, loss,
             self._residual, nnz, self.last_step_losses) = out
        else:
            (net.params, net.updater_states, net.states, loss,
             self._residual, nnz) = out
        if self.threshold > 0.0:
            # keep per-rendezvous device scalars; summed (in f64-safe
            # host arithmetic) only when wire_stats() is read, so the
            # hot loop never syncs
            self._sent_nnz.append(nnz)
            self._n_rendezvous += 1
        net.iteration += k
        net._score = loss
        net._apply_score_decay(loss)
        for listener in net.listeners:
            listener.iteration_done(net, net.iteration)
        return loss


class StaleGradientTrainer:
    """DP-4's async training DYNAMICS, TPU-natively (parity role:
    SharedTrainingMaster.java:72 / SharedTrainingWrapper.java:196-240 —
    workers there train on gradients that arrive late through the Aeron
    parameter server).

    SPMD redesign: bounded 1-step staleness instead of unbounded async.
    Step t computes this batch's globally-averaged gradient g_t but
    APPLIES g_{t-1}: the cross-slice all-reduce of g_t therefore sits
    on the program's critical path BEHIND the next step's compute, so
    XLA's async collectives can overlap it with forward/backward work —
    the latency-hiding role of the reference's parameter server with a
    hard staleness bound (and none of its lost-update races, SURVEY
    §5.2). fit() flushes the final pending gradient so no update is
    dropped; updater state (momentum etc.) advances with the DELAYED
    gradient stream, matching how the reference's workers consume late
    updates.

    Constraints: tp == 1 (params replicated inside the shard_map), no
    truncated BPTT.
    """

    def __init__(self, net, mesh: Mesh, program=None):
        if mesh.shape["tp"] != 1:
            raise NotImplementedError(
                "StaleGradientTrainer requires tp == 1")
        if getattr(net.conf, "backprop_type", None) == "truncated_bptt":
            raise NotImplementedError(
                "StaleGradientTrainer does not support truncated BPTT")
        from deeplearning4j_tpu.engine import StepProgram

        self.net = net
        self.mesh = mesh
        # compilation is engine-owned (StepProgram.trainer_program):
        # the delayed-gradient programs live in the net's JitCache
        # with forensics + precision-policy registration
        self._program = program or StepProgram(net)
        self._pending = None     # g_{t-1}: replicated averaged gradient

    def _build(self, with_fm: bool, with_lm: bool, flush: bool,
               trace_key: str = "stale_grad"):
        from deeplearning4j_tpu.nn.updater import schedule_lr

        net = self.net
        conf = net.conf
        # rebuilt per cache entry: the frozen set is baked into these
        # closures (cache is keyed on frozen_sig for that reason)
        loss_for_grad, apply_updates = _make_loss_and_apply(net)

        def worker(params, upd_states, states, prev_g, step, x, y, fm,
                   lm, rng, lr_scale):
            net._jit_cache.record_trace(trace_key)
            lr = schedule_lr(conf, step) * lr_scale
            if flush:
                # terminal half-step: apply the last pending gradient
                params, upd_states = apply_updates(
                    params, upd_states, prev_g, lr, step)
                return (params, upd_states, states, prev_g,
                        jnp.zeros(()))
            rng = jax.random.fold_in(rng, jax.lax.axis_index("dp"))
            (loss, new_states), grads = jax.value_and_grad(
                loss_for_grad, has_aux=True)(
                    params, states, x, y, rng, fm, lm)
            grads = net._clip_grads(grads)
            pmean = lambda t: jax.tree_util.tree_map(
                lambda a: jax.lax.pmean(a, "dp"), t)
            g_avg = pmean(grads)
            # per-shard BN running stats must agree before the
            # replicated out_spec (same contract as LocalStepTrainer)
            new_states = pmean(new_states)
            # apply the PREVIOUS step's gradient (1-step staleness)
            params, upd_states = apply_updates(
                params, upd_states, prev_g, lr, step)
            return (params, upd_states, new_states, g_avg,
                    jax.lax.pmean(loss, "dp"))

        rep = P()
        xspec = P("dp")
        fspec = xspec if with_fm else rep
        lspec = xspec if with_lm else rep
        return jax.jit(jax.shard_map(
            worker, mesh=self.mesh,
            in_specs=(rep, rep, rep, rep, rep, xspec, xspec, fspec,
                      lspec, rep, rep),
            out_specs=(rep, rep, rep, rep, rep),
            check_vma=False),
            donate_argnums=(0, 1, 2, 3))

    def _zero_grads(self):
        return jax.tree_util.tree_map(jnp.zeros_like, self.net.params)

    def step(self, x, y, fm=None, lm=None):
        net = self.net
        if self._pending is None:
            self._pending = self._zero_grads()
        with_fm, with_lm = fm is not None, lm is not None
        fn = self._program.trainer_program(
            "engine_stale",
            lambda tk: self._build(with_fm, with_lm, False, tk),
            with_fm, with_lm)
        net._rng, sub = jax.random.split(net._rng)
        (net.params, net.updater_states, net.states, self._pending,
         loss) = fn(
            net.params, net.updater_states, net.states, self._pending,
            jnp.asarray(net.iteration, jnp.int32), x, y, fm, lm, sub,
            jnp.asarray(net._lr_score_factor, jnp.float32))
        net.iteration += 1
        net._score = loss
        net._apply_score_decay(loss)
        for listener in net.listeners:
            listener.iteration_done(net, net.iteration)
        return loss

    def flush(self):
        """Apply the final pending gradient (call at end of fit)."""
        net = self.net
        if self._pending is None:
            return
        fn = self._program.trainer_program(
            "engine_stale_flush",
            lambda tk: self._build(False, False, True, tk))
        dummy = jnp.zeros((self.mesh.shape["dp"], 1), net.dtype)
        (net.params, net.updater_states, net.states, self._pending,
         _) = fn(
            net.params, net.updater_states, net.states, self._pending,
            jnp.asarray(net.iteration, jnp.int32), dummy, dummy, None,
            None, jax.random.PRNGKey(0),
            jnp.asarray(net._lr_score_factor, jnp.float32))
        self._pending = None

    def fit(self, batches):
        """Train over an iterable of batches in any _as_batch shape
        ((x, y), (x, y, fm, lm), DataSet, ...), flushing the last
        pending gradient at the end. Leading dims are padded to a dp
        multiple with loss-masked rows."""
        net = self.net
        dp = self.mesh.shape["dp"]
        with self.mesh:
            for batch in batches:
                x, y, fm, lm = _as_batch(batch)
                x, y, fm, lm = _pad_batch_with_masks(
                    dp, np.asarray(x), np.asarray(y), fm, lm)
                x = jnp.asarray(x, net.dtype)
                y = jnp.asarray(y, net.dtype)
                fm = None if fm is None else jnp.asarray(fm)
                lm = None if lm is None else jnp.asarray(lm)
                is_graph = hasattr(net.conf, "network_inputs")
                if is_graph:
                    name = net.conf.network_inputs[0]
                    self.step({name: x}, [y],
                              None if fm is None else {name: fm},
                              None if lm is None else [lm])
                else:
                    self.step(x, y, fm, lm)
            self.flush()
        return self
