"""TrainingMaster: multi-host (DCN) data-parallel training orchestration.

Parity: the Spark training stack's role —
spark/api/TrainingMaster.java (SPI: executeTraining, worker config,
result aggregation), ParameterAveragingTrainingMaster.java:326 (BSP
splits + aggregate), ExecuteWorkerFlatMap.java (per-worker data
partition), SharedTrainingMaster.java:72 (the async gradient mesh).

TPU-native design: instead of Spark shipping serialized models to
executors and tree-aggregating parameters, every host runs THIS same
program under `jax.distributed`; the per-host input partition (the
RDD-partition role) is assembled into one global device array
(`jax.make_array_from_process_local_data`), and the gradient exchange
is the XLA all-reduce GSPMD inserts into the SAME compiled train step
used on one chip — collectives ride ICI within a slice and DCN across
slices, replacing both the Aeron parameter server and Spark
treeAggregate (SURVEY §2.4, §5.8).

Fault tolerance (SURVEY §5.3): step-granular checkpoints
{params, updater state, BN states, iteration, rng} written by process 0
(shared filesystem assumption, like Spark's checkpoint dir); a killed
job relaunches with the same arguments and resumes from the latest
checkpoint — the reference's "stateless per split, re-fit from last
broadcast" recovery, made explicit.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import re
import time
from functools import partial
from typing import Callable, Optional, Tuple

import numpy as np

from deeplearning4j_tpu.observability import metrics as _obs
from deeplearning4j_tpu.resilience import checkpoint_integrity as _ci
from deeplearning4j_tpu.resilience.errors import (
    FaultInjectedError,
    NonFiniteLossError,
    PreemptedError,
    StepHangError,
)
from deeplearning4j_tpu.resilience.faults import fire as _fire
from deeplearning4j_tpu.resilience.retry import Retry
from deeplearning4j_tpu.resilience.supervisor import (
    NonFiniteGuard,
    PreemptionHandler,
    StepWatchdog,
    Supervisor,
    fire_hang_hard,
)

logger = logging.getLogger("deeplearning4j_tpu")


class TrainingMaster:
    """Orchestrates SPMD data-parallel training of one net across all
    processes in a `jax.distributed` job (or a single process).

    Every process must construct the SAME net (same config + seed) and
    call the same TrainingMaster methods in the same order — standard
    SPMD discipline (the reference instead broadcasts the model; with
    identical seeds the construction IS the broadcast)."""

    def __init__(self, net, checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 0, mesh=None,
                 averaging_frequency: int = 1,
                 threshold_compression: float = 0.0,
                 checkpoint_format: str = "npz",
                 keep_last: int = 0,
                 checkpoint_retry: Optional[Retry] = None,
                 guard: Optional[NonFiniteGuard] = None,
                 watchdog: Optional[StepWatchdog] = None,
                 preemption=False,
                 data_retry: Optional[Retry] = None,
                 skip_bad_batches: bool = False,
                 supervisor: Optional[Supervisor] = None,
                 guard_inner_steps: bool = False,
                 tracer=None,
                 phase_profiler=None,
                 steps_per_dispatch: int = 1,
                 per_rank_checkpoints: bool = False,
                 pipeline: Optional[bool] = None,
                 pipeline_depth: int = 2,
                 sharding: Optional[str] = None):
        """`averaging_frequency=k > 1` runs k-step local SGD between
        parameter rendezvous — each dp shard trains privately for k
        steps, then params (+ updater state) are averaged. This is the
        DCN-traffic-reduction role of the reference's threshold-encoded
        gradient compression (EncodingHandler.java:64): instead of
        compressing a per-step exchange, the exchange happens k times
        less often; `threshold_compression=t > 0` additionally
        threshold-encodes the k-step parameter delta with per-shard
        residual accumulation before the cross-shard average
        (EncodingHandler.java:57-73) — frequency reduction and byte
        reduction compose. Wire accounting lands in
        training_stats()["wire"]."""
        import jax
        from deeplearning4j_tpu.parallel.mesh import make_mesh

        if checkpoint_format not in ("npz", "orbax"):
            raise ValueError(
                f"checkpoint_format must be npz|orbax: {checkpoint_format}")
        if sharding not in (None, "replicated", "zero1"):
            raise ValueError(
                f"sharding must be None|'replicated'|'zero1': {sharding}")
        # ZeRO-1 (engine/sharding.py, arXiv 2004.13336): optimizer
        # state sharded over the mesh's dp axis, the weight update
        # reduce-scattered / shard-local / all-gathered INSIDE the one
        # compiled step. Equal to the replicated program within a few
        # ulp; 1/n per-replica optimizer memory.
        self.zero1 = sharding == "zero1"
        self.net = net
        # per-rank checkpoint copies (`<dir>/rank-<r>/`): EVERY process
        # writes its own copy instead of process 0 alone — the input
        # the ClusterSupervisor's divergence quorum votes over (a
        # silently forked replica is out-voted, quarantined aside, and
        # healed before any resume). Replicated dp training makes the
        # copies the same state, so the canonical state digest
        # (recorded in each manifest at save) compares equal.
        self.per_rank_checkpoints = bool(per_rank_checkpoints)
        if self.per_rank_checkpoints and checkpoint_format != "npz":
            raise ValueError(
                "per_rank_checkpoints requires checkpoint_format='npz' "
                "(the divergence quorum votes over npz state digests)")
        self._ckpt_base = checkpoint_dir
        if self.per_rank_checkpoints and checkpoint_dir:
            from deeplearning4j_tpu.resilience.checkpoint_integrity import (
                rank_checkpoint_dir,
            )

            checkpoint_dir = rank_checkpoint_dir(
                checkpoint_dir, jax.process_index())
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = checkpoint_every
        self.checkpoint_format = checkpoint_format
        if mesh is None:
            mesh = make_mesh(dp=len(jax.devices()))
        self.mesh = mesh
        from deeplearning4j_tpu.parallel.wrapper import (
            _require_local_sgd,
        )

        self.averaging_frequency = max(1, averaging_frequency)
        self.threshold_compression = float(threshold_compression)
        _require_local_sgd(self.averaging_frequency,
                           self.threshold_compression)
        # keep_last > 0 prunes old step checkpoints after each save;
        # transient filesystem errors on the checkpoint path retry with
        # backoff (injected faults / corruption are NOT retryable)
        self.keep_last = int(keep_last)
        self._ckpt_retry = checkpoint_retry or Retry(
            max_attempts=3, initial_backoff_s=0.05,
            retryable=lambda e: isinstance(e, OSError))
        # --- self-healing hooks (resilience/supervisor.py): all opt-in,
        # all zero-cost when None/False
        if guard is not None and guard.policy == "rollback" \
                and not checkpoint_dir:
            raise ValueError(
                "NonFiniteGuard(policy='rollback') requires a "
                "checkpoint_dir to roll back to")
        self.guard = guard
        self.watchdog = watchdog
        if preemption is True:
            preemption = PreemptionHandler()
        self.preemption = preemption or None
        self.data_retry = data_retry
        self.skip_bad_batches = skip_bad_batches
        self.supervisor = supervisor
        # local-SGD granularity fix (flag-gated — the default compiled
        # program and cost profile are unchanged): the group program
        # additionally returns per-inner-step losses so the guard can
        # localize a poisoned INNER step instead of condemning the
        # whole k-step window
        self.guard_inner_steps = bool(guard_inner_steps)
        # `steps_per_dispatch=k > 1` runs the engine's lax.scan k-step
        # group on the single-program path: one dispatch advances k
        # steps (amortizing per-dispatch RTT, PERF.md), per-inner-step
        # losses preserved so the guard condemns ONE poisoned step.
        # Orthogonal to averaging_frequency (which groups steps at the
        # local-SGD rendezvous instead).
        self.steps_per_dispatch = max(1, int(steps_per_dispatch))
        if self.steps_per_dispatch > 1 and self.averaging_frequency > 1:
            raise ValueError(
                "steps_per_dispatch > 1 and averaging_frequency > 1 "
                "are mutually exclusive groupings (the local-SGD "
                "rendezvous already scans its k steps in one dispatch)")
        # harness-owned input pipeline (engine/pipeline.py): a producer
        # thread runs fetch -> retry/skip -> poison -> h2d staging
        # ahead of the compute so data_wait/h2d overlap device_compute.
        # Default (None): ON for single-process jobs, OFF multi-host
        # (cross-rank staging order stays on the consumer thread until
        # the sharded scale-out arc); pipeline=False opts out.
        self.pipeline = pipeline
        self.pipeline_depth = max(1, int(pipeline_depth))
        self._prefetch = None
        self._staged = False
        self._local_step = None
        # ONE supervisor (engine.StepHarness) owns the guard-verdict
        # dispatch, watchdog lifecycle, preemption checks, the
        # StepAccumulator every per-step metric batches through
        # (flushed every 32 steps and at fit end — container appends,
        # not registry locks), and the opt-in phase profiler; a Tracer
        # records per-step spans (fetch/dispatch/sync/checkpoint) on
        # one exportable timeline.
        from deeplearning4j_tpu.engine import StepHarness

        self._harness = StepHarness(
            net, guard=guard, watchdog=watchdog,
            preemption=self.preemption, supervisor=supervisor,
            tracer=tracer, phase_profiler=phase_profiler)
        self._obs_acc = self._harness.acc
        self._poisoned_steps = self._harness.poisoned_steps
        self._resil_counters = self._harness.counters
        self._mesh_mgr = None
        if self.zero1:
            if self.averaging_frequency > 1:
                raise ValueError(
                    "sharding='zero1' and averaging_frequency > 1 are "
                    "incompatible (local SGD keeps per-shard params; "
                    "ZeRO-1 shards the synchronous update)")
            if checkpoint_format != "npz":
                raise ValueError(
                    "sharding='zero1' requires checkpoint_format='npz'"
                    " (sharded optimizer-state slices ride npz "
                    "sidecars)")
            if (self.checkpoint_dir and jax.process_count() > 1
                    and not self.per_rank_checkpoints):
                raise ValueError(
                    "sharding='zero1' in a multi-process gang needs "
                    "per_rank_checkpoints=True (every rank must write "
                    "its own optimizer-state slice)")
            from deeplearning4j_tpu.engine.mesh import MeshManager

            self._mesh_mgr = MeshManager(mesh=self.mesh)
            self._harness.program.attach_mesh(self._mesh_mgr)

    # tracer / phase_profiler delegate to the harness so post-
    # construction assignment reaches the loop that actually reads them
    @property
    def tracer(self):
        return self._harness.tracer

    @tracer.setter
    def tracer(self, tracer):
        self._harness.tracer = tracer
        pp = self._harness.phase_profiler
        if pp is not None and pp.tracer is None:
            pp.tracer = tracer

    @property
    def phase_profiler(self):
        return self._harness.phase_profiler

    @phase_profiler.setter
    def phase_profiler(self, pp):
        if pp is not None:
            if pp.accumulator is None:
                pp.accumulator = self._harness.acc
            if pp.tracer is None:
                pp.tracer = self._harness.tracer
        self._harness.phase_profiler = pp

    # ------------------------------------------------------------ dist init
    @staticmethod
    def initialize_distributed(coordinator_address: str,
                               num_processes: int, process_id: int):
        """`jax.distributed.initialize` wrapper (must run before any
        device use). No-op for num_processes == 1."""
        if num_processes <= 1:
            return
        import jax

        # read by the CPU backend only (the multi-process tests and the
        # CPU gangs); a TPU gang's collectives ride ICI/DCN
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id)

    @staticmethod
    def process_info() -> Tuple[int, int]:
        import jax

        return jax.process_index(), jax.process_count()

    def world_info(self) -> dict:
        """The LIVE world this master trains in: process count (the
        dp-average denominator's host axis after a shrink-to-fit
        relaunch), device count, and the mesh's dp extent. Everything
        that shards data or averages across replicas derives from
        these live values — never from a configured world size — so an
        elastic gang that relaunches smaller re-derives its global
        batch semantics automatically."""
        import jax

        try:
            dp = int(self.mesh.shape.get("dp", 1))
        except Exception:   # noqa: BLE001 - exotic mesh: report devices
            dp = len(jax.devices())
        return {"processes": int(jax.process_count()),
                "devices": len(jax.devices()),
                "dp": dp,
                "sharding": "zero1" if self.zero1 else "replicated",
                "per_rank_checkpoints": self.per_rank_checkpoints}

    # ------------------------------------------------------------- staging
    def _replicated(self, tree):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = NamedSharding(self.mesh, P())
        return jax.tree_util.tree_map(
            lambda a: jax.make_array_from_process_local_data(
                sh, np.asarray(a)), tree)

    def _stage_net(self):
        if self._staged:
            return
        from deeplearning4j_tpu.parallel.wrapper import (
            _disable_flat_chain,
        )

        if self.net.params is None:
            self.net.init()
        _disable_flat_chain(self.net)
        self.net.params = self._replicated(self.net.params)
        if self._mesh_mgr is not None:
            # ZeRO-1: optimizer state lives SHARDED between steps —
            # divisible leaves split their leading dim over dp (1/n
            # per replica), the rest replicate
            import jax as _jax

            self.net.updater_states = self._mesh_mgr.shard_tree(
                _jax.tree_util.tree_map(self._host_leaf,
                                        self.net.updater_states))
        else:
            self.net.updater_states = self._replicated(
                self.net.updater_states)
        self.net.states = self._replicated(self.net.states)
        self._staged = True

    def _stage(self, a, spec):
        """Host partition -> global device array with `spec` sharding,
        cast to the net's dtype."""
        import jax
        import numpy as _np
        from jax.sharding import NamedSharding

        dtype = _np.dtype(getattr(self.net, "dtype", None) or _np.float32)
        return jax.make_array_from_process_local_data(
            NamedSharding(self.mesh, spec), np.asarray(a, dtype))

    def _global_batch(self, x_local, y_local):
        """Per-host partition -> global [G, ...] device arrays sharded
        over dp (the ExecuteWorkerFlatMap data-partition role)."""
        from jax.sharding import PartitionSpec as P

        return (self._stage(x_local, P("dp")),
                self._stage(y_local, P("dp")))

    # ----------------------------------------------------------------- fit
    def fit(self, batch_fn: Callable[[int], Tuple], num_steps: int,
            start_step: Optional[int] = None,
            collect_training_stats: bool = False):
        """Train for `num_steps` global steps.

        `batch_fn(step) -> (x_local, y_local)`: THIS process's partition
        of the global batch at `step` (deterministic in step, so resume
        replays the data stream from the checkpointed position — the
        step index is the iterator position).

        If `start_step` is None and a checkpoint exists, training
        resumes after the last checkpointed step.

        `collect_training_stats=True` records per-step phase timings
        (data staging / train step / checkpoint) retrievable via
        `training_stats()` — the Spark CommonSparkTrainingStats role
        (ref TrainingMaster.setCollectTrainingStats,
        spark/stats/StatsUtils.java timeline export).

        Self-healing (resilience/supervisor.py, all opt-in via the
        constructor): a NonFiniteGuard checks loss+params after
        (sampled) steps and skips/rolls-back/aborts on NaN or loss
        spikes; a StepWatchdog heartbeats around dispatch/fetch and
        escalates a hung step; a PreemptionHandler turns SIGTERM/SIGINT
        (or the `train.preempt` fault) into checkpoint-then-
        PreemptedError at the next step boundary; `data_retry` +
        `skip_bad_batches` make a flaky batch_fn (the `data.next`
        fault point) survivable. Run the whole fit under
        `Supervisor.run` to also survive crashes/hangs/preemptions via
        checkpoint resume.

        Telemetry (observability/): every loop iteration lands in the
        global MetricsRegistry (`dl4j_train_steps_total` counts
        ATTEMPTED steps, including skipped ones;
        `dl4j_train_step_seconds` their wall time); with a `tracer`
        attached each step records a parent span with
        fetch/dispatch/sync/checkpoint children, and the StepWatchdog's
        monitor thread parents its hang events to the current step
        span."""
        self._stage_net()
        # the live world: data sharding and the dp-average denominator
        # derive from THIS (mesh over the processes actually present),
        # so a shrink-to-fit relaunch predictably re-averages the loss
        # over the surviving replicas; the gauge makes it scrapeable
        _obs.set_gauge("dl4j_cluster_world_size",
                       self.world_info()["processes"])
        guard = self.guard
        if start_step is None:
            start_step = self.load_latest_checkpoint()
        if collect_training_stats:
            self._stats = []
        self._harness.program.require_sgd("TrainingMaster")
        if (guard is not None and guard.policy == "rollback"
                and self.checkpoint_dir and not self.list_checkpoints()):
            # a rollback target must exist before the first poisoned
            # step — seed one at the fit's starting state
            self.save_checkpoint(start_step)
        with self._harness.session():
            self._prefetch = None
            if self._pipeline_enabled():
                self._prefetch = self._harness.build_step_pipeline(
                    lambda s: self._produce(batch_fn, s),
                    start=start_step, stop=num_steps,
                    depth=self.pipeline_depth,
                    skip=self._poisoned_steps.__contains__,
                    meta={"sharding": "dp",
                          "world": self.world_info()})
            if self.averaging_frequency > 1:
                return self._fit_local_sgd(batch_fn, num_steps,
                                           start_step,
                                           collect_training_stats)
            if self.steps_per_dispatch > 1:
                return self._fit_grouped(batch_fn, num_steps,
                                         start_step,
                                         collect_training_stats)
            with self.mesh:
                step = start_step
                while step < num_steps:
                    if step in self._poisoned_steps:
                        step += 1   # rollback replay: skip the poisoned
                        continue    # data window, train nothing on it
                    self._check_preemption(step)
                    with self._harness.step_scope(step):
                        step = self._fit_one_step(
                            batch_fn, step, collect_training_stats)
        return self

    def _fit_one_step(self, batch_fn, step,
                      collect_training_stats) -> int:
        """One attempted global step (fit() wraps it in the harness's
        step_scope for span + metric accounting): returns the step
        index to continue from — step+1 normally and on skips, the
        restored step after a rollback."""
        net = self.net
        guard = self.guard
        harness = self._harness
        tr = self.tracer
        sp = harness.step_span
        _fire("train.step")
        _fire("train.hang")
        fire_hang_hard()
        harness.beat("dispatch", step=step)
        harness.mark("data_wait")
        t0 = time.perf_counter()
        staged = self._fetch_step(batch_fn, step)
        if staged is None:      # bad batch skipped by policy
            return step + 1
        x, y = staged
        t1 = time.perf_counter()
        if tr is not None:
            tr.record("fetch_and_stage", t0, t1, cat="train", parent=sp)
        done = step + 1
        ckpt_due = bool(
            self.checkpoint_dir and self.checkpoint_every
            and done % self.checkpoint_every == 0)
        # a checkpoint must never publish non-finite state: force a
        # check on checkpoint steps even when the sampling cadence
        # would skip them
        check_now = harness.should_check(step=step) \
            or (ckpt_due and harness.should_check(force=True))
        snap = harness.pre_step_snapshot(check_now)
        harness.mark("dispatch")
        harness.program.run(x, y)
        t_disp = time.perf_counter()
        if tr is not None:
            tr.record("dispatch", t1, t_disp, cat="train", parent=sp)
        harness.beat("fetch", step=step)
        # sampled device sync: the blocked interval on the step's
        # loss value is the device_compute phase; everything after
        # is host-side sync work (guard checks, score fetches)
        harness.sync(getattr(net, "_score", None), step=step)
        harness.mark("host_sync")
        if check_now:
            verdict = guard.post_step(net)
            if verdict != "ok":
                restored = {}

                def _rollback_to_checkpoint():
                    self._poisoned_steps.add(step)
                    restored["step"] = self.load_latest_checkpoint()
                    logger.warning(
                        "guard: rolled back to checkpoint step %d; "
                        "step %d will be skipped on replay",
                        restored["step"], step)

                action = harness.dispatch_verdict(
                    verdict, snap=snap,
                    restore_rollback=_rollback_to_checkpoint,
                    context=f"at step {step}")
                if action == "skip":
                    return step + 1
                if action == "rollback":
                    return restored["step"]
        if collect_training_stats:
            # host fetch = true step barrier for honest timing
            # analyze: allow=jit-host-sync — opt-in stats mode only
            float(net.score())
        t2 = time.perf_counter()
        if tr is not None and (check_now or collect_training_stats):
            # the guard check / stats fetch forced a host sync — this
            # span is the device+fetch-result phase made visible
            tr.record("device_sync", t_disp, t2, cat="train",
                      parent=sp)
        harness.mark("telemetry")   # listener callbacks are user telemetry
        for listener in net.listeners:
            listener.iteration_done(net, net.iteration)
        t3 = time.perf_counter()
        if ckpt_due:
            harness.mark("checkpoint")
            self.save_checkpoint(done)
        if collect_training_stats:
            self._stats.append({
                "step": step,
                "data_ms": (t1 - t0) * 1e3,
                "fit_ms": (t2 - t1) * 1e3,
                "listener_ms": (t3 - t2) * 1e3,
                "checkpoint_ms":
                    (time.perf_counter() - t3) * 1e3,
            })
        return step + 1

    # --------------------------------------------------- input pipeline
    def _pipeline_enabled(self) -> bool:
        """Pipeline resolution: explicit flag wins; default ON
        everywhere. Multi-host staging is sharding-aware (the producer
        thread stages THIS rank's partition through
        `make_array_from_process_local_data` on the live mesh — a
        per-process placement, no cross-rank coordination to
        misorder), so the PR 12 multi-host auto-off is gone;
        pipeline=False opts out."""
        if self.pipeline is not None:
            return bool(self.pipeline)
        return True

    def _produce(self, batch_fn, step):
        """Producer-side work for ONE step (runs on the prefetch
        thread): the `data.next` fault point + `data_retry`/
        `skip_bad_batches` policy, chaos poisoning, and the h2d staging
        itself — a poisoned batch condemns the right step, and the copy
        of step k+1 overlaps compute on step k. Returns staged (x, y)
        global arrays sharded over the LIVE mesh's dp axis, or SKIPPED
        when the skip policy consumed the failure."""
        from deeplearning4j_tpu.engine.pipeline import SKIPPED

        b = self._next_batch(batch_fn, step, observe=False)
        if b is None:
            return SKIPPED
        return self._global_batch(self._maybe_poison(b[0]), b[1])

    def _fetch_step(self, batch_fn, step):
        """Staged (x, y) device arrays for `step`, or None when the
        step was skipped by policy — through the harness-owned
        prefetcher when the pipeline is on (fetch + h2d already
        overlapped earlier compute; the residual wait is what
        data_wait shrinks to), else fetched + staged synchronously."""
        harness = self._harness
        if self._prefetch is not None:
            t0 = time.perf_counter()
            out = self._prefetch.get(step)
            harness.mark("h2d")
            if out is None:
                return None
            self._obs_acc.observe("dl4j_train_data_wait_seconds",
                                  time.perf_counter() - t0)
            return out
        batch = self._next_batch(batch_fn, step)
        if batch is None:
            return None
        harness.mark("h2d")
        return self._global_batch(
            self._maybe_poison(batch[0]), batch[1])

    def _fetch_window(self, batch_fn, step, span):
        """(group, abs_steps) for a k-window's non-poisoned steps —
        pipeline on: staged (x, y) device pairs; off: host pairs. The
        per-inner-step ordering (and therefore the fault-point hit →
        step mapping) is identical in both modes."""
        group, abs_steps = [], []
        for s in range(step, step + span):
            if s in self._poisoned_steps:
                continue   # rollback replay: skip poisoned data
            if self._prefetch is not None:
                t0 = time.perf_counter()
                out = self._prefetch.get(s)
                if out is None:
                    continue
                self._obs_acc.observe("dl4j_train_data_wait_seconds",
                                      time.perf_counter() - t0)
                group.append(out)
                abs_steps.append(s)
            else:
                b = self._next_batch(batch_fn, s)
                if b is not None:
                    group.append((self._maybe_poison(b[0]), b[1]))
                    abs_steps.append(s)
        return group, abs_steps

    def _stack_window(self, group):
        """[k] batch pairs -> ([k, G, ...], [k, G, ...]) staged with
        P(None, 'dp'). Pipeline entries stack on DEVICE (stack_staged —
        no host np.stack copy of the k-window); host entries stack then
        stage. Same values, same sharding, same compiled program."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        if self._prefetch is not None:
            from deeplearning4j_tpu.engine.pipeline import stack_staged

            sh = NamedSharding(self.mesh, P(None, "dp"))
            return (stack_staged([g[0] for g in group], sh),
                    stack_staged([g[1] for g in group], sh))
        return (self._stage(np.stack([g[0] for g in group]),
                            P(None, "dp")),
                self._stage(np.stack([g[1] for g in group]),
                            P(None, "dp")))

    # ------------------------------------------------------- self-healing
    def _next_batch(self, batch_fn, step, observe: bool = True):
        """Fetch this step's batch through the `data.next` fault point,
        retried per `data_retry`; returns None (skip the step) when the
        fetch ultimately fails and `skip_bad_batches` is set.
        `observe=False` on the pipeline's producer thread: the
        StepAccumulator is single-owner, so the consumer observes its
        own (residual) wait instead."""
        def get():
            _fire("data.next")
            return batch_fn(step)

        t_fetch = time.perf_counter()
        try:
            if self.data_retry is not None:
                out = self.data_retry.call(get)
            else:
                out = get()
        except (StepHangError, PreemptedError):
            raise          # escalations, not data failures
        except Exception:
            if self.skip_bad_batches:
                self._resil_counters["data_skipped_steps"] += 1
                _obs.count("dl4j_train_data_skipped_steps_total")
                logger.warning("data.next failed at step %d — step "
                               "skipped (skip_bad_batches)", step)
                return None
            raise
        if observe:
            self._obs_acc.observe("dl4j_train_data_wait_seconds",
                                  time.perf_counter() - t_fetch)
        return out

    def _maybe_poison(self, x):
        """`train.grad_nonfinite` chaos hook: a triggered fire is
        consumed by poisoning the batch with NaN, so non-finite
        loss/grads flow through the REAL step math (what the guard must
        catch), not a synthetic exception."""
        try:
            _fire("train.grad_nonfinite")
        except FaultInjectedError:
            self._resil_counters["grad_poisoned_steps"] += 1
            x = np.full(np.shape(x), np.nan, np.float32)
        return x

    def _check_preemption(self, step):
        """Step-boundary preemption check (engine.StepHarness owns the
        logic): a pending SIGTERM/SIGINT or a triggered `train.preempt`
        fault checkpoints the CURRENT state and raises PreemptedError —
        a preempted job loses zero completed steps and a Supervisor (or
        a relaunch) resumes exactly here."""
        self._harness.check_preemption(
            step, save_checkpoint=(self.save_checkpoint
                                   if self.checkpoint_dir else None))

    def _fit_grouped(self, batch_fn, num_steps, start_step,
                     collect_training_stats=False):
        """`steps_per_dispatch=k`: the engine's `lax.scan` k-step group
        on the single-program path — ONE dispatch advances k steps
        (amortizing per-dispatch RTT, PERF.md), data stacked
        [k, G, ...]. The group program returns per-inner-step losses,
        fetched only on checked groups, so the guard condemns the ONE
        poisoned inner step and the window replays without it — same
        granularity contract as the local-SGD `guard_inner_steps`
        path, now the default for engine groups."""
        net = self.net
        guard = self.guard
        harness = self._harness
        program = harness.program
        program.require_sgd("TrainingMaster")
        k = self.steps_per_dispatch
        every = self.checkpoint_every
        pp = self.phase_profiler
        with self.mesh:
            step = start_step
            while step < num_steps:
                self._check_preemption(step)
                _fire("train.step")
                _fire("train.hang")
                fire_hang_hard()
                harness.beat("dispatch", step=step)
                if pp is not None:
                    pp.begin_step(step)
                    pp.mark("data_wait")
                t0 = time.perf_counter()
                span = min(step + k, num_steps) - step
                group, abs_steps = self._fetch_window(
                    batch_fn, step, span)
                if not group:
                    step += span
                    continue
                if pp is not None:
                    pp.mark("h2d")
                xs, ys = self._stack_window(group)
                t1 = time.perf_counter()
                # guard at group granularity: one check per dispatch
                # (already a 1/k sampling of the underlying steps)
                check_now = guard is not None and guard.check_every > 0
                snap = harness.pre_step_snapshot(check_now)
                if pp is not None:
                    pp.mark("dispatch")
                program.run_group(xs, ys)
                harness.beat("fetch", step=step)
                if pp is not None:
                    pp.mark("host_sync")
                if check_now:
                    # the scan group ALWAYS returns per-inner-step
                    # losses: the FIRST non-finite one is the poisoned
                    # step (the scan carries params, so every later
                    # inner loss is downstream contamination — those
                    # steps replay on clean state instead)
                    inner = np.asarray(program.last_step_losses)
                    finite = np.isfinite(inner)
                    bad = ([abs_steps[int(np.argmax(~finite))]]
                           if not finite.all() else [])
                    if bad:
                        guard.counters["checks"] += 1
                        guard.counters["nonfinite"] += 1
                        _obs.count("dl4j_train_guard_checks_total")
                        _obs.count("dl4j_train_guard_nonfinite_total")
                        self._poisoned_steps.update(bad)

                        def _rollback_group():
                            self._grouped_restore = \
                                self.load_latest_checkpoint()

                        action = harness.dispatch_verdict(
                            "nonfinite", snap=snap,
                            restore_rollback=_rollback_group,
                            context=f"at inner step(s) {bad} of group "
                                    f"at step {step}")
                        if action == "skip":
                            logger.warning(
                                "guard: non-finite inner step(s) %s — "
                                "window replayed without them", bad)
                        else:   # rollback
                            step = self._grouped_restore
                        continue   # re-enter the window minus `bad`
                    verdict = guard.post_step(net)
                    if verdict != "ok":
                        def _rollback_window():
                            for s in range(step, step + span):
                                self._poisoned_steps.add(s)
                            self._grouped_restore = \
                                self.load_latest_checkpoint()

                        action = harness.dispatch_verdict(
                            verdict, snap=snap,
                            restore_rollback=_rollback_window,
                            context=f"in group at step {step}")
                        if action == "skip":
                            step += span
                        else:   # rollback
                            step = self._grouped_restore
                        continue
                if collect_training_stats:
                    # analyze: allow=jit-host-sync — opt-in stats barrier
                    float(net.score())
                t2 = time.perf_counter()
                # group telemetry: steps_total counts the inner steps
                # actually trained; step_seconds stays in per-step
                # units (group wall time averaged over its steps)
                self._obs_acc.count_observe(
                    "dl4j_train_steps_total", "dl4j_train_step_seconds",
                    (t2 - t0) / max(1, len(abs_steps)),
                    n=len(abs_steps))
                if self.tracer is not None:
                    self.tracer.record(
                        "train_group", t0, t2, cat="train",
                        args={"step": step, "steps": len(abs_steps)})
                for listener in net.listeners:
                    listener.iteration_done(net, net.iteration)
                prev = step
                step += span
                # checkpoint when the group CROSSES a cadence boundary
                # (group ends rarely align with checkpoint_every)
                if (self.checkpoint_dir and every
                        and prev // every != step // every):
                    if pp is not None:
                        pp.mark("checkpoint")
                    self.save_checkpoint(step)
                if pp is not None:
                    pp.end_step()
                if collect_training_stats:
                    self._stats.append({
                        "step": prev,
                        "data_ms": (t1 - t0) * 1e3,
                        "fit_ms": (t2 - t1) * 1e3,
                        "listener_ms": 0.0,
                        "checkpoint_ms":
                            (time.perf_counter() - t2) * 1e3,
                    })
        return self

    def _fit_local_sgd(self, batch_fn, num_steps, start_step,
                       collect_training_stats=False):
        """k-step local-SGD groups over the global mesh (the DCN
        compression role — see __init__). Reuses LocalStepTrainer's
        shard_map program; data stacked [k, G, ...] per group."""
        import time

        from deeplearning4j_tpu.parallel.wrapper import LocalStepTrainer

        net = self.net
        guard = self.guard
        wd = self.watchdog
        k = self.averaging_frequency
        if self._local_step is None:
            self._local_step = LocalStepTrainer(
                net, self.mesh,
                threshold=self.threshold_compression,
                per_step_losses=self.guard_inner_steps)
        is_graph = hasattr(net.conf, "network_inputs")
        every = self.checkpoint_every
        pp = self.phase_profiler
        with self.mesh:
            step = start_step
            while step < num_steps:
                self._check_preemption(step)
                _fire("train.step")
                _fire("train.hang")
                fire_hang_hard()
                if wd is not None:
                    wd.beat("dispatch", step=step)
                # group-level phase attribution (guard-anomaly exits
                # leave the group unprofiled; begin_step resets state)
                if pp is not None:
                    pp.begin_step(step)
                    pp.mark("data_wait")
                t0 = time.perf_counter()
                span = min(step + k, num_steps) - step
                group, abs_steps = self._fetch_window(
                    batch_fn, step, span)
                if not group:
                    step += span
                    continue
                if pp is not None:
                    pp.mark("h2d")
                xs, ys = self._stack_window(group)
                t1 = time.perf_counter()
                # guard at group granularity: one check per rendezvous
                # (already a 1/k sampling of the underlying steps)
                check_now = guard is not None and guard.check_every > 0
                snap = (guard.snapshot(net)
                        if check_now and guard.policy == "skip_step"
                        else None)
                if pp is not None:
                    pp.mark("dispatch")
                if is_graph:
                    name = net.conf.network_inputs[0]
                    self._local_step.run_arrays({name: xs}, [ys])
                else:
                    self._local_step.run_arrays(xs, ys)
                if wd is not None:
                    wd.beat("fetch", step=step)
                if pp is not None:
                    pp.mark("host_sync")
                if check_now and self.guard_inner_steps:
                    # granularity fix: the compiled group program also
                    # returned per-inner-step (dp-averaged) losses — a
                    # non-finite one condemns THAT step only, not the
                    # whole k-step window
                    inner = np.asarray(
                        self._local_step.last_step_losses)
                    bad = [abs_steps[i] for i in range(len(abs_steps))
                           if not np.isfinite(inner[i])]
                    if bad:
                        guard.counters["checks"] += 1
                        guard.counters["nonfinite"] += 1
                        _obs.count("dl4j_train_guard_checks_total")
                        _obs.count("dl4j_train_guard_nonfinite_total")
                        if guard.policy == "abort":
                            raise NonFiniteLossError(
                                f"non-finite loss at inner step(s) "
                                f"{bad} of group at step {step} "
                                f"(policy=abort)")
                        self._poisoned_steps.update(bad)
                        if guard.policy == "skip_step":
                            guard.restore(net, snap)
                            guard.note_skip()
                            logger.warning(
                                "guard: non-finite inner step(s) %s — "
                                "window replayed without them", bad)
                        else:   # rollback
                            guard.note_rollback()
                            if guard.counters["rollbacks"] \
                                    > guard.max_rollbacks:
                                raise NonFiniteLossError(
                                    "guard exceeded max_rollbacks="
                                    f"{guard.max_rollbacks}")
                            step = self.load_latest_checkpoint()
                        continue   # re-enter the window minus `bad`
                if check_now:
                    verdict = guard.post_step(net)
                    if verdict != "ok":
                        if guard.policy == "skip_step":
                            guard.restore(net, snap)
                            guard.note_skip()
                            step += span
                            continue
                        if guard.policy == "rollback":
                            # the whole group is the poisoned window
                            for s in range(step, step + span):
                                self._poisoned_steps.add(s)
                            guard.note_rollback()
                            if guard.counters["rollbacks"] \
                                    > guard.max_rollbacks:
                                raise NonFiniteLossError(
                                    "guard exceeded max_rollbacks="
                                    f"{guard.max_rollbacks}")
                            step = self.load_latest_checkpoint()
                            continue
                        raise NonFiniteLossError(
                            f"{verdict} training state in group at "
                            f"step {step} (policy=abort)")
                if collect_training_stats:
                    # analyze: allow=jit-host-sync — opt-in stats barrier
                    float(net.score())
                t2 = time.perf_counter()
                # group telemetry: steps_total counts the inner steps
                # actually trained; step_seconds stays in per-step
                # units (group wall time averaged over its steps)
                self._obs_acc.count_observe(
                    "dl4j_train_steps_total", "dl4j_train_step_seconds",
                    (t2 - t0) / max(1, len(abs_steps)),
                    n=len(abs_steps))
                if self.tracer is not None:
                    self.tracer.record(
                        "train_group", t0, t2, cat="train",
                        args={"step": step, "steps": len(abs_steps)})
                prev = step
                step += span
                # checkpoint when the group CROSSES a cadence boundary
                # (group ends rarely align with checkpoint_every)
                if (self.checkpoint_dir and every
                        and prev // every != step // every):
                    if pp is not None:
                        pp.mark("checkpoint")
                    self.save_checkpoint(step)
                if pp is not None:
                    pp.end_step()
                if collect_training_stats:
                    self._stats.append({
                        "step": prev,
                        "data_ms": (t1 - t0) * 1e3,
                        "fit_ms": (t2 - t1) * 1e3,
                        "listener_ms": 0.0,
                        "checkpoint_ms":
                            (time.perf_counter() - t2) * 1e3,
                    })
        return self

    def training_stats(self):
        """Per-step phase timings recorded when fit(...,
        collect_training_stats=True) — the CommonSparkTrainingStats
        equivalent. Returns a list of dicts plus an aggregate row, and a
        `resilience` block (guard / watchdog / preemption / supervisor
        counters) whenever any self-healing hook is attached."""
        stats = list(getattr(self, "_stats", []))
        wire = (self._local_step.wire_stats()
                if self._local_step is not None else None)
        resil = self.resilience_stats()
        prof = self._profiler_stats()
        phases = (self.phase_profiler.report()
                  if self.phase_profiler is not None else None)
        pipe = self._harness.pipeline_stats()
        if not stats:
            return {"steps": [], "summary": {}, "wire": wire,
                    "resilience": resil, "profiler": prof,
                    "phases": phases, "pipeline": pipe}
        summary = {
            k: float(np.mean([s[k] for s in stats]))
            for k in ("data_ms", "fit_ms", "listener_ms", "checkpoint_ms")
        }
        return {"steps": stats, "summary": summary, "wire": wire,
                "resilience": resil, "profiler": prof,
                "phases": phases, "pipeline": pipe}

    def _profiler_stats(self):
        """Surface an attached ProfilerListener's device-trace facts
        (satellite: trace_dir was previously only reachable by digging
        the listener out of net.listeners by hand)."""
        for listener in getattr(self.net, "listeners", []):
            if hasattr(listener, "trace_dir") \
                    and hasattr(listener, "log_dir"):
                return {"trace_dir": listener.trace_dir,
                        "log_dir": listener.log_dir,
                        "active": bool(getattr(listener, "_active",
                                               False)),
                        "done": bool(getattr(listener, "_done", False))}
        return None

    def resilience_stats(self):
        """Guard / watchdog / preemption / restart counters (None when
        no self-healing hook is attached and nothing was counted) —
        delegated to the shared harness, which owns the counters."""
        return self._harness.resilience_stats()

    def export_stats_html(self, path: str):
        """Timeline HTML export (ref StatsUtils.exportStatsAsHtml)."""
        import json as _json

        data = self.training_stats()
        rows = "".join(
            f"<tr><td>{s['step']}</td><td>{s['data_ms']:.2f}</td>"
            f"<td>{s['fit_ms']:.2f}</td>"
            f"<td>{s['checkpoint_ms']:.2f}</td></tr>"
            for s in data["steps"])
        resil = ("" if data.get("resilience") is None else
                 f"<p>resilience: {_json.dumps(data['resilience'])}</p>")
        page = (
            "<!DOCTYPE html><html><head><meta charset='utf-8'>"
            "<title>training timeline</title></head><body>"
            f"<h1>TrainingMaster timeline</h1>"
            f"<p>summary: {_json.dumps(data['summary'])}</p>"
            f"{resil}"
            "<table border='1'><tr><th>step</th><th>data ms</th>"
            "<th>fit ms</th><th>checkpoint ms</th></tr>"
            f"{rows}</table></body></html>")
        with open(path, "w") as f:
            f.write(page)
        return path

    # ------------------------------------------------------------ evaluate
    def evaluate(self, batch_fn: Callable[[int], Tuple], num_steps: int,
                 evaluation=None):
        """Distributed evaluation (the Spark eval flatMap+reduce role,
        IEvaluateFlatMapFunction/IEvaluationReduceFunction): every
        process runs inference on its partition of each batch; the
        device argmax comparison is summed over the dp axis inside the
        compiled program, so each host ends with identical GLOBAL
        confusion counts folded into `evaluation`."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from deeplearning4j_tpu.eval import Evaluation

        self._stage_net()
        net = self.net
        if evaluation is None:
            evaluation = Evaluation()
        is_graph = hasattr(net.conf, "network_inputs")
        rep = NamedSharding(self.mesh, P())

        if getattr(self, "_eval_fn", None) is None:
            @partial(jax.jit, static_argnums=(4,))
            def confusion_counts(params, states, x, y, has_mask, lm):
                if is_graph:
                    name = net.conf.network_inputs[0]
                    acts, _, _ = net._forward(params, states, {name: x},
                                              train=False, rng=None)
                    out = acts[net.conf.network_outputs[0]]
                else:
                    out, _, _ = net._forward(params, states, x,
                                             train=False, rng=None)
                c = y.shape[-1]
                # time-series outputs [N,T,C] flatten to rows like
                # Evaluation.eval does
                pred = jnp.argmax(out, axis=-1).reshape(-1)
                actual = jnp.argmax(y, axis=-1).reshape(-1)
                onehot = (jax.nn.one_hot(actual, c)[:, :, None]
                          * jax.nn.one_hot(pred, c)[:, None, :])
                if has_mask:
                    # label mask [N,T] (or [N]): drop padded timesteps
                    # exactly like Evaluation.eval(..., mask=lm) — any
                    # nonzero mask value means "keep" (boolean semantics)
                    keep = (lm.reshape(-1) != 0).astype(onehot.dtype)
                    onehot = onehot * keep[:, None, None]
                # global sum: GSPMD reduces over the dp-sharded batch
                return jax.lax.with_sharding_constraint(
                    jnp.sum(onehot, axis=0), rep)

            self._eval_fn = confusion_counts
        confusion_counts = self._eval_fn

        with self.mesh:
            for step in range(num_steps):
                # batch_fn follows the container convention
                # (x, y[, features_mask[, labels_mask]]); like the
                # containers' evaluate(), only the LABEL mask shapes the
                # confusion counts (Evaluation.eval(..., mask=lm))
                batch = batch_fn(step)
                x, y = self._global_batch(batch[0], batch[1])
                lm = batch[3] if len(batch) > 3 else None
                if lm is not None:
                    lm = self._stage(lm, P("dp"))
                counts = confusion_counts(net.params, net.states, x, y,
                                          lm is not None, lm)
                m = np.asarray(self._host_leaf(counts)).astype(np.int64)
                evaluation._ensure(m.shape[0])
                evaluation.confusion.matrix += m
        return evaluation

    # ------------------------------------------------------- checkpointing
    def _ckpt_path(self, step: int) -> str:
        return os.path.join(self.checkpoint_dir, f"step-{step:08d}.npz")

    @staticmethod
    def _host_leaf(a):
        """Fetch a (replicated) global array to host."""
        if hasattr(a, "addressable_shards"):
            return np.asarray(a.addressable_shards[0].data)
        return np.asarray(a)

    def save_checkpoint(self, step: int):
        """Timed wrapper around the format-specific save: checkpoint
        write latency + count land in the registry, and with a tracer
        attached the save records a span parented to the current step
        span."""
        t0 = time.perf_counter()
        result = self._save_checkpoint_impl(step)
        t1 = time.perf_counter()
        _obs.count("dl4j_checkpoint_writes_total")
        _obs.observe("dl4j_checkpoint_write_seconds", t1 - t0)
        if self.tracer is not None:
            self.tracer.record("checkpoint_save", t0, t1,
                               cat="checkpoint",
                               parent=self._harness.step_span,
                               args={"step": step})
        return result

    def _save_checkpoint_impl(self, step: int):
        """Write {params, updater state, states, step, rng}.

        format="npz": process 0 gathers everything to host and writes
        one crash-safe .npz (shared-FS model, ref
        ParameterAveragingTrainingMaster's driver-side ownership) —
        right for replicated dp training at this scale. The write is
        tmp + fsync + os.replace with a sha256 manifest entry recorded
        from the pre-publish bytes, so a kill mid-write publishes
        nothing and a torn write is detected on load; transient OSErrors
        retry per `checkpoint_retry`; `keep_last` prunes old steps.
        format="orbax": every process participates in an
        orbax.checkpoint save (SURVEY §7's "orbax-style sharded
        checkpoints for scale" — sharded arrays are written without
        gathering to one host)."""
        import jax

        if self.checkpoint_format == "orbax":
            return self._save_orbax(step)
        # per-rank mode: EVERY process writes its own copy (into its
        # rank-<r> dir) — the divergence quorum's voters
        if jax.process_index() != 0 and not self.per_rank_checkpoints:
            return
        os.makedirs(self.checkpoint_dir, exist_ok=True)
        net = self.net
        payload = {}
        for group, tree in (("params", net.params),
                            ("states", net.states)):
            for i, leaf in enumerate(jax.tree_util.tree_leaves(tree)):
                payload[f"{group}:{i}"] = self._host_leaf(leaf)
        # ZeRO-1: sharded optimizer leaves go to a per-rank sidecar
        # (this rank's slice only); the replicated remainder rides the
        # main payload so the divergence quorum's state digest stays
        # identical across ranks
        shard_slices = None
        if self._mesh_mgr is not None:
            shard_slices = self._sharded_upd_payload(payload)
        else:
            for i, leaf in enumerate(
                    jax.tree_util.tree_leaves(net.updater_states)):
                payload[f"upd:{i}"] = self._host_leaf(leaf)
        payload["rng"] = np.asarray(net._rng)
        # self-describing: fallback loads recover position without
        # trusting latest.json (which may point at the damaged step)
        payload["step"] = np.asarray(step)
        payload["iteration"] = np.asarray(int(net.iteration))
        payload["epoch"] = np.asarray(int(net.epoch))
        final = self._ckpt_path(step)
        fn = os.path.basename(final)
        # canonical state digest (container-timestamp-immune): what the
        # cross-rank divergence quorum compares — identical replicated
        # state hashes equal on every rank even though the zip bytes
        # differ
        state_h = hashlib.sha256()
        for k in sorted(payload):
            a = np.ascontiguousarray(payload[k])
            state_h.update(k.encode())
            state_h.update(str(a.dtype).encode())
            state_h.update(str(a.shape).encode())
            state_h.update(a.tobytes())
        state_sha = state_h.hexdigest()

        def _write():
            with _ci.atomic_writer(final, suffix=".tmp.npz") as tmp:
                with open(tmp, "wb") as f:
                    np.savez(f, **payload)
                digest = _ci.sha256_file(tmp)
                size = os.path.getsize(tmp)
                # chaos hook: 'raise' = kill mid-write (tmp discarded,
                # nothing published); 'truncate' = torn write slipping
                # past the atomic publish — caught by the checksum
                _fire("checkpoint.write", path=tmp)
            _ci.record_checksum(self.checkpoint_dir, fn, digest, size,
                                extra={"step": step,
                                       "state_sha256": state_sha})

        if shard_slices is not None:
            # sidecar FIRST: a published main step implies its slice
            # exists (a kill between the two leaves an orphan sidecar,
            # which the sharded quorum simply never elects)
            self._write_shard_sidecar(step, shard_slices, state_sha)
        self._ckpt_retry.call(_write)
        meta = {"step": step, "iteration": int(net.iteration),
                "epoch": int(net.epoch)}
        _ci.atomic_write_json(
            os.path.join(self.checkpoint_dir, "latest.json"), meta)
        _ci.apply_retention(self.checkpoint_dir, self.keep_last)

    def _sharded_upd_payload(self, payload) -> dict:
        """Split the updater-state leaves for the ZeRO-1 checkpoint
        layout: replicated leaves into the (quorum-voted) main
        `payload` as `upd:<i>`, sharded leaves gathered from the mesh
        (timed as `dl4j_mesh_allgather_seconds`) and sliced to THIS
        process's contiguous rows for the sidecar. The main payload
        records `upd_sharded_idx` + `shard_world` so the digest covers
        the layout itself."""
        import jax

        from deeplearning4j_tpu.engine.sharding import slice_rows

        net = self.net
        mgr = self._mesh_mgr
        layout = mgr.shard_layout(net.updater_states)
        full = mgr.gather_tree(net.updater_states)
        leaves = jax.tree_util.tree_leaves(full)
        world = max(1, int(jax.process_count()))
        rank = int(jax.process_index())
        slices = {}
        sharded_idx = []
        for i, (leaf, sharded) in enumerate(zip(leaves, layout)):
            if sharded and leaf.shape[0] % world == 0:
                sharded_idx.append(i)
                slices[f"slice:{i}"] = slice_rows(leaf, rank, world)
            else:
                payload[f"upd:{i}"] = leaf
        payload["upd_sharded_idx"] = np.asarray(sharded_idx, np.int64)
        payload["shard_world"] = np.asarray(world)
        return slices

    def _write_shard_sidecar(self, step, slices, state_sha):
        """This rank's optimizer-state slice sidecar: atomic write +
        manifest entry carrying `main_state_sha256`, the digest of the
        main state the slice belongs to — the linkage the sharded
        quorum verifies before trusting a slice."""
        import jax

        side_fn = _ci.shard_sidecar_filename(step)
        side = os.path.join(self.checkpoint_dir, side_fn)
        world = max(1, int(jax.process_count()))
        rank = int(jax.process_index())

        def _write_side():
            with _ci.atomic_writer(side, suffix=".tmp.npz") as tmp:
                with open(tmp, "wb") as f:
                    np.savez(f, shard_rank=np.asarray(rank),
                             shard_world=np.asarray(world), **slices)
                digest = _ci.sha256_file(tmp)
                size = os.path.getsize(tmp)
            _ci.record_checksum(
                self.checkpoint_dir, side_fn, digest, size,
                extra={"step": step, "shard_rank": rank,
                       "shard_world": world,
                       "main_state_sha256": state_sha})

        self._ckpt_retry.call(_write_side)

    def _restore_sharded_upd(self, data, step: int):
        """Host updater-state tree reassembled from the sharded
        checkpoint layout: replicated leaves from the main payload,
        sharded leaves from the per-rank sidecar slices — saved at ANY
        world size; the zero1 staging re-slices for the CURRENT world
        (resharding on resume, counted as `dl4j_mesh_reshard_total`
        when the worlds differ)."""
        import jax

        from deeplearning4j_tpu.engine.sharding import assemble_rows
        from deeplearning4j_tpu.resilience.errors import (
            CheckpointIntegrityError,
        )

        net = self.net
        leaves, treedef = jax.tree_util.tree_flatten(net.updater_states)
        world = int(data["shard_world"])
        sharded_idx = [int(i) for i in
                       np.asarray(data["upd_sharded_idx"]).reshape(-1)]
        new = [None] * len(leaves)
        for i in range(len(leaves)):
            if i not in sharded_idx:
                new[i] = data[f"upd:{i}"]
        if sharded_idx:
            fn = os.path.basename(self._ckpt_path(step))
            expect = _ci.state_digest(self.checkpoint_dir, fn)
            if self.per_rank_checkpoints or world > 1:
                base = self._ckpt_base
                dirs = [_ci.rank_checkpoint_dir(base, r)
                        for r in range(world)]
            else:
                dirs = [self.checkpoint_dir]
            slices = _ci.collect_sharded_slices(
                dirs, step, expect_digest=expect)
            if slices is None:
                raise CheckpointIntegrityError(
                    f"sharded checkpoint step {step}: optimizer-state "
                    f"slice set incomplete or untrusted across "
                    f"{len(dirs)} rank dir(s)")
            opened = {r: np.load(p) for r, p in slices.items()}
            try:
                for i in sharded_idx:
                    new[i] = assemble_rows(
                        {r: d[f"slice:{i}"] for r, d in opened.items()},
                        world)
            finally:
                for d in opened.values():
                    d.close()
        cur_world = self.world_info()["processes"]
        if world != cur_world:
            # loading slices written by a different world: the staging
            # below re-slices them for the live mesh
            if self._mesh_mgr is not None:
                self._mesh_mgr.reshards += 1
            _obs.count("dl4j_mesh_reshard_total")
            logger.warning(
                "sharded checkpoint step %d: resharding optimizer "
                "state from save-world %d to live world %d", step,
                world, cur_world)
        return jax.tree_util.tree_unflatten(treedef, new)

    def _orbax_path(self, step: int) -> str:
        return os.path.abspath(os.path.join(
            self.checkpoint_dir, f"step-{step}.orbax"))

    def _save_orbax(self, step: int):
        import jax
        import orbax.checkpoint as ocp

        net = self.net
        os.makedirs(self.checkpoint_dir, exist_ok=True)
        # self-describing payload (step/iteration/epoch ride inside):
        # the fallback scan can resume position without latest.json,
        # matching the npz format's contract
        payload = {"params": net.params, "upd": net.updater_states,
                   "states": net.states, "rng": np.asarray(net._rng),
                   "step": np.asarray(step),
                   "iteration": np.asarray(int(net.iteration)),
                   "epoch": np.asarray(int(net.epoch))}
        with ocp.StandardCheckpointer() as ckptr:
            ckptr.save(self._orbax_path(step), payload, force=True)
        if jax.process_index() == 0:
            # integrity parity with the .npz path: per-file sha256
            # sidecar inside the orbax dir, verified before any restore
            # so the fallback scan skips torn directories
            _ci.write_tree_manifest(self._orbax_path(step))
            meta = {"step": step, "iteration": int(net.iteration),
                    "epoch": int(net.epoch), "format": "orbax"}
            _ci.atomic_write_json(
                os.path.join(self.checkpoint_dir, "latest.json"), meta)
            _ci.apply_retention(self.checkpoint_dir, self.keep_last)

    def _load_orbax(self, meta) -> int:
        import jax
        import orbax.checkpoint as ocp

        t_restore = time.perf_counter()
        net = self.net
        if net.params is None:
            net.init()
        # torn/tampered orbax dir: raise BEFORE restore so the caller's
        # fallback scan moves on to the next-newest candidate
        _ci.require_valid_tree(self._orbax_path(meta["step"]))
        with ocp.StandardCheckpointer() as ckptr:
            data = ckptr.restore(self._orbax_path(meta["step"]))
        net.params = self._replicated(data["params"])
        net.updater_states = self._replicated(data["upd"])
        net.states = self._replicated(data["states"])
        net._rng = jax.numpy.asarray(np.asarray(data["rng"]))
        # meta (latest.json) may be missing during a fallback scan;
        # newer payloads are self-describing
        if "iteration" in meta:
            net.iteration = meta["iteration"]
            net.epoch = meta["epoch"]
        elif "iteration" in data:
            net.iteration = int(np.asarray(data["iteration"]))
            net.epoch = int(np.asarray(data["epoch"]))
        self._staged = True
        _obs.count("dl4j_checkpoint_restores_total")
        _obs.observe("dl4j_checkpoint_restore_seconds",
                     time.perf_counter() - t_restore)
        return meta["step"]

    def _orbax_steps(self):
        return [s for s, fn in _ci.list_all_checkpoints(
            self.checkpoint_dir) if fn.endswith(".orbax")]

    def _restore_newest_valid_orbax(self) -> int:
        """Fallback scan parity for orbax-format checkpoints: when the
        latest pointer is damaged/missing (or points at a damaged dir),
        restore the newest orbax directory that actually loads."""
        for step in reversed(self._orbax_steps()):
            try:
                return self._load_orbax({"step": step})
            except Exception:   # noqa: BLE001 - damaged dir: try older
                continue
        return 0

    @staticmethod
    def _structural_ok(path: str) -> None:
        """Cheap structural probe: a truncated/torn .npz fails to open
        or to yield its zip directory. Raises on damage."""
        with np.load(path) as z:
            z["rng"]

    def _read_latest_meta(self):
        latest = os.path.join(self.checkpoint_dir, "latest.json")
        try:
            with open(latest) as f:
                return json.load(f)
        except (OSError, ValueError):
            # missing or torn latest pointer: fall back to a dir scan
            return None

    def _select_valid_step(self, meta) -> Optional[int]:
        """The step to restore: the latest pointer's target if it passes
        checksum + structural validation, else the newest checkpoint in
        the directory that does (SURVEY §5.3 made real: a truncated
        'latest' must never win)."""
        if meta is not None and "step" in meta:
            step = meta["step"]
            fn = os.path.basename(self._ckpt_path(step))
            if _ci.validate_file(self.checkpoint_dir, fn):
                try:
                    self._structural_ok(self._ckpt_path(step))
                    return step
                except Exception:   # noqa: BLE001 - damaged file
                    pass
        return _ci.newest_valid_checkpoint(
            self.checkpoint_dir, structural_check=self._structural_ok)

    def load_latest_checkpoint(self) -> int:
        """Restore the newest *valid* checkpoint if present; returns the
        step to resume FROM (0 if none survives validation). All
        processes load the same file. Corrupt/truncated candidates are
        skipped in favor of the newest one passing sha256 + structural
        checks."""
        if not self.checkpoint_dir or not os.path.isdir(self.checkpoint_dir):
            return 0
        meta = self._read_latest_meta()
        if meta is not None and meta.get("format") == "orbax":
            try:
                return self._load_orbax(meta)
            except Exception:   # noqa: BLE001 - damaged target: scan
                return self._restore_newest_valid_orbax()
        step = self._select_valid_step(meta)
        if step is None:
            # no valid npz: orbax dirs saved without (or with a torn)
            # latest pointer still count — retention/fallback parity
            return self._restore_newest_valid_orbax()
        return self._restore_npz(step, meta)

    def load_checkpoint_at(self, step: int) -> int:
        """Resume handshake: restore EXACTLY `step` (validated),
        raising on a missing/torn file instead of silently falling back
        — the ClusterSupervisor relaunches every rank with one shared
        resume step, and a rank whose filesystem view disagrees must
        fail loudly (and be gang-restarted) rather than resume
        elsewhere. step <= 0 means 'no checkpoint': start fresh."""
        from deeplearning4j_tpu.resilience.errors import (
            CheckpointIntegrityError,
        )

        if step <= 0:
            self._stage_net()
            return 0
        if self.checkpoint_format == "orbax":
            return self._load_orbax({"step": step})
        path = self._ckpt_path(step)
        fn = os.path.basename(path)
        if not _ci.validate_file(self.checkpoint_dir or "", fn):
            raise CheckpointIntegrityError(
                f"resume handshake: checkpoint step {step} missing or "
                f"failed validation in {self.checkpoint_dir}")
        self._structural_ok(path)
        return self._restore_npz(step, self._read_latest_meta())

    def _restore_npz(self, step: int, meta) -> int:
        t_restore = time.perf_counter()
        data = self._ckpt_retry.call(np.load, self._ckpt_path(step))
        import jax

        net = self.net
        if net.params is None:
            net.init()

        def restore(group, tree):
            leaves, treedef = jax.tree_util.tree_flatten(tree)
            new = [data[f"{group}:{i}"] for i in range(len(leaves))]
            return jax.tree_util.tree_unflatten(treedef, new)

        net.params = self._replicated(restore("params", net.params))
        if "shard_world" in data.files:
            upd = self._restore_sharded_upd(data, step)
        else:
            upd = restore("upd", net.updater_states)
        if self._mesh_mgr is not None:
            # zero1 staging re-slices the assembled state for the LIVE
            # mesh — the resharding-on-resume placement
            net.updater_states = self._mesh_mgr.shard_tree(upd)
        else:
            net.updater_states = self._replicated(upd)
        net.states = self._replicated(restore("states", net.states))
        net._rng = jax.numpy.asarray(data["rng"])
        # newer checkpoints are self-describing; latest.json only covers
        # the pre-manifest format (and may describe a different step)
        if "iteration" in data.files:
            net.iteration = int(data["iteration"])
            net.epoch = int(data["epoch"])
        elif meta is not None and meta.get("step") == step:
            net.iteration = meta["iteration"]
            net.epoch = meta["epoch"]
        self._staged = True
        _obs.count("dl4j_checkpoint_restores_total")
        _obs.observe("dl4j_checkpoint_restore_seconds",
                     time.perf_counter() - t_restore)
        return step

    def list_checkpoints(self):
        if not self.checkpoint_dir or not os.path.isdir(self.checkpoint_dir):
            return []
        out = []
        for fn in sorted(os.listdir(self.checkpoint_dir)):
            m = re.match(r"step-(\d+)\.(npz|orbax)$", fn)
            if m:
                out.append(int(m.group(1)))
        return sorted(out)
