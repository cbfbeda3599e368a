"""EarlyStoppingTrainer (parity: earlystopping/trainer/
EarlyStoppingTrainer.java / BaseEarlyStoppingTrainer.java): epoch loop
with per-iteration abort conditions, per-epoch held-out scoring, best-
model checkpointing."""

from __future__ import annotations

import logging

from deeplearning4j_tpu.earlystopping.config import (
    EarlyStoppingResult,
    TerminationReason,
)

logger = logging.getLogger("deeplearning4j_tpu")


class EarlyStoppingTrainer:
    def __init__(self, config, net, train_iterator, guard=None,
                 snapshot_every: int = 0,
                 pipeline=None, pipeline_depth: int = 2,
                 sharding=None):
        """`guard` (resilience.NonFiniteGuard) checks the net after
        (sampled) training batches: a non-finite/spiking batch is
        skipped with the pre-batch state restored (policy='skip_step')
        or aborts the fit (policy='abort'). 'rollback' needs a
        rollback target: pass `snapshot_every=N` and an in-memory
        device snapshot (resilience.PeriodicSnapshotter) refreshed
        every N guarded batches is restored instead — no checkpoint
        directory required."""
        self._snapshotter = None
        if guard is not None and guard.policy == "rollback":
            if snapshot_every <= 0:
                raise ValueError(
                    "NonFiniteGuard(policy='rollback') under "
                    "EarlyStoppingTrainer needs snapshot_every=N > 0 "
                    "(an in-memory rollback target; TrainingMaster "
                    "uses checkpoints instead)")
            from deeplearning4j_tpu.resilience.supervisor import (
                PeriodicSnapshotter,
            )

            self._snapshotter = PeriodicSnapshotter(
                guard, every=snapshot_every)
        from deeplearning4j_tpu.engine import StepHarness

        self.config = config
        self.net = net
        self.train_iterator = train_iterator
        # harness-owned input pipeline (engine/pipeline.py): async ETL
        # + double-buffered device staging ahead of fit_batch. Default
        # (None): ON for single-process jobs; pipeline=False opts out.
        self.pipeline = pipeline
        self.pipeline_depth = max(1, int(pipeline_depth))
        # the shared supervisor (engine/): one guard-verdict dispatch
        # for all three fit entry points; this trainer's rollback
        # target is the in-memory snapshotter
        self._harness = StepHarness(net, guard=guard,
                                    snapshotter=self._snapshotter)
        self.guard = self._harness.guard
        # ZeRO-1 (engine/sharding.py): _fit_batch routes through the
        # mesh-sharded StepProgram — optimizer state sharded over the
        # live device mesh, equal to the unsharded trainer within a
        # few ulp
        if sharding not in (None, "replicated", "zero1"):
            raise ValueError(
                f"sharding must be None|'replicated'|'zero1': {sharding}")
        self._mesh_mgr = None
        if sharding == "zero1":
            from deeplearning4j_tpu.engine.mesh import MeshManager

            self._mesh_mgr = MeshManager()
            if net.params is None:
                net.init()
            import jax
            import numpy as _np

            net.params = self._mesh_mgr.replicate_tree(
                jax.tree_util.tree_map(_np.asarray, net.params))
            net.updater_states = self._mesh_mgr.shard_tree(
                jax.tree_util.tree_map(_np.asarray, net.updater_states))
            net.states = self._mesh_mgr.replicate_tree(
                jax.tree_util.tree_map(_np.asarray, net.states))
            self._harness.program.attach_mesh(self._mesh_mgr)

    def _pipeline_enabled(self) -> bool:
        if self.pipeline is not None:
            return bool(self.pipeline)
        import jax

        return jax.process_count() == 1

    def _pipeline_host_only(self) -> bool:
        """Device staging suits the plain trainer (fit_batch consumes
        the staged tuple directly); the parallel trainer re-buffers
        host batches for its wrapper and overrides this to True."""
        return False

    def _fit_batch(self, batch):
        """One training batch through the shared StepProgram (full
        fit_batch semantics — listener fire, TBPTT/solver fallback);
        EarlyStoppingParallelTrainer overrides to route through
        ParallelWrapper. Uses the fit_batch path so the net's epoch
        counter stays under THIS trainer's control."""
        self._harness.program.run_batch(batch)

    def _fit_batch_guarded(self, batch) -> bool:
        """Run one batch under the shared harness's guard dispatch
        (engine.StepHarness.guarded); False = batch rejected (state
        restored), so the caller skips score/termination checks."""
        if self.guard is None:
            self._fit_batch(batch)
            return True
        return self._harness.guarded(
            lambda: self._fit_batch(batch),
            context=f"at epoch {self.net.epoch}", observe=False)

    def _on_epoch_data_end(self):
        """Hook after the epoch's batch loop (parallel trainer flushes
        its local-SGD group here)."""

    def fit(self) -> EarlyStoppingResult:
        cfg = self.config
        net = self.net
        if net.params is None:
            net.init()
        for c in (cfg.epoch_termination_conditions
                  + cfg.iteration_termination_conditions):
            c.initialize()
        score_vs_epoch = {}
        best_score = None
        best_epoch = -1
        epoch = 0
        reason = None
        details = ""

        # shared session lifecycle: flush + close the train iterator's
        # prefetch thread (AsyncDataSetIterator.close) even when a
        # termination condition or the guard aborts the fit
        self._data = self.train_iterator
        if self._pipeline_enabled():
            self._data = self._harness.build_iterator_pipeline(
                self.train_iterator, depth=self.pipeline_depth,
                host_only=self._pipeline_host_only())
        else:
            self._harness.attach_data(self.train_iterator)
        with self._harness.session():
            reason, details, best_score, best_epoch, epoch = \
                self._fit_epochs(cfg, net, score_vs_epoch, best_score,
                                 best_epoch, epoch, reason, details)

        logger.info("Early stopping: %s (%s); best epoch %d score %s",
                    reason, details, best_epoch, best_score)
        best_model = cfg.model_saver.get_best_model(like_net=net)
        return EarlyStoppingResult(
            termination_reason=reason,
            termination_details=details,
            score_vs_epoch=score_vs_epoch,
            best_model_epoch=best_epoch,
            best_model_score=(float("nan") if best_score is None
                              else best_score),
            total_epochs=epoch,
            best_model=best_model,
        )

    def _fit_epochs(self, cfg, net, score_vs_epoch, best_score,
                    best_epoch, epoch, reason, details):
        data = getattr(self, "_data", self.train_iterator)
        while reason is None:
            net.epoch = epoch
            if hasattr(data, "reset"):
                data.reset()
            for batch in data:
                if not self._fit_batch_guarded(batch):
                    continue   # guard rejected the batch: state restored
                score = net.score()
                if score is None:
                    # Parallel trainer with averaging_frequency=k buffers
                    # the first k-1 batches, so no score exists yet; the
                    # iteration conditions are only defined on real scores.
                    continue
                for c in cfg.iteration_termination_conditions:
                    if c.terminate(score):
                        reason = TerminationReason.ITERATION_TERMINATION
                        details = f"{type(c).__name__} at score {score}"
                        break
                if reason:
                    break
            if reason:
                break
            self._on_epoch_data_end()

            if epoch % cfg.evaluate_every_n_epochs == 0:
                if cfg.score_calculator is not None:
                    score = cfg.score_calculator.calculate_score(net)
                else:
                    score = net.score()
                score_vs_epoch[epoch] = score
                if best_score is None or score < best_score:
                    best_score = score
                    best_epoch = epoch
                    cfg.model_saver.save_best_model(net, score)
                if cfg.save_last_model:
                    cfg.model_saver.save_latest_model(net, score)
                for c in cfg.epoch_termination_conditions:
                    if c.terminate(epoch, score):
                        reason = TerminationReason.EPOCH_TERMINATION
                        details = f"{type(c).__name__} at epoch {epoch}"
                        break
            epoch += 1
        return reason, details, best_score, best_epoch, epoch
