"""Training engine: ONE compiled step program + ONE host supervisor.

ROADMAP item 1. Before this package, TrainingMaster.fit,
ParallelWrapper._run_guarded, and EarlyStoppingTrainer each re-wired
the same concerns (non-finite guard, watchdog, preemption, checkpoint
publish, telemetry accumulator, phase profiler) around three separate
step loops — so every compiled-path change (MFU work, pjit sharding)
had to land three times. Tensor Processing Primitives (arXiv
2104.05755) argues for exactly this separation: a small set of
compiled primitives composed under one host-side schedule; Automatic
Cross-Replica Sharding of Weight Update (arXiv 2004.13336) assumes a
single step program to shard. Two halves:

  StepProgram   the compiled half — a pure, jitted, donated-buffer
                train step (params / updater state / BN states donated
                end-to-end), owner of the shared loss/update closures
                the local-SGD and stale-gradient trainers also compile
                from, registered with the net's JitCache (recompile
                forensics).
                Optional `lax.scan` k-step grouping: one dispatch
                advances k steps while per-inner-step dp-visible
                losses are preserved
                so a NonFiniteGuard can condemn ONE poisoned inner
                step instead of the whole window.
  StepHarness   the host half — one supervisor owning the
                guard-verdict dispatch (skip / rollback / abort),
                watchdog lifecycle + beats, preemption install +
                step-boundary checks, checkpoint cadence, the
                StepAccumulator every per-step metric batches through,
                the StepPhaseProfiler wiring, tracer spans, and
                teardown (flush, stop, close attached data iterators).
                TrainingMaster, ParallelWrapper, and
                EarlyStoppingTrainer are thin adapters over it.
  pipeline      the harness-owned input pipeline (engine/pipeline.py):
                StepPrefetcher / IteratorPipeline run fetch + h2d
                staging ahead of the compute on a producer thread so
                `data_wait`/`h2d` overlap `device_compute` — built and
                torn down by the harness session, opt-out per entry
                point via `pipeline=False`.
  mesh/sharding the sharded scale-out subsystem (ROADMAP item 2,
                arXiv 2004.13336): MeshManager derives the live dp
                mesh and owns the ZeRO-1 placement policy;
                engine/sharding.py builds the mesh-sharded donated
                step (reduce-scatter grads → shard-local update →
                all-gather params inside ONE program) that
                StepProgram.attach_mesh routes run/run_group/
                run_batch through — `sharding="zero1"` on any entry
                point, byte-identical to the unsharded step with 1/n
                per-replica optimizer memory.
"""

from deeplearning4j_tpu.engine.harness import StepHarness
from deeplearning4j_tpu.engine.pipeline import (
    SKIPPED,
    IteratorPipeline,
    StepPrefetcher,
    stack_staged,
)
from deeplearning4j_tpu.engine.mesh import MeshManager
from deeplearning4j_tpu.engine.sharding import (
    assemble_rows,
    reslice,
    slice_bounds,
    slice_rows,
    zero1_leaf_sharded,
)
from deeplearning4j_tpu.engine.step_program import (
    StepProgram,
    make_loss_and_apply,
)
from deeplearning4j_tpu.engine.decode_program import DecodeProgram

__all__ = ["StepProgram", "StepHarness", "make_loss_and_apply",
           "StepPrefetcher", "IteratorPipeline", "stack_staged",
           "SKIPPED", "MeshManager", "zero1_leaf_sharded",
           "slice_bounds", "slice_rows", "assemble_rows", "reslice",
           "DecodeProgram"]
