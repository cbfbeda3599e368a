"""ZeRO-1 sharding policies + the mesh-sharded compiled step.

ROADMAP item 2, grounded in "Automatic Cross-Replica Sharding of
Weight Update in Data-Parallel Training" (arXiv 2004.13336): in plain
data-parallel training every replica redundantly applies the SAME
weight update to the SAME fully-replicated optimizer state — O(n)
duplicated update flops and O(n) duplicated optimizer memory for n
replicas. The fix is to shard the update: each replica keeps only its
1/n slice of the optimizer state, reduce-scatters the gradient so it
owns the matching slice, updates shard-locally, and all-gathers the
updated parameters for the next forward pass. The Julia-to-TPU
full-compilation work (arXiv 1810.09868) motivates keeping the whole
sharded step INSIDE one XLA program instead of host-orchestrated
collectives — here the reduce-scatter / shard-local update /
all-gather sequence is expressed as GSPMD sharding constraints inside
the ONE donated-buffer compiled step, so XLA fuses and schedules the
collectives and every fit loop inherits the sharded program unchanged.

Two halves, kept in one module because they must agree on ONE slicing
convention:

  compiled half   `build_zero1_step` / `build_zero1_group`: the
                  StepProgram-owned jitted programs (jax-importing
                  functions only).
  host half       `zero1_leaf_sharded` / `slice_rows` /
                  `assemble_rows` / `reslice`: pure-numpy slice
                  arithmetic shared by checkpoint save, the
                  resharding-on-resume path, and the fast no-jax
                  tier-1 drill twins. A leaf shards over dp iff its
                  leading dim divides dp (jax rejects uneven
                  shardings); everything else stays replicated.

Parity contract (pinned in tests/test_mesh.py): the sharded step
agrees with the unsharded StepProgram oracle — params AND updater
state — within a few ulp of each tensor's scale. Every shipped updater
rule is elementwise (nn/updater), so updating a slice equals slicing
the update; what differs is the order in which the partitioner adds
the per-device partial gradients (a reduce-scatter here, an all-reduce
there), so a gradient element can round the other way. The k-step
group, checkpoint resume and resharding compare the sharded program
with itself and stay bitwise. The update runs the per-layer UNFUSED
updater path
(`make_loss_and_apply(..., fused=False)`): the fused chain concatenates
layers into one flat buffer, which would force XLA to all-gather the
very state we sharded; the unfused math is bitwise-identical by
construction (same elementwise ops, no reordering).

This module stays import-light at module scope (numpy only) so the
host half serves the no-jax checkpoint/reshard drill twins.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

ZERO1_AXIS = "dp"


# ------------------------------------------------------ host-side slicing
def zero1_leaf_sharded(shape: Sequence[int], dp: int) -> bool:
    """True when a leaf of this shape shards its leading dim over a
    dp-extent mesh axis: non-scalar, leading dim divisible by dp (jax
    NamedSharding rejects uneven shardings — indivisible leaves stay
    replicated, a best-effort ZeRO exactly like arXiv 2004.13336's
    per-tensor applicability)."""
    shape = tuple(shape)
    return (dp > 1 and len(shape) >= 1 and shape[0] > 0
            and shape[0] % dp == 0)


def slice_bounds(n_rows: int, rank: int, world: int) -> Tuple[int, int]:
    """Row interval [lo, hi) of process `rank`'s slice of a sharded
    leaf. Processes hold CONTIGUOUS device shards (jax.devices() is
    process-major), so the per-process slice is rows
    [rank*n/world, (rank+1)*n/world) regardless of how many local
    devices subdivide it — the one convention checkpoint save, resume
    resharding, and the in-memory staging all derive from."""
    if n_rows % world:
        raise ValueError(
            f"leaf with {n_rows} rows cannot slice over world {world}")
    per = n_rows // world
    return rank * per, (rank + 1) * per


def slice_rows(arr: np.ndarray, rank: int, world: int) -> np.ndarray:
    lo, hi = slice_bounds(arr.shape[0], rank, world)
    return np.ascontiguousarray(np.asarray(arr)[lo:hi])


def assemble_rows(slices: Dict[int, np.ndarray],
                  world: int) -> np.ndarray:
    """Reassemble one full leaf from {shard_rank: slice}. Requires a
    COMPLETE slice set (every rank 0..world-1) — a missing slice is a
    hole in the optimizer state and must fail loudly, never be
    zero-filled."""
    missing = [r for r in range(world) if r not in slices]
    if missing:
        raise ValueError(
            f"incomplete sharded state: missing slice(s) for "
            f"rank(s) {missing} of world {world}")
    return np.concatenate([np.asarray(slices[r])
                           for r in range(world)], axis=0)


def reslice(full: np.ndarray, new_world: int) -> List[np.ndarray]:
    """Re-slice a fully-assembled leaf for a different world size —
    the elastic 3→2 shrink's resharding-on-resume primitive."""
    return [slice_rows(full, r, new_world) for r in range(new_world)]


# ----------------------------------------------------- compiled programs
def _constrain(tree, spec_fn):
    """with_sharding_constraint over every leaf (inside jit)."""
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.lax.with_sharding_constraint(a, spec_fn(a)), tree)


def build_zero1_step(net, manager, trace_key: str):
    """The ZeRO-1 donated-buffer train step for `net` over
    `manager`'s mesh (engine/mesh.py MeshManager).

    Program shape (all inside ONE jit):
      1. grads of the dp-sharded global batch (GSPMD inserts the grad
         all-reduce exactly as the unsharded program's mean does);
      2. constrain grads + params to the ZeRO shard layout — XLA
         lowers all-reduce + keep-my-slice into a reduce-scatter;
      3. shard-local unfused updater chain against the SHARDED
         optimizer state (donated in, sharded out — 1/n per-replica
         optimizer memory between steps);
      4. constrain updated params back to replicated — the all-gather
         that feeds the next forward.

    Signature and state contract match the net's own cached train step
    (`_build_train_step`), so StepProgram.run can route either."""
    import jax

    from deeplearning4j_tpu.engine.step_program import (
        make_loss_and_apply,
    )
    from deeplearning4j_tpu.nn.updater import schedule_lr

    conf = net.conf
    loss_for_grad, apply_updates = make_loss_and_apply(net, fused=False)

    def step_fn(params, upd_states, states, step, x, y, fmask, lmask,
                rng, lr_scale):
        net._jit_cache.record_trace(trace_key)
        (loss, new_states), grads = jax.value_and_grad(
            loss_for_grad, has_aux=True)(
                params, states, x, y, rng, fmask, lmask)
        grads = net._clip_grads(grads)
        grads = _constrain(grads, manager.leaf_sharding)
        pslice = _constrain(params, manager.leaf_sharding)
        lr = schedule_lr(conf, step) * lr_scale
        new_params, new_upd = apply_updates(
            pslice, upd_states, grads, lr, step)
        new_params = _constrain(new_params,
                                lambda a: manager.replicated())
        return new_params, new_upd, new_states, loss

    return jax.jit(step_fn, donate_argnums=(0, 1, 2))


def build_zero1_group(net, manager, k: int, trace_key: str):
    """The k-step `lax.scan` grouping of the ZeRO-1 step: one dispatch
    advances k steps on [k, ...]-stacked data, rng chain split exactly
    like k sequential steps, optimizer state carried SHARDED through
    the scan, per-inner-step losses surfaced for the guard — the
    zero1 twin of StepProgram._build_group."""
    import jax

    from deeplearning4j_tpu.engine.step_program import (
        make_loss_and_apply,
    )
    from deeplearning4j_tpu.nn.updater import schedule_lr

    conf = net.conf
    loss_for_grad, apply_updates = make_loss_and_apply(net, fused=False)

    def group_step_fn(params, upd_states, states, rng, step0,
                      xs, ys, fms, lms, lr_scale):
        net._jit_cache.record_trace(trace_key)

        def one(carry, sl):
            params, upd_states, states, rng, step = carry
            x, y, fm, lm = sl
            rng, sub = jax.random.split(rng)
            (loss, new_states), grads = jax.value_and_grad(
                loss_for_grad, has_aux=True)(
                    params, states, x, y, sub, fm, lm)
            grads = net._clip_grads(grads)
            grads = _constrain(grads, manager.leaf_sharding)
            pslice = _constrain(params, manager.leaf_sharding)
            lr = schedule_lr(conf, step) * lr_scale
            params, upd_states = apply_updates(
                pslice, upd_states, grads, lr, step)
            params = _constrain(params, lambda a: manager.replicated())
            return ((params, upd_states, new_states, rng, step + 1),
                    loss)

        (params, upd_states, states, rng, _), losses = jax.lax.scan(
            one, (params, upd_states, states, rng, step0),
            (xs, ys, fms, lms))
        return params, upd_states, states, rng, losses

    return jax.jit(group_step_fn, donate_argnums=(0, 1, 2, 3))


__all__ = ["ZERO1_AXIS", "zero1_leaf_sharded", "slice_bounds",
           "slice_rows", "assemble_rows", "reslice",
           "build_zero1_step", "build_zero1_group"]
