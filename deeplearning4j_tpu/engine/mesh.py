"""MeshManager: the live device mesh every sharded program compiles on.

The engine's view of "where am I running": a MeshManager derives a
1-axis data-parallel mesh (axis ``dp``, the parallel/mesh.py axis
convention) from the LIVE world — `jax.devices()` under the current
`jax.distributed` membership — and owns every placement decision the
ZeRO-1 subsystem (engine/sharding.py) makes against it:

  - PartitionSpec policy: batch dims shard over ``dp``; optimizer-state
    leaves shard their leading dim over ``dp`` when divisible
    (`zero1_leaf_sharded`), everything else replicates;
  - staging: host→device placement that works identically in one
    process (device_put) and across a multi-host gang
    (`jax.make_array_from_process_local_data` with this process's
    contiguous slice);
  - elasticity: `refresh()` re-derives the mesh when the live world
    changed (the PR 10 shrink-to-fit relaunch) and `reshard_tree`
    re-places state onto the new mesh — the in-memory half of the
    resharding-on-resume path (the on-disk half re-slices checkpoint
    slices, resilience/checkpoint_integrity.py);
  - telemetry: `dl4j_mesh_world_size` (gauge, set at every derive),
    `dl4j_mesh_reshard_total` (counter, one per state reshard), and
    `dl4j_mesh_allgather_seconds` (observed around every host gather
    of sharded state — the checkpoint-save all-gather cost arXiv
    2004.13336 trades against the per-step memory win).

Construction is cheap and jax-lazy only at the module level; the
constructor touches jax (it derives the mesh immediately).
"""

from __future__ import annotations

import time
from typing import Any, Optional

import numpy as np

from deeplearning4j_tpu.engine.sharding import (
    ZERO1_AXIS,
    slice_bounds,
    zero1_leaf_sharded,
)
from deeplearning4j_tpu.observability import metrics as _obs


class MeshManager:
    """One live 1-axis dp mesh + the ZeRO-1 placement policy over it.

    `devices=None` (production) derives from the full live device set
    and re-derives on `refresh()`; an explicit device list pins the
    mesh (tests shrink a manager from 4 to 2 devices this way, and
    ParallelWrapper hands in its own mesh's dp submesh)."""

    def __init__(self, devices=None, mesh=None):
        import jax

        self._explicit_devices = (None if devices is None
                                  else list(devices))
        self._explicit_mesh = mesh
        self.mesh = None
        self.reshards = 0
        self._world: dict = {}
        self.derive()

    # ------------------------------------------------------- derivation
    def derive(self) -> "MeshManager":
        """(Re)build the mesh from the live world: every addressable +
        remote device under the current `jax.distributed` membership,
        one ``dp`` axis. The world signature (processes, devices, dp)
        is what `refresh()` compares and what checkpoints record."""
        import jax
        from jax.sharding import Mesh

        if self._explicit_mesh is not None:
            self.mesh = self._explicit_mesh
            dp = int(self.mesh.shape.get(ZERO1_AXIS, 1))
        else:
            devs = (list(jax.devices())
                    if self._explicit_devices is None
                    else list(self._explicit_devices))
            self.mesh = Mesh(np.array(devs), (ZERO1_AXIS,))
            dp = len(devs)
        self._world = {
            "processes": int(jax.process_count()),
            "devices": len(jax.devices()),
            "dp": dp,
        }
        _obs.set_gauge("dl4j_mesh_world_size", self._world["processes"])
        return self

    @property
    def dp(self) -> int:
        return self._world["dp"]

    def world_signature(self) -> dict:
        return dict(self._world)

    def cache_token(self) -> tuple:
        """Hashable identity of the derived mesh for compiled-program
        cache keys — a relaunch/reshard at a different world must
        compile a fresh program, never reuse a closure over the old
        mesh."""
        return (self._world["processes"], self._world["devices"],
                self._world["dp"])

    def refresh(self) -> bool:
        """Re-derive if the live world changed (elastic shrink/grow).
        Returns True when the mesh was rebuilt — callers then
        `reshard_tree` any state placed on the old mesh."""
        import jax

        if self._explicit_mesh is not None:
            return False
        if self._explicit_devices is None \
                and len(jax.devices()) == self._world["devices"] \
                and int(jax.process_count()) == self._world["processes"]:
            return False
        before = self.cache_token()
        self.derive()
        return self.cache_token() != before

    # ---------------------------------------------------------- policy
    def replicated(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P())

    def leaf_spec(self, leaf):
        """PartitionSpec of one param/optimizer leaf under the ZeRO-1
        rule: leading dim over dp when divisible, else replicated."""
        from jax.sharding import PartitionSpec as P

        shape = getattr(leaf, "shape", ())
        if zero1_leaf_sharded(shape, self.dp):
            return P(ZERO1_AXIS)
        return P()

    def leaf_sharding(self, leaf):
        from jax.sharding import NamedSharding

        return NamedSharding(self.mesh, self.leaf_spec(leaf))

    def batch_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P(ZERO1_AXIS))

    def shard_layout(self, tree) -> list:
        """[bool] per flattened leaf: sharded under the current dp?
        The checkpoint writer records exactly this layout."""
        import jax

        return [zero1_leaf_sharded(getattr(a, "shape", ()), self.dp)
                for a in jax.tree_util.tree_leaves(tree)]

    # --------------------------------------------------------- staging
    def _put(self, host_full, sharding, sharded: bool):
        """One leaf host→device: single-process device_put, multi-host
        `make_array_from_process_local_data` with this process's
        contiguous slice (slice_bounds — the same convention the
        checkpoint slices use)."""
        import jax

        a = np.asarray(host_full)
        if self._world["processes"] <= 1:
            return jax.device_put(a, sharding)
        if sharded:
            lo, hi = slice_bounds(a.shape[0], jax.process_index(),
                                  self._world["processes"])
            local = a[lo:hi]
        else:
            local = a
        return jax.make_array_from_process_local_data(sharding, local)

    def shard_tree(self, tree) -> Any:
        """Place a host pytree with the ZeRO-1 rule (optimizer-state
        staging: divisible leaves sharded, the rest replicated)."""
        import jax

        return jax.tree_util.tree_map(
            lambda a: self._put(
                a, self.leaf_sharding(a),
                zero1_leaf_sharded(np.shape(a), self.dp)),
            tree)

    def replicate_tree(self, tree) -> Any:
        import jax

        rep = self.replicated()
        return jax.tree_util.tree_map(
            lambda a: self._put(a, rep, False), tree)

    def gather_tree(self, tree) -> Any:
        """Host pytree of FULL (unsharded) arrays — the checkpoint
        writer's all-gather of sharded optimizer state. In a gang this
        is collective-free for the caller (each process fetches the
        full logical array; jax gathers remote shards). Timed into
        `dl4j_mesh_allgather_seconds`."""
        import jax

        t0 = time.perf_counter()

        def fetch(a):
            if hasattr(a, "is_fully_addressable") \
                    and not a.is_fully_addressable:
                from jax.experimental import multihost_utils

                return np.asarray(
                    multihost_utils.process_allgather(a, tiled=True))
            return np.asarray(a)

        out = jax.tree_util.tree_map(fetch, tree)
        _obs.observe("dl4j_mesh_allgather_seconds",
                     time.perf_counter() - t0)
        return out

    def reshard_tree(self, tree) -> Any:
        """Re-place a device pytree onto the CURRENT mesh (after a
        `refresh()` that re-derived it, or to move assembled
        checkpoint state onto a different world) — the in-memory
        resharding half of the elastic shrink. Counts
        `dl4j_mesh_reshard_total`."""
        self.reshards += 1
        _obs.count("dl4j_mesh_reshard_total")
        return self.shard_tree(self.gather_tree(tree))

    # ------------------------------------------------------------ facts
    def memory_facts(self, tree) -> dict:
        """Per-replica optimizer-state memory under the current
        placement: full bytes, this-replica bytes (shard-aware), and
        the ratio — the measurable 1/n claim (asserted from array
        shard shapes in tests)."""
        import jax

        full = 0
        local = 0
        for a in jax.tree_util.tree_leaves(tree):
            size = int(np.prod(a.shape)) if a.shape else 1
            item = np.dtype(a.dtype).itemsize
            full += size * item
            if hasattr(a, "addressable_shards") and a.shape:
                sh = a.addressable_shards[0].data.shape
                local += (int(np.prod(sh)) if sh else 1) * item
            else:
                local += size * item
        return {"full_bytes": full, "replica_bytes": local,
                "replica_fraction": (local / full) if full else 1.0,
                "dp": self.dp}


__all__ = ["MeshManager"]
