"""Sharded scale-out tests (the device-mesh + ZeRO-1 subsystem).

Parity pins (the acceptance bar): the ZeRO-1 mesh-sharded step —
optimizer state sharded over dp, reduce-scatter → shard-local update →
all-gather inside the ONE donated compiled program — agrees with the
unsharded StepProgram oracle within a few ulp (params AND updater
state; `_assert_trees_close` says why not bitwise) for all three fit
entry points (TrainingMaster, ParallelWrapper,
EarlyStoppingTrainer), while per-replica optimizer-state memory is
1/n, asserted from real array shard shapes. Checkpoint drills: sharded
per-rank slices round-trip, reshard on resume at a DIFFERENT world
size (the fast in-process twin of the elastic 3→2 shrink gang drill in
test_cluster.py), and the divergence quorum stays correct over sharded
copies — votes on the replicated main state, slices tied to the
elected digest via `main_state_sha256`, a forked rank's slice rejected
with fallback to an older fully-agreed step. The slice arithmetic
twins run on pure numpy (no jax) so the reshard math is tier-1-cheap.

Metric pins (conformance discipline): dl4j_mesh_world_size,
dl4j_mesh_reshard_total, dl4j_mesh_allgather_seconds.
"""

import os

import numpy as np
import pytest

pytestmark = pytest.mark.mesh

N_IN, HIDDEN, N_OUT, ROWS = 24, 24, 24, 24


def _net(seed=7, lr=1e-2):
    from deeplearning4j_tpu import (
        MultiLayerNetwork,
        NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.conf import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer

    conf = (NeuralNetConfiguration.Builder().seed(seed).updater("adam")
            .learning_rate(lr).activation("tanh").weight_init("xavier")
            .list()
            .layer(DenseLayer(n_out=HIDDEN))
            .layer(OutputLayer(n_out=N_OUT, loss="mcxent"))
            .set_input_type(InputType.feed_forward(N_IN))
            .build())
    return MultiLayerNetwork(conf).init()


def _batch(step):
    rng = np.random.default_rng(500 + step)
    x = rng.normal(size=(ROWS, N_IN)).astype(np.float32)
    y = np.eye(N_OUT, dtype=np.float32)[rng.integers(0, N_OUT, ROWS)]
    return x, y


def _leaves(tree):
    import jax

    return [np.asarray(leaf)
            for leaf in jax.tree_util.tree_leaves(tree)]


def _assert_trees_equal(tree_a, tree_b):
    la, lb = _leaves(tree_a), _leaves(tree_b)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(a, b)


def _assert_trees_close(tree_a, tree_b):
    """ZeRO-1 against the replicated program: equal within 4 ulp of
    each tensor's largest element. Not bitwise, because the two are
    different programs: where the replicated step all-reduces a
    gradient, the ZeRO-1 step reduce-scatters it, and XLA's
    partitioner (Shardy, under the installed jax 0.9.0) is free to add
    the per-device partial sums in another order. Each gradient
    element then rounds differently by an ulp of the sum, and the
    elementwise updater carries that into params and state. The error
    is absolute at the tensor's scale (an element that is small after
    cancellation still differs by an ulp of the larger terms), so the
    bound is on |a - b| against the largest |a|; measured 0.5-1.5 such
    ulp after 6 Adam steps. Bitwise equality stays where the two sides
    run the SAME program (k-group vs k=1 below, checkpoint resume, the
    decode engine vs sequential_decode)."""
    la, lb = _leaves(tree_a), _leaves(tree_b)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_allclose(
            a, b, rtol=0, atol=4 * np.spacing(np.abs(a).max()))


# ================================ host-side slice arithmetic (no jax)
def test_zero1_slice_arithmetic_no_jax():
    """The one slicing convention checkpoint save, resume resharding,
    and in-memory staging share — pure numpy, the fast twin of the
    elastic reshard drill's math."""
    from deeplearning4j_tpu.engine.sharding import (
        assemble_rows,
        reslice,
        slice_bounds,
        slice_rows,
        zero1_leaf_sharded,
    )

    assert zero1_leaf_sharded((24, 8), 8)
    assert zero1_leaf_sharded((24,), 6)
    assert not zero1_leaf_sharded((5, 8), 8)     # indivisible
    assert not zero1_leaf_sharded((), 8)         # scalar
    assert not zero1_leaf_sharded((24, 8), 1)    # no mesh

    full = np.arange(24 * 4, dtype=np.float32).reshape(24, 4)
    assert slice_bounds(24, 1, 3) == (8, 16)
    s3 = {r: slice_rows(full, r, 3) for r in range(3)}
    np.testing.assert_array_equal(assemble_rows(s3, 3), full)
    # reshard 3 -> 2: reassemble then re-slice, byte-preserving
    s2 = reslice(assemble_rows(s3, 3), 2)
    np.testing.assert_array_equal(np.concatenate(s2), full)
    with pytest.raises(ValueError):
        assemble_rows({0: s3[0], 2: s3[2]}, 3)   # hole in the state
    with pytest.raises(ValueError):
        slice_bounds(10, 0, 3)                   # indivisible


def test_mesh_manager_derive_and_policy():
    import jax

    from deeplearning4j_tpu.engine import MeshManager

    mgr = MeshManager()
    n = len(jax.devices())
    assert mgr.dp == n
    sig = mgr.world_signature()
    assert sig["devices"] == n and sig["processes"] == 1
    assert mgr.cache_token() == (1, n, n)
    # policy: divisible leading dims shard, the rest replicate
    import jax.numpy as jnp

    assert mgr.leaf_spec(jnp.zeros((3 * n, 4))) \
        != mgr.leaf_spec(jnp.zeros((3,)))
    assert not mgr.refresh()     # world unchanged: no rebuild


# ===================================== parity: the three entry points
def _tm_pair(n_steps=6, **zero1_kw):
    from deeplearning4j_tpu.parallel.training_master import (
        TrainingMaster,
    )

    net_r = _net()
    TrainingMaster(net_r).fit(lambda s: _batch(s), n_steps)
    net_z = _net()
    tm_z = TrainingMaster(net_z, sharding="zero1", **zero1_kw)
    tm_z.fit(lambda s: _batch(s), n_steps)
    return net_r, net_z, tm_z


def test_training_master_zero1_matches_unsharded_oracle():
    """THE acceptance pin: same dp-sharded batches, replicated vs
    ZeRO-1 sharded optimizer state — params AND updater state equal
    within a few ulp (`_assert_trees_close`), with per-replica
    optimizer memory 1/n from real shard shapes."""
    import jax

    net_r, net_z, tm_z = _tm_pair()
    _assert_trees_close(net_r.params, net_z.params)
    _assert_trees_close(net_r.updater_states, net_z.updater_states)
    np.testing.assert_array_equal(np.asarray(net_r._rng),
                                  np.asarray(net_z._rng))
    facts = tm_z._mesh_mgr.memory_facts(net_z.updater_states)
    n = len(jax.devices())
    assert facts["dp"] == n
    # every leaf of this net divides the dp extent: exactly 1/n
    assert facts["replica_fraction"] == pytest.approx(1.0 / n)
    # shard shapes say the same thing leaf by leaf
    for leaf in jax.tree_util.tree_leaves(net_z.updater_states):
        assert leaf.addressable_shards[0].data.shape[0] \
            == leaf.shape[0] // n
    assert tm_z.world_info()["sharding"] == "zero1"


def test_parallel_wrapper_zero1_matches_oracle():
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    batches = [_batch(s) for s in range(6)]
    net_r = _net()
    ParallelWrapper(net_r).fit(list(batches))
    net_z = _net()
    ParallelWrapper(net_z, sharding="zero1").fit(list(batches))
    _assert_trees_close(net_r.params, net_z.params)
    _assert_trees_close(net_r.updater_states, net_z.updater_states)


def test_early_stopping_zero1_matches_staged_oracle():
    """ES oracle follows the PR 9 precedent (`_tm_oracle`): device
    placement participates in compilation, so the zero1 trainer is
    compared against the UNSHARDED StepProgram staged on the same
    mesh with the same dp-sharded batches (`_assert_trees_close`)."""
    import jax

    from deeplearning4j_tpu.earlystopping.config import (
        EarlyStoppingConfiguration,
    )
    from deeplearning4j_tpu.earlystopping.saver import (
        InMemoryModelSaver,
    )
    from deeplearning4j_tpu.earlystopping.termination import (
        MaxEpochsTerminationCondition,
    )
    from deeplearning4j_tpu.earlystopping.trainer import (
        EarlyStoppingTrainer,
    )
    from deeplearning4j_tpu.engine import MeshManager, StepProgram

    # oracle: unsharded StepProgram, replicated-staged, dp batches
    net_o = _net()
    mgr = MeshManager()
    tmap = jax.tree_util.tree_map
    net_o.params = mgr.replicate_tree(tmap(np.asarray, net_o.params))
    net_o.updater_states = mgr.replicate_tree(
        tmap(np.asarray, net_o.updater_states))
    net_o.states = mgr.replicate_tree(tmap(np.asarray, net_o.states))
    prog = StepProgram(net_o)
    for _ in range(2):                      # 2 epochs x 3 batches
        for s in range(3):
            x, y = _batch(s)
            prog.run(jax.device_put(x, mgr.batch_sharding()),
                     jax.device_put(y, mgr.batch_sharding()))

    cfg = (EarlyStoppingConfiguration.Builder()
           .epoch_termination_conditions(
               MaxEpochsTerminationCondition(2))
           .model_saver(InMemoryModelSaver())
           .evaluate_every_n_epochs(1).build())
    net_z = _net()
    EarlyStoppingTrainer(cfg, net_z, [_batch(s) for s in range(3)],
                         sharding="zero1").fit()
    _assert_trees_close(net_o.params, net_z.params)
    _assert_trees_close(net_o.updater_states, net_z.updater_states)


def test_zero1_k_group_matches_k1():
    """steps_per_dispatch=k routes through the zero1 lax.scan group:
    byte-identical to k=1 zero1 dispatches (same rng chain)."""
    from deeplearning4j_tpu.parallel.training_master import (
        TrainingMaster,
    )

    net_1 = _net()
    TrainingMaster(net_1, sharding="zero1").fit(
        lambda s: _batch(s), 8)
    net_k = _net()
    TrainingMaster(net_k, sharding="zero1",
                   steps_per_dispatch=4).fit(lambda s: _batch(s), 8)
    _assert_trees_equal(net_1.params, net_k.params)
    _assert_trees_equal(net_1.updater_states, net_k.updater_states)
    np.testing.assert_array_equal(np.asarray(net_1._rng),
                                  np.asarray(net_k._rng))


def test_zero1_validations():
    from deeplearning4j_tpu.parallel.training_master import (
        TrainingMaster,
    )
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    with pytest.raises(ValueError, match="averaging_frequency"):
        TrainingMaster(_net(), sharding="zero1",
                       averaging_frequency=2)
    with pytest.raises(ValueError, match="npz"):
        TrainingMaster(_net(), sharding="zero1",
                       checkpoint_format="orbax")
    with pytest.raises(ValueError, match="sharding"):
        TrainingMaster(_net(), sharding="zero2")
    with pytest.raises(NotImplementedError, match="tp"):
        ParallelWrapper(_net(), workers=4, tp=2, sharding="zero1")


# ============================================== sharded checkpointing
def test_zero1_checkpoint_roundtrip_and_retention(tmp_path):
    """Sharded save: main npz (quorum-votable replicated state) +
    `.updshard.npz` sidecar; resume into a fresh zero1 master is
    byte-identical to the uninterrupted run; retention prunes the
    sidecar with its step."""
    from deeplearning4j_tpu.parallel.training_master import (
        TrainingMaster,
    )

    d = str(tmp_path / "ckpt")
    net = _net()
    TrainingMaster(net, checkpoint_dir=d, checkpoint_every=2,
                   sharding="zero1").fit(lambda s: _batch(s), 6)
    files = sorted(os.listdir(d))
    assert "step-00000006.npz" in files
    assert "step-00000006.updshard.npz" in files
    # main payload excludes the sharded leaves but records the layout
    with np.load(os.path.join(d, "step-00000006.npz")) as z:
        assert int(z["shard_world"]) == 1
        assert len(np.asarray(z["upd_sharded_idx"]).reshape(-1)) > 0

    net_resume = _net()
    TrainingMaster(net_resume, checkpoint_dir=d, checkpoint_every=2,
                   sharding="zero1").fit(lambda s: _batch(s), 8)
    net_oracle = _net()
    TrainingMaster(net_oracle, sharding="zero1").fit(
        lambda s: _batch(s), 8)
    _assert_trees_equal(net_resume.params, net_oracle.params)
    _assert_trees_equal(net_resume.updater_states,
                        net_oracle.updater_states)

    # retention: pruning a step takes its slice sidecar with it
    from deeplearning4j_tpu.resilience import checkpoint_integrity as ci

    ci.apply_retention(d, keep_last=1)
    left = sorted(os.listdir(d))
    assert "step-00000002.npz" not in left
    assert "step-00000002.updshard.npz" not in left
    assert "step-00000008.updshard.npz" in left


def _write_sharded_rank_ckpt(base, step, payload, slices_full, world,
                             extra_payload=None):
    """Craft a world-`world` sharded per-rank checkpoint set: every
    rank dir gets the identical main npz (replicated portion) and its
    own slice sidecar — the same layout TrainingMaster writes, built
    by hand so single-process tests can simulate any world size."""
    from deeplearning4j_tpu.resilience import checkpoint_integrity as ci
    from deeplearning4j_tpu.engine.sharding import slice_rows

    fn = ci.step_filename(step)
    side_fn = ci.shard_sidecar_filename(step)
    sharded_idx = sorted(slices_full)
    main = dict(payload)
    main["upd_sharded_idx"] = np.asarray(sharded_idx, np.int64)
    main["shard_world"] = np.asarray(world)
    if extra_payload:
        main.update(extra_payload)
    for r in range(world):
        d = ci.rank_checkpoint_dir(base, r)
        os.makedirs(d, exist_ok=True)
        p = os.path.join(d, fn)
        with open(p, "wb") as f:
            np.savez(f, **main)
        state_sha = ci.compute_state_digest(p)
        ci.record_checksum(d, fn, ci.sha256_file(p),
                           os.path.getsize(p),
                           extra={"step": step,
                                  "state_sha256": state_sha})
        sp = os.path.join(d, side_fn)
        with open(sp, "wb") as f:
            np.savez(f, shard_rank=np.asarray(r),
                     shard_world=np.asarray(world),
                     **{f"slice:{i}": slice_rows(a, r, world)
                        for i, a in slices_full.items()})
        ci.record_checksum(d, side_fn, ci.sha256_file(sp),
                           os.path.getsize(sp),
                           extra={"step": step, "shard_rank": r,
                                  "shard_world": world,
                                  "main_state_sha256": state_sha})
    return state_sha


def test_zero1_reshard_on_resume_from_larger_world(tmp_path):
    """The in-process twin of the elastic 3→2 shrink: a checkpoint
    whose optimizer slices were written by THREE ranks is resumed by a
    single-process zero1 master — slices reassembled across rank dirs,
    re-sliced for the live mesh, `dl4j_mesh_reshard_total` counted,
    and the continued run byte-identical to the uninterrupted one."""
    import jax

    from deeplearning4j_tpu.observability import get_registry
    from deeplearning4j_tpu.parallel.training_master import (
        TrainingMaster,
    )
    from deeplearning4j_tpu.resilience import checkpoint_integrity as ci

    base = str(tmp_path / "ckpt")
    # phase A: single-process zero1 run checkpoints step 4 (per-rank
    # layout: everything lands in rank-0)
    net_a = _net()
    tm_a = TrainingMaster(net_a, checkpoint_dir=base,
                          checkpoint_every=4, per_rank_checkpoints=True,
                          sharding="zero1")
    tm_a.fit(lambda s: _batch(s), 4)
    d0 = ci.rank_checkpoint_dir(base, 0)
    with np.load(os.path.join(d0, ci.step_filename(4))) as z:
        payload = {k: z[k] for k in z.files
                   if k not in ("upd_sharded_idx", "shard_world")}
        sharded_idx = [int(i) for i in
                       np.asarray(z["upd_sharded_idx"]).reshape(-1)]
    with np.load(os.path.join(
            d0, ci.shard_sidecar_filename(4))) as z:
        slices_full = {i: np.asarray(z[f"slice:{i}"])
                       for i in sharded_idx}

    # phase B: REWRITE the step-4 checkpoint as if THREE ranks had
    # written it (world 3 slices of the same optimizer state)
    import shutil

    shutil.rmtree(base)
    _write_sharded_rank_ckpt(base, 4, payload, slices_full, world=3)
    meta = {"step": 4, "iteration": 4, "epoch": 0}
    ci.atomic_write_json(os.path.join(
        ci.rank_checkpoint_dir(base, 0), "latest.json"), meta)

    reg = get_registry()
    reshards0 = reg.counter_value("dl4j_mesh_reshard_total")
    net_b = _net()
    tm_b = TrainingMaster(net_b, checkpoint_dir=base,
                          checkpoint_every=4, per_rank_checkpoints=True,
                          sharding="zero1")
    tm_b.fit(lambda s: _batch(s), 8)
    assert reg.counter_value("dl4j_mesh_reshard_total") \
        == reshards0 + 1

    net_oracle = _net()
    TrainingMaster(net_oracle, sharding="zero1").fit(
        lambda s: _batch(s), 8)
    _assert_trees_equal(net_b.params, net_oracle.params)
    _assert_trees_equal(net_b.updater_states,
                        net_oracle.updater_states)
    n = len(jax.devices())
    for leaf in jax.tree_util.tree_leaves(net_b.updater_states):
        assert leaf.addressable_shards[0].data.shape[0] \
            == leaf.shape[0] // n


# ========================== divergence quorum over sharded copies
def _toy_sharded_ckpt(base, step, seed, world=3):
    rng = np.random.default_rng(seed)
    payload = {"params:0": rng.normal(size=(6, 4)).astype(np.float32),
               "states:0": np.zeros((2,), np.float32),
               "rng": np.arange(2, dtype=np.uint32),
               "step": np.asarray(step),
               "iteration": np.asarray(step),
               "epoch": np.asarray(0),
               "upd:1": np.ones((3,), np.float32)}
    slices_full = {0: rng.normal(size=(12, 4)).astype(np.float32)}
    return _write_sharded_rank_ckpt(base, step, payload, slices_full,
                                    world)


def test_sharded_quorum_votes_replicated_state_not_slices(tmp_path):
    """Legitimately different per-rank slices must NOT read as
    divergence: the quorum votes on the replicated main state (its
    digest is identical across ranks), and a perturbed minority main
    copy is out-voted and healed while every rank's own slice stays
    in place and trusted (its recorded main digest is the elected
    one)."""
    from deeplearning4j_tpu.resilience import checkpoint_integrity as ci

    base = str(tmp_path)
    _toy_sharded_ckpt(base, 2, seed=1)
    _toy_sharded_ckpt(base, 4, seed=2)

    # fork rank 1's newest MAIN copy, self-consistent manifest
    d1 = ci.rank_checkpoint_dir(base, 1)
    fn = ci.step_filename(4)
    p1 = os.path.join(d1, fn)
    with np.load(p1) as z:
        forged = {k: np.asarray(z[k]) for k in z.files}
    forged["params:0"] = forged["params:0"] + 1.0
    with open(p1, "wb") as f:
        np.savez(f, **forged)
    ci.record_checksum(d1, fn, ci.sha256_file(p1),
                       os.path.getsize(p1),
                       extra={"step": 4,
                              "state_sha256":
                                  ci.compute_state_digest(p1)})

    report = ci.sharded_quorum_resume_step(base, nprocs=3)
    assert report is not None and report["step"] == 4
    assert report["shard_world"] == 3
    assert report["healed"] == [1]
    assert sorted(report["slices"]) == [0, 1, 2]
    # the healed rank's main copy now matches the quorum digest
    assert ci.state_digest(d1, fn) == report["digest"]


def test_sharded_quorum_rejects_forked_slice_and_falls_back(tmp_path):
    """A rank whose slice was recorded against a FORKED main digest
    (a replica that trained divergently and saved a self-consistent
    fork) is unreconstructable — the elected step is rejected and the
    quorum falls back to the older fully-agreed step."""
    from deeplearning4j_tpu.resilience import checkpoint_integrity as ci

    base = str(tmp_path)
    _toy_sharded_ckpt(base, 2, seed=1)
    _toy_sharded_ckpt(base, 4, seed=2)

    d1 = ci.rank_checkpoint_dir(base, 1)
    side_fn = ci.shard_sidecar_filename(4)
    # rewrite rank 1's sidecar manifest entry as if it belonged to a
    # forked main state (wrong main_state_sha256)
    entry = ci.read_manifest(d1)[side_fn]
    ci.record_checksum(d1, side_fn, entry["sha256"], entry["size"],
                       extra={"step": 4, "shard_rank": 1,
                              "shard_world": 3,
                              "main_state_sha256": "f" * 64})
    report = ci.sharded_quorum_resume_step(base, nprocs=3)
    assert report is not None
    assert report["step"] == 2        # fell back past the bad slice

    # a MISSING sidecar falls back the same way
    _toy_sharded_ckpt(base, 6, seed=3)
    os.remove(os.path.join(ci.rank_checkpoint_dir(base, 2),
                           ci.shard_sidecar_filename(6)))
    report2 = ci.sharded_quorum_resume_step(base, nprocs=3)
    assert report2 is not None and report2["step"] == 2


def test_sharded_quorum_scans_save_world_after_shrink(tmp_path):
    """After a 3→2 shrink the surviving gang is 2 ranks, but the
    newest checkpoint was written by 3 — the sharded quorum votes over
    the SAVE-time world read from the copies, so rank 2's dir still
    votes and still contributes its slice."""
    from deeplearning4j_tpu.resilience import checkpoint_integrity as ci

    base = str(tmp_path)
    _toy_sharded_ckpt(base, 4, seed=2, world=3)
    report = ci.sharded_quorum_resume_step(base, nprocs=2)
    assert report is not None and report["step"] == 4
    assert report["shard_world"] == 3
    assert sorted(report["slices"]) == [0, 1, 2]


# ====================================== engine-owned trainer programs
def test_trainer_compilation_is_engine_owned():
    """LocalStepTrainer / StaleGradientTrainer compile through
    StepProgram.trainer_program: the program lands in the net's
    JitCache under an engine key with the precision policy registered
    — one compilation owner (forensics + program lint + mesh arc)."""
    from deeplearning4j_tpu.engine import StepProgram
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.parallel.wrapper import (
        LocalStepTrainer,
        StaleGradientTrainer,
    )

    net = _net()
    prog = StepProgram(net)
    built = []

    def build(tk):
        built.append(tk)
        return lambda: None

    fn = prog.trainer_program("engine_local_sgd", build, 4, False,
                              False)
    fn2 = prog.trainer_program(
        "engine_local_sgd",
        lambda tk: (_ for _ in ()).throw(AssertionError("rebuilt")),
        4, False, False)
    assert fn is fn2
    assert built and "engine_local_sgd" in built[0]
    key = ("engine_local_sgd", 4, False, False, prog._frozen_sig())
    assert net._jit_cache.policy(key) == prog.precision_policy

    mesh = make_mesh(dp=1)
    assert isinstance(LocalStepTrainer(net, mesh)._program,
                      StepProgram)
    assert isinstance(StaleGradientTrainer(net, mesh)._program,
                      StepProgram)


# =============================================== telemetry + analysis
def test_mesh_metrics_registered_and_emitted():
    """The three mesh metrics are registered, and every emission site
    fires: dl4j_mesh_world_size at derive, dl4j_mesh_reshard_total at
    reshard_tree, dl4j_mesh_allgather_seconds at gather_tree."""
    import jax

    from deeplearning4j_tpu.engine import MeshManager
    from deeplearning4j_tpu.observability import get_registry
    from deeplearning4j_tpu.observability.metrics import (
        REGISTERED_METRICS,
    )

    for name in ("dl4j_mesh_world_size", "dl4j_mesh_reshard_total",
                 "dl4j_mesh_allgather_seconds"):
        assert name in REGISTERED_METRICS

    reg = get_registry()
    devs = list(jax.devices())
    mgr4 = MeshManager(devices=devs[:4])
    assert reg.gauge_value("dl4j_mesh_world_size") == 1
    tree = mgr4.shard_tree({"w": np.ones((8, 2), np.float32)})
    assert tree["w"].addressable_shards[0].data.shape == (2, 2)

    ag0 = reg.snapshot()["histograms"].get(
        "dl4j_mesh_allgather_seconds", {"count": 0})["count"]
    full = mgr4.gather_tree(tree)
    np.testing.assert_array_equal(full["w"],
                                  np.ones((8, 2), np.float32))
    assert reg.snapshot()["histograms"][
        "dl4j_mesh_allgather_seconds"]["count"] == ag0 + 1

    reshards0 = reg.counter_value("dl4j_mesh_reshard_total")
    mgr2 = MeshManager(devices=devs[:2])
    tree2 = mgr2.reshard_tree(tree)
    assert reg.counter_value("dl4j_mesh_reshard_total") \
        == reshards0 + 1
    assert tree2["w"].addressable_shards[0].data.shape == (4, 2)
    np.testing.assert_array_equal(np.asarray(tree2["w"]),
                                  np.ones((8, 2), np.float32))


def test_dashboard_mesh_line():
    """telemetry_lines renders the mesh status line from the ONE
    metrics substrate (pinned like the cluster/serving lines)."""
    from deeplearning4j_tpu.observability import get_registry
    from deeplearning4j_tpu.observability import metrics as obs
    from deeplearning4j_tpu.stats import telemetry_lines

    obs.set_gauge("dl4j_mesh_world_size", 3)
    obs.count("dl4j_mesh_reshard_total")
    lines = telemetry_lines(get_registry())
    mesh_lines = [ln for ln in lines if ln.startswith("mesh — ")]
    assert mesh_lines and "world 3" in mesh_lines[0]
    assert "reshards" in mesh_lines[0]


def test_zero1_program_lint_clean():
    """The mesh-registered zero1 program passes the compiled-program
    lint — including `prog-unsharded-optimizer-state`, which verifies
    the lowered module really shards + donates the optimizer state."""
    from deeplearning4j_tpu.analysis import program_lint, programs
    from deeplearning4j_tpu.analysis.program_lint import (
        REGISTERED_PROGRAM_RULES,
    )

    assert "prog-unsharded-optimizer-state" in REGISTERED_PROGRAM_RULES
    records = programs._mesh_records()
    assert [r.name for r in records] == ["engine_zero1"]
    assert records[0].sharded_argnums == (1,)
    finds = program_lint.run(records)
    assert finds == [], [f.render() for f in finds]


def test_run_batch_indivisible_batch_still_trains():
    """A batch that does not divide the dp extent replicates instead
    of sharding — correctness over partitioning."""
    import jax

    from deeplearning4j_tpu.engine import MeshManager, StepProgram

    net = _net()
    mgr = MeshManager()
    tmap = jax.tree_util.tree_map
    net.params = mgr.replicate_tree(tmap(np.asarray, net.params))
    net.updater_states = mgr.shard_tree(
        tmap(np.asarray, net.updater_states))
    net.states = mgr.replicate_tree(tmap(np.asarray, net.states))
    prog = StepProgram(net).attach_mesh(mgr)
    x, y = _batch(0)
    loss = prog.run_batch((x[:5], y[:5]))    # 5 % 8 != 0
    assert np.isfinite(float(loss))
    assert net.iteration == 1
