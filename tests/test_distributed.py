"""Multi-host training tests: 2-process x 4-device CPU SPMD via
subprocess (the reference's local-mode Spark simulation technique,
BaseSparkTest.java:89 "local[N]"), with the single-process serial fit
as oracle (TestCompareParameterAveragingSparkVsSingleMachine role) and
a kill-between-steps resume test (SURVEY §5.3)."""

import os
import subprocess
import sys

import numpy as np
import pytest

HELPER = os.path.join(os.path.dirname(__file__), "helpers",
                      "distributed_worker.py")
REPO = os.path.join(os.path.dirname(__file__), "..")


def _worker_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    # fresh world per subprocess (the parent's jax state is irrelevant)
    env.pop("JAX_COORDINATOR_ADDRESS", None)
    return env

def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _launch(nprocs, steps, out_dir, extra=(), env_extra=None):
    port = _free_port()
    env = _worker_env()
    env.update(env_extra or {})
    procs = []
    for pid in range(nprocs):
        procs.append(subprocess.Popen(
            [sys.executable, HELPER, str(pid), str(nprocs), str(port),
             str(steps), out_dir, *extra],
            env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=300)
        outs.append(out)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{out[-3000:]}"
    return outs


def _oracle_params(steps):
    """Single-process serial training on the same global batches."""
    sys.path.insert(0, os.path.join(os.path.dirname(HELPER)))
    import distributed_worker as dw

    net = dw.build_net()
    for s in range(steps):
        net.fit([dw.global_batch(s)])
    import jax

    return [np.asarray(l) for l in jax.tree_util.tree_leaves(net.params)]


def test_two_process_training_matches_serial(tmp_path_factory):
    out = str(tmp_path_factory.mktemp("dist"))
    steps = 6
    _launch(2, steps, out)
    data = np.load(os.path.join(out, "final_params.npz"))
    got = [data[k] for k in data.files if k.startswith("arr_")]
    assert int(data["iteration"]) == steps
    expect = _oracle_params(steps)
    assert len(got) == len(expect)
    for g, e in zip(got, expect):
        np.testing.assert_allclose(g, e, rtol=1e-4, atol=1e-5)


def test_kill_and_resume_matches_uninterrupted(tmp_path_factory):
    """Kill the job between steps; relaunching resumes from the last
    checkpoint and the final params match an uninterrupted run."""
    steps = 6
    # uninterrupted reference run (2-proc, with checkpoints enabled)
    ref_dir = str(tmp_path_factory.mktemp("ref"))
    _launch(2, steps, ref_dir, ("--checkpoint-every", "2"))
    ref = np.load(os.path.join(ref_dir, "final_params.npz"))

    # interrupted run: stop ("kill") after 4 steps, checkpoint every 2
    out = str(tmp_path_factory.mktemp("resume"))
    _launch(2, steps, out,
            ("--checkpoint-every", "2", "--stop-after", "4"))
    assert not os.path.exists(os.path.join(out, "final_params.npz"))
    ckpts = sorted(os.listdir(os.path.join(out, "ckpt")))
    assert "step-00000004.npz" in ckpts

    # relaunch: must resume from step 4, not restart
    outs = _launch(2, steps, out, ("--checkpoint-every", "2"))
    data = np.load(os.path.join(out, "final_params.npz"))
    got = [data[k] for k in data.files if k.startswith("arr_")]
    refp = [ref[k] for k in ref.files if k.startswith("arr_")]
    for g, e in zip(got, refp):
        np.testing.assert_allclose(g, e, rtol=1e-4, atol=1e-5)
    assert int(data["iteration"]) == steps


def test_single_process_training_master(tmp_path, rng):
    """TrainingMaster degrades to single-process (no jax.distributed)."""
    sys.path.insert(0, os.path.join(os.path.dirname(HELPER)))
    import distributed_worker as dw

    from deeplearning4j_tpu.parallel.training_master import TrainingMaster

    net = dw.build_net()
    tm = TrainingMaster(net, checkpoint_dir=str(tmp_path / "ck"),
                        checkpoint_every=2)
    tm.fit(lambda s: dw.global_batch(s), 4)
    assert tm.list_checkpoints() == [2, 4]
    assert net.iteration == 4

    # resume continues from step 4
    net2 = dw.build_net()
    tm2 = TrainingMaster(net2, checkpoint_dir=str(tmp_path / "ck"),
                         checkpoint_every=2)
    tm2.fit(lambda s: dw.global_batch(s), 6)
    assert net2.iteration == 6
    p1 = [np.asarray(l) for l in
          __import__("jax").tree_util.tree_leaves(net.params)]
    # independently train net 6 steps for comparison
    net3 = dw.build_net()
    TrainingMaster(net3).fit(lambda s: dw.global_batch(s), 6)
    p2 = [np.asarray(l) for l in
          __import__("jax").tree_util.tree_leaves(net2.params)]
    p3 = [np.asarray(l) for l in
          __import__("jax").tree_util.tree_leaves(net3.params)]
    for a, b in zip(p2, p3):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_training_master_distributed_evaluate(rng):
    """Global confusion counts via in-program dp reduction match a
    host-side evaluation of the same data."""
    sys.path.insert(0, os.path.join(os.path.dirname(HELPER)))
    import distributed_worker as dw

    from deeplearning4j_tpu.eval import Evaluation
    from deeplearning4j_tpu.parallel.training_master import TrainingMaster

    net = dw.build_net()
    tm = TrainingMaster(net)
    tm.fit(lambda s: dw.global_batch(s), 3)
    ev = tm.evaluate(lambda s: dw.global_batch(100 + s), 2)

    expect = Evaluation()
    for s in range(2):
        x, y = dw.global_batch(100 + s)
        expect.eval(y, np.asarray(net.output(x)))
    np.testing.assert_array_equal(ev.confusion.matrix,
                                  expect.confusion.matrix)
    assert 0.0 <= ev.accuracy() <= 1.0


def test_training_master_masked_evaluate(rng):
    """batch_fn may return the standard (x, y, fm, lm) tuple; the label
    mask (index 3, per the container convention) drops padded rows from
    the global confusion counts (round-3 advisor)."""
    sys.path.insert(0, os.path.join(os.path.dirname(HELPER)))
    import distributed_worker as dw

    from deeplearning4j_tpu.eval import Evaluation
    from deeplearning4j_tpu.parallel.training_master import TrainingMaster

    net = dw.build_net()
    tm = TrainingMaster(net)
    tm.fit(lambda s: dw.global_batch(s), 2)

    masks = {}

    def batch_fn(s):
        x, y = dw.global_batch(200 + s)
        lm = (rng.random(y.shape[0]) > 0.4).astype(np.float32)
        masks[s] = (x, y, lm)
        return x, y, None, lm

    ev = tm.evaluate(batch_fn, 2)
    expect = Evaluation()
    for s in range(2):
        x, y, lm = masks[s]
        expect.eval(y, np.asarray(net.output(x)), mask=lm)
    np.testing.assert_array_equal(ev.confusion.matrix,
                                  expect.confusion.matrix)
    assert ev.confusion.total() < sum(m[1].shape[0] for m in masks.values())


def test_evaluation_merge():
    from deeplearning4j_tpu.eval import Evaluation

    a = Evaluation(3)
    b = Evaluation(3)
    y = np.eye(3, dtype=np.float32)
    a.eval(y, y)                      # 3 correct
    p = np.roll(y, 1, axis=1)
    b.eval(y, p)                      # 3 wrong
    a.merge(b)
    assert a.confusion.total() == 6
    assert a.accuracy() == 0.5


def test_training_stats_collection(tmp_path):
    sys.path.insert(0, os.path.join(os.path.dirname(HELPER)))
    import distributed_worker as dw

    from deeplearning4j_tpu.parallel.training_master import TrainingMaster

    net = dw.build_net()
    tm = TrainingMaster(net)
    tm.fit(lambda s: dw.global_batch(s), 3, collect_training_stats=True)
    stats = tm.training_stats()
    assert len(stats["steps"]) == 3
    assert stats["summary"]["fit_ms"] > 0
    out = str(tmp_path / "timeline.html")
    tm.export_stats_html(out)
    content = open(out).read()
    assert "TrainingMaster timeline" in content and "<table" in content


def test_training_master_local_sgd_matches_parallel_wrapper(rng):
    """TrainingMaster(averaging_frequency=k) == ParallelWrapper(k) on
    the same mesh + data (both drive LocalStepTrainer)."""
    sys.path.insert(0, os.path.join(os.path.dirname(HELPER)))
    import distributed_worker as dw
    import jax

    from deeplearning4j_tpu.parallel import ParallelWrapper, make_mesh
    from deeplearning4j_tpu.parallel.training_master import TrainingMaster

    ds = jax.devices("cpu")[:4]
    mesh1 = make_mesh(dp=4, devices=ds)
    tm_net = dw.build_net()
    tm = TrainingMaster(tm_net, mesh=mesh1, averaging_frequency=2)
    tm.fit(lambda s: dw.global_batch(s), 4)

    mesh2 = make_mesh(dp=4, devices=ds)
    pw_net = dw.build_net()
    batches = [dw.global_batch(s) for s in range(4)]
    ParallelWrapper(pw_net, mesh=mesh2, averaging_frequency=2).fit(batches)

    for a, b in zip(jax.tree_util.tree_leaves(tm_net.params),
                    jax.tree_util.tree_leaves(pw_net.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_two_process_compressed_local_sgd(tmp_path):
    """Threshold-compressed local SGD across REAL process boundaries
    (2 hosts x 4 devices, jax.distributed + gloo): trains to a finite
    score and reports cross-host wire accounting — the
    WiredEncodingHandler-over-the-network role, end to end."""
    outs = _launch(2, 8, str(tmp_path),
                   extra=("--averaging-frequency", "4",
                          "--threshold-compression", "0.03"))
    assert all("done" in o for o in outs), outs
    data = np.load(tmp_path / "final_params.npz")
    assert np.isfinite(float(data["score"]))
    assert int(data["wire_rendezvous"]) == 2
    assert 0.0 < float(data["wire_ratio"]) < 1.0


@pytest.mark.chaos
@pytest.mark.slow
def test_two_process_supervised_worker_kill_midstep(tmp_path_factory):
    """ROADMAP gap closed: a REAL 2-process `jax.distributed` job is
    killed mid-step via the `train.step` fault point (armed identically
    on both workers through DL4J_TPU_FAULTS — the whole slice dies, the
    deterministic analogue of a TPU worker loss); each worker's
    in-process Supervisor catches the crash, restores the newest valid
    checkpoint, and resumes. Final params must match an uninterrupted
    2-process run exactly."""
    steps = 6
    ref_dir = str(tmp_path_factory.mktemp("chaos_ref"))
    _launch(2, steps, ref_dir, ("--checkpoint-every", "1"))
    ref = np.load(os.path.join(ref_dir, "final_params.npz"))

    out = str(tmp_path_factory.mktemp("chaos_kill"))
    outs = _launch(
        2, steps, out, ("--checkpoint-every", "1", "--supervise", "2"),
        env_extra={"DL4J_TPU_FAULTS": "train.step:raise@4"})
    assert all("done" in o for o in outs), outs
    data = np.load(os.path.join(out, "final_params.npz"))
    assert int(data["restarts"]) == 1   # exactly one supervised resume
    got = [data[k] for k in data.files if k.startswith("arr_")]
    refp = [ref[k] for k in ref.files if k.startswith("arr_")]
    assert len(got) == len(refp)
    for g, e in zip(got, refp):
        # checkpoint resume replays the identical data/rng stream
        np.testing.assert_allclose(g, e, rtol=1e-6, atol=1e-7)
    assert int(data["iteration"]) == steps


def test_orbax_checkpoint_resume(tmp_path):
    """checkpoint_format='orbax': save/kill/resume reproduces the
    uninterrupted run exactly, matching the npz path's contract (the
    SURVEY 'orbax-style sharded checkpoints for scale' role)."""
    import jax

    sys.path.insert(0, os.path.join(os.path.dirname(HELPER)))
    import distributed_worker as dw
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.parallel.training_master import TrainingMaster

    devices = jax.devices("cpu")[:4]

    def batch_fn(step):
        return dw.global_batch(step)

    def run(ck_dir, steps, stop_after=None):
        net = dw.build_net()
        tm = TrainingMaster(net, checkpoint_dir=ck_dir,
                            checkpoint_every=1,
                            checkpoint_format="orbax",
                            mesh=make_mesh(dp=4, devices=devices))
        tm.fit(batch_fn, stop_after or steps)
        if stop_after:
            # "kill": fresh objects resume from the orbax checkpoint
            net2 = dw.build_net()
            tm2 = TrainingMaster(net2, checkpoint_dir=ck_dir,
                                 checkpoint_every=1,
                                 checkpoint_format="orbax",
                                 mesh=make_mesh(dp=4, devices=devices))
            tm2.fit(batch_fn, steps)
            return net2, tm2
        return net, tm

    straight, tm_a = run(str(tmp_path / "a"), 5)
    resumed, tm_b = run(str(tmp_path / "b"), 5, stop_after=2)
    assert tm_b.list_checkpoints() == [1, 2, 3, 4, 5]
    for a, b in zip(jax.tree_util.tree_leaves(straight.params),
                    jax.tree_util.tree_leaves(resumed.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
