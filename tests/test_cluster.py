"""Cluster-supervision tests (PR 4 tentpole + PR 10 elasticity):
HeartbeatFile leases, ClusterSupervisor gang restart (crash / SIGKILL /
hard hang / injected stale lease), worker quarantine
(`RestartsExhaustedError`), the resume-step handshake, the
bounded-wall-time guarantee — and the elastic layer: spare-worker
rescheduling, shrink-to-fit restarts (`allow_shrink`/`min_workers`
with the dp-average denominator re-derived from the live world size),
and the per-rank checkpoint divergence quorum
(`CheckpointDivergenceError`, minority forks quarantined aside and
healed).

Fast tests use trivial python -c workers (no jax) and are tier-1; the
2/3-process jax.distributed gang drills are marked chaos+slow.

Named fault points exercised here: `dist.heartbeat_stale` (forced
stale-lease verdict in the supervisor), `dist.spare_exhausted` (the
no-spare-left juncture), and `train.hang_hard` (SIGUSR1-immune wedge
in the worker fit loop). Cluster metrics pinned here:
`dl4j_cluster_world_size`, `dl4j_cluster_spare_reschedules_total`,
`dl4j_cluster_shrinks_total`.
"""

import os
import shutil
import signal
import sys
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.observability.metrics import get_registry
from deeplearning4j_tpu.resilience import (
    CheckpointDivergenceError,
    ClusterSupervisor,
    DeadlineExceededError,
    FaultInjectedError,
    HeartbeatFile,
    RestartsExhaustedError,
    compute_state_digest,
    divergence_quorum,
    heartbeat_path,
    injector,
    quorum_resume_step,
    rank_checkpoint_dir,
    record_checksum,
    sha256_file,
)

HELPER = os.path.join(os.path.dirname(__file__), "helpers",
                      "distributed_worker.py")
REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


# ================================================= heartbeat leases
def test_heartbeat_file_roundtrip_and_throttle(tmp_path):
    path = str(tmp_path / "w.hb.json")
    hb = HeartbeatFile(path, min_interval_s=10.0)
    hb.write(phase="dispatch", step=3)
    rec = HeartbeatFile.read(path)
    assert rec["pid"] == os.getpid()
    assert rec["step"] == 3 and rec["phase"] == "dispatch"
    assert rec["status"] == "running"
    assert HeartbeatFile.age_s(path) < 5.0

    # same-status writes inside the interval are throttled (the beat
    # path must not pay a disk write per step)
    hb.write(phase="fetch", step=4)
    assert hb.counters == {"writes": 1, "throttled": 1}
    assert HeartbeatFile.read(path)["step"] == 3

    # a status CHANGE always lands, throttle or not
    hb.mark_hang("dispatch", 12.0)
    rec = HeartbeatFile.read(path)
    assert rec["status"] == "hang" and rec["step"] == 4

    assert HeartbeatFile.read(str(tmp_path / "missing")) is None
    assert HeartbeatFile.age_s(str(tmp_path / "missing")) is None


def test_heartbeat_lease_world_size_and_slot_fields(tmp_path):
    """Satellite: lease records carry the worker's elastic identity —
    world size from the launch handshake, slot from the supervisor —
    on EVERY record (incl. forced status marks), survive torn writes
    via the mtime fallback, and ride the coarse-mtime fallback path."""
    path = str(tmp_path / "w.hb.json")
    hb = HeartbeatFile(path, min_interval_s=0.0, world_size=3, slot=4)
    hb.write(phase="dispatch", step=7)
    rec = HeartbeatFile.read(path)
    assert rec["world_size"] == 3 and rec["slot"] == 4

    # a status mark (the hang/done paths) keeps the identity fields
    hb.mark("done")
    rec = HeartbeatFile.read(path)
    assert rec["status"] == "done"
    assert rec["world_size"] == 3 and rec["slot"] == 4

    # torn write: a half-record still counts as a liveness renewal
    # (mtime fallback) but parses to None — never a crash
    with open(path, "w") as f:
        f.write('{"pid": 1, "world_si')
    assert HeartbeatFile.read(path) is None
    age = HeartbeatFile.age_s(path)
    assert age is not None and age < 5.0

    # coarse-mtime NFS shape: a record whose embedded time is in the
    # future (writer clock skew) falls back to the file mtime
    hb.write(phase="step", step=8, force=True)
    rec = HeartbeatFile.read(path)
    rec["time"] = time.time() + 3600.0
    with open(path, "w") as f:
        import json as _json

        f.write(_json.dumps(rec))
    past = time.time() - 40.0
    os.utime(path, (past, past))
    age = HeartbeatFile.age_s(path)
    assert 30.0 < age < 120.0       # mtime won, future time ignored

    # legacy leases (no elastic identity) stay field-free
    hb2 = HeartbeatFile(str(tmp_path / "w2.hb.json"))
    hb2.write(step=1)
    rec2 = HeartbeatFile.read(str(tmp_path / "w2.hb.json"))
    assert "world_size" not in rec2 and "slot" not in rec2


def _hb_writer_script(hb_dir: str, rank: int, loop: bool) -> str:
    """A trivial no-jax worker: renew the lease, then exit 0 (loop=False)
    or renew forever (loop=True)."""
    body = ("while True:\n    hb.write(step=1, force=True)\n"
            "    time.sleep(0.05)\n" if loop
            else "hb.write(step=1, force=True)\nhb.mark('done')\n")
    return (
        "import sys, time\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from deeplearning4j_tpu.resilience.cluster import (\n"
        "    HeartbeatFile, heartbeat_path)\n"
        f"hb = HeartbeatFile(heartbeat_path({hb_dir!r}, {rank}))\n"
        + body)


# ================================================= supervisor basics
def test_cluster_success_path(tmp_path):
    hb_dir = str(tmp_path / "hb")

    def command_fn(rank, nprocs, port, resume_step):
        assert nprocs == 2 and port > 0 and resume_step == 0
        return [sys.executable, "-c",
                _hb_writer_script(hb_dir, rank, loop=False)]

    cs = ClusterSupervisor(2, command_fn, hb_dir, poll_s=0.05,
                           startup_grace_s=60.0)
    stats = cs.run(timeout_s=60.0)
    assert stats["gang_restarts"] == 0
    assert stats["generations"] == 1
    assert stats["quarantined"] == [] and stats["ledger"] == []
    for rank in range(2):
        assert HeartbeatFile.read(
            heartbeat_path(hb_dir, rank))["status"] == "done"


@pytest.mark.chaos
def test_cluster_quarantine_after_restart_budget(tmp_path):
    """A member that keeps crashing exhausts its per-worker budget: the
    supervisor quarantines it and aborts the GANG with
    RestartsExhaustedError — bounded recovery, and the healthy member
    is killed too (a half gang cannot make progress)."""
    hb_dir = str(tmp_path / "hb")

    def command_fn(rank, nprocs, port, resume_step):
        if rank == 0:
            return [sys.executable, "-c", "import sys; sys.exit(3)"]
        return [sys.executable, "-c",
                _hb_writer_script(hb_dir, rank, loop=True)]

    cs = ClusterSupervisor(2, command_fn, hb_dir, poll_s=0.05,
                           grace_s=0.5, restart_backoff_s=0.05,
                           max_restarts_per_worker=1,
                           startup_grace_s=60.0)
    t0 = time.monotonic()
    with pytest.raises(RestartsExhaustedError) as ei:
        cs.run(timeout_s=60.0)
    assert time.monotonic() - t0 < 30.0          # never an open-ended hang
    assert cs.quarantined == [0]
    assert cs.gang_restarts == 2                 # budget 1 + the final straw
    assert [e["worker"] for e in ei.value.ledger] == [0, 0]
    assert all(e["reason"] == "crash" for e in ei.value.ledger)
    for m in cs.members:                         # nothing leaked
        assert not m.alive


@pytest.mark.chaos
def test_cluster_injected_stale_lease_kills_live_worker(tmp_path):
    """`dist.heartbeat_stale` armed in the SUPERVISOR process forces a
    stale verdict on a perfectly live worker: the SIGTERM-then-SIGKILL
    + gang-restart path runs without a real 60-second hang."""
    hb_dir = str(tmp_path / "hb")

    def command_fn(rank, nprocs, port, resume_step):
        return [sys.executable, "-c",
                _hb_writer_script(hb_dir, rank, loop=True)]

    injector().inject("dist.heartbeat_stale", at_hit=1)
    cs = ClusterSupervisor(2, command_fn, hb_dir, poll_s=0.05,
                           grace_s=0.5, restart_backoff_s=0.05,
                           max_restarts_per_worker=0,
                           startup_grace_s=60.0)
    with pytest.raises(RestartsExhaustedError) as ei:
        cs.run(timeout_s=60.0)
    assert ei.value.ledger[0]["reason"] == "heartbeat_stale(injected)"
    assert cs.quarantined == [0]
    for m in cs.members:
        assert not m.alive


@pytest.mark.chaos
def test_cluster_run_deadline_never_hangs(tmp_path):
    """A gang that is healthy but never finishes is still bounded:
    run(timeout_s) kills it and raises instead of waiting forever."""
    hb_dir = str(tmp_path / "hb")

    def command_fn(rank, nprocs, port, resume_step):
        return [sys.executable, "-c",
                _hb_writer_script(hb_dir, rank, loop=True)]

    cs = ClusterSupervisor(1, command_fn, hb_dir, poll_s=0.05,
                           grace_s=0.5, startup_grace_s=60.0)
    with pytest.raises(DeadlineExceededError):
        cs.run(timeout_s=1.5)
    assert not cs.members[0].alive


def test_cluster_resume_step_scan_prefers_newest_valid(tmp_path):
    """The gang-restart handshake picks the newest checkpoint passing
    integrity validation — a torn newest file is skipped (the existing
    checkpoint_integrity scan, reused verbatim)."""
    from deeplearning4j_tpu.resilience import record_checksum, sha256_file

    ck = tmp_path / "ckpt"
    ck.mkdir()
    for step, payload in ((2, b"x" * 64), (4, b"y" * 64)):
        p = ck / f"step-{step:08d}.npz"
        p.write_bytes(payload)
        record_checksum(str(ck), p.name, sha256_file(str(p)), 64,
                        extra={"step": step})
    cs = ClusterSupervisor(1, lambda *a: ["true"], str(tmp_path / "hb"),
                           checkpoint_dir=str(ck))
    assert cs._resume_step() == 4
    # tear the newest: the handshake falls back to step 2
    (ck / "step-00000004.npz").write_bytes(b"y" * 32)
    assert cs._resume_step() == 2
    cs_none = ClusterSupervisor(1, lambda *a: ["true"],
                                str(tmp_path / "hb2"))
    assert cs_none._resume_step() == 0


# ====================================== elastic gang scheduling (fast)
@pytest.mark.chaos
def test_cluster_spare_reschedule_after_quarantine(tmp_path):
    """Tentpole: a worker that exhausts its restart budget is
    quarantined and its rank RESCHEDULED onto a spare slot — fresh
    workdir, same rank id, budget reset — and the gang completes
    instead of aborting. The per-slot ledger and the
    dl4j_cluster_spare_reschedules_total counter record the event."""
    hb_dir = str(tmp_path / "hb")
    marker = str(tmp_path / "crashed-once")
    reg = get_registry()
    resched0 = reg.counter_value("dl4j_cluster_spare_reschedules_total")

    def command_fn(rank, nprocs, port, resume_step):
        if rank == 0:
            # crash once (before the marker exists), then behave —
            # slot visibility via the DL4J_TPU_SLOT env the supervisor
            # sets (recorded into a slot-<n>.seen file)
            return [sys.executable, "-c", (
                "import os, sys, time\n"
                f"sys.path.insert(0, {REPO!r})\n"
                "slot = os.environ['DL4J_TPU_SLOT']\n"
                "slot_dir = os.environ['DL4J_TPU_SLOT_DIR']\n"
                "assert os.path.isdir(slot_dir), slot_dir\n"
                f"open(os.path.join({str(tmp_path)!r}, "
                "'slot-' + slot + '.seen'), 'w').close()\n"
                f"m = {marker!r}\n"
                "if not os.path.exists(m):\n"
                "    open(m, 'w').close(); sys.exit(3)\n"
                "from deeplearning4j_tpu.resilience.cluster import (\n"
                "    HeartbeatFile, heartbeat_path)\n"
                f"hb = HeartbeatFile(heartbeat_path({hb_dir!r}, 0))\n"
                "hb.write(step=1, force=True)\n"
                "hb.mark('done')\n")]
        return [sys.executable, "-c",
                _hb_writer_script(hb_dir, rank, loop=False)]

    cs = ClusterSupervisor(2, command_fn, hb_dir, poll_s=0.05,
                           grace_s=0.5, restart_backoff_s=0.05,
                           max_restarts_per_worker=0, spares=1,
                           startup_grace_s=60.0)
    stats = cs.run(timeout_s=60.0)
    assert stats["spare_reschedules"] == 1
    assert stats["quarantined"] == [0]
    assert stats["quarantined_slots"] == [0]
    assert stats["spares_left"] == 0
    assert stats["slots"][0] == 2          # rank 0 now lives on slot 2
    events = [(e["event"], e["slot"], e["rank"])
              for e in stats["slot_ledger"]]
    assert events == [("quarantined", 0, 0), ("rescheduled", 2, 0)]
    # the rescheduled incarnation ran from the FRESH spare workdir
    assert os.path.exists(str(tmp_path / "slot-0.seen"))
    assert os.path.exists(str(tmp_path / "slot-2.seen"))
    assert os.path.isdir(os.path.join(hb_dir, "slot-2"))
    assert reg.counter_value("dl4j_cluster_spare_reschedules_total") \
        == resched0 + 1
    assert reg.gauge_value("dl4j_cluster_world_size") == 2


@pytest.mark.chaos
def test_cluster_shrink_to_fit_after_spares_dry(tmp_path):
    """Tentpole: with no spare left, `allow_shrink=True` relaunches the
    gang at reduced world size (floor min_workers) — the relaunched
    workers receive the NEW world size through command_fn's nprocs
    argument, and dl4j_cluster_world_size tracks the live gang."""
    hb_dir = str(tmp_path / "hb")
    launches = []
    reg = get_registry()
    shrinks0 = reg.counter_value("dl4j_cluster_shrinks_total")

    def command_fn(rank, nprocs, port, resume_step):
        launches.append((rank, nprocs))
        if nprocs == 3 and rank == 2:
            return [sys.executable, "-c", "import sys; sys.exit(3)"]
        return [sys.executable, "-c",
                _hb_writer_script(hb_dir, rank, loop=False)]

    cs = ClusterSupervisor(3, command_fn, hb_dir, poll_s=0.05,
                           grace_s=0.5, restart_backoff_s=0.05,
                           max_restarts_per_worker=0,
                           allow_shrink=True, min_workers=2,
                           startup_grace_s=60.0)
    stats = cs.run(timeout_s=60.0)
    assert stats["shrinks"] == 1
    assert stats["world_size"] == 2 and stats["nprocs"] == 2
    assert stats["quarantined_slots"] == [2]
    assert ("retired_shrink", 2, 2) in [
        (e["event"], e["slot"], e["rank"]) for e in stats["slot_ledger"]]
    # generation 0 launched 3 workers; generation 1 launched 2, and
    # every relaunched worker was told nprocs=2 (the resume handshake)
    assert [np for _, np in launches[:3]] == [3, 3, 3]
    assert [np for _, np in launches[3:]] == [2, 2]
    assert reg.counter_value("dl4j_cluster_shrinks_total") == shrinks0 + 1
    assert reg.gauge_value("dl4j_cluster_world_size") == 2
    # shrink below min_workers is refused: a 2-gang with min_workers=2
    # aborts instead of shrinking to 1
    hb2 = str(tmp_path / "hb2")

    def always_crash(rank, nprocs, port, resume_step):
        return [sys.executable, "-c", "import sys; sys.exit(3)"]

    cs2 = ClusterSupervisor(2, always_crash, hb2, poll_s=0.05,
                            grace_s=0.5, restart_backoff_s=0.05,
                            max_restarts_per_worker=0,
                            allow_shrink=True, min_workers=2,
                            startup_grace_s=60.0)
    with pytest.raises(RestartsExhaustedError) as ei:
        cs2.run(timeout_s=60.0)
    assert "min_workers" in str(ei.value)


@pytest.mark.chaos
def test_cluster_spare_exhausted_fault_point_and_abort(tmp_path):
    """`dist.spare_exhausted` fires exactly when a quarantined worker
    finds the spare pool dry: the drill arms it as a raise; unarmed,
    the same juncture aborts with RestartsExhaustedError whose ledger
    shows the reschedule that consumed the spare."""
    hb_dir = str(tmp_path / "hb")

    def always_crash(rank, nprocs, port, resume_step):
        return [sys.executable, "-c", "import sys; sys.exit(3)"]

    injector().inject("dist.spare_exhausted", at_hit=1)
    cs = ClusterSupervisor(1, always_crash, hb_dir, poll_s=0.05,
                           grace_s=0.5, restart_backoff_s=0.05,
                           max_restarts_per_worker=0, spares=1,
                           startup_grace_s=60.0)
    with pytest.raises(FaultInjectedError):
        cs.run(timeout_s=60.0)
    assert cs.spare_reschedules == 1   # the spare WAS consumed first
    injector().clear()

    cs2 = ClusterSupervisor(1, always_crash, str(tmp_path / "hb2"),
                            poll_s=0.05, grace_s=0.5,
                            restart_backoff_s=0.05,
                            max_restarts_per_worker=0, spares=1,
                            startup_grace_s=60.0)
    with pytest.raises(RestartsExhaustedError) as ei:
        cs2.run(timeout_s=60.0)
    assert cs2.spare_reschedules == 1
    assert "no spare left" in str(ei.value)
    assert cs2.quarantined_slots == [0, 1]
    for m in cs2.members:
        assert not m.alive


# ===================================== checkpoint divergence quorum
def _write_rank_ckpt(base, rank, step, val, iteration=0):
    """One rank's npz checkpoint copy + manifest entry (file sha AND
    the canonical state digest, like TrainingMaster records)."""
    d = rank_checkpoint_dir(str(base), rank)
    os.makedirs(d, exist_ok=True)
    fn = f"step-{step:08d}.npz"
    p = os.path.join(d, fn)
    np.savez(p, params=np.full(8, val, np.float32),
             rng=np.arange(4), iteration=np.asarray(iteration))
    record_checksum(d, fn, sha256_file(p), os.path.getsize(p),
                    extra={"step": step,
                           "state_sha256": compute_state_digest(p)})
    return p


def test_divergence_quorum_outvotes_and_heals_minority(tmp_path):
    """Tentpole: 2-of-3 ranks agree on step 3; the divergent rank-1
    copy is out-voted, quarantined ASIDE (renamed, never deleted) and
    replaced by the quorum copy — after healing all three rank copies
    hash identically."""
    for r in range(3):
        _write_rank_ckpt(tmp_path, r, 3, val=1.0)
    divergent = _write_rank_ckpt(tmp_path, 1, 3, val=99.0)  # the fork
    report = quorum_resume_step(str(tmp_path), 3)
    assert report["step"] == 3
    assert report["healed"] == [1]
    assert len(report["quarantined"]) == 1
    aside = report["quarantined"][0]
    assert aside.endswith(".divergent") and os.path.exists(aside)
    # the quarantined bytes ARE the divergent copy, preserved
    assert compute_state_digest(aside) != report["digest"]
    # post-heal: unanimous
    digests = {compute_state_digest(
        os.path.join(rank_checkpoint_dir(str(tmp_path), r),
                     "step-00000003.npz")) for r in range(3)}
    assert digests == {report["digest"]}
    # idempotent: a second quorum pass heals nothing
    again = divergence_quorum(str(tmp_path), 3, 3)
    assert again["healed"] == [] and again["quarantined"] == []
    assert divergent == os.path.join(
        rank_checkpoint_dir(str(tmp_path), 1), "step-00000003.npz")


def test_divergence_quorum_heals_missing_and_torn_ranks(tmp_path):
    """A rank whose copy is missing (crashed before the write) or torn
    (fails its own checksum) is a non-voter: quorum elects the healthy
    majority and copies the file in, so the shared resume handshake
    holds for EVERY relaunched rank."""
    for r in range(3):
        _write_rank_ckpt(tmp_path, r, 5, val=2.0)
    # rank 0: torn (truncate, keep stale manifest); rank 2: missing
    p0 = os.path.join(rank_checkpoint_dir(str(tmp_path), 0),
                      "step-00000005.npz")
    with open(p0, "r+b") as f:
        f.truncate(os.path.getsize(p0) // 2)
    os.remove(os.path.join(rank_checkpoint_dir(str(tmp_path), 2),
                           "step-00000005.npz"))
    report = divergence_quorum(str(tmp_path), 3, 5)
    # 1-of-3 valid votes is NOT a majority: no quorum at this step
    assert report["digest"] is None
    # with a second healthy rank the quorum elects and heals both
    _write_rank_ckpt(tmp_path, 2, 5, val=2.0)
    report = divergence_quorum(str(tmp_path), 3, 5)
    assert report["digest"] is not None
    assert report["healed"] == [0]
    assert divergence_quorum(str(tmp_path), 3, 5)["healed"] == []


def test_divergence_quorum_tie_fails_loudly(tmp_path):
    """No-quorum tie (1v1 across 2 ranks): CheckpointDivergenceError
    carries the step and the vote map — resume never silently elects
    an arbitrary fork."""
    _write_rank_ckpt(tmp_path, 0, 4, val=1.0)
    _write_rank_ckpt(tmp_path, 1, 4, val=2.0)
    with pytest.raises(CheckpointDivergenceError) as ei:
        quorum_resume_step(str(tmp_path), 2)
    assert ei.value.step == 4
    assert len(ei.value.votes) == 2
    assert sorted(sum(ei.value.votes.values(), [])) == [0, 1]


def test_quorum_resume_skips_minority_newest_step(tmp_path):
    """A newest step held by only a minority of ranks (the gang died
    mid-checkpoint-cadence) elects nothing; the scan falls back to the
    newest step with a real quorum — the per-rank analogue of the
    newest-common-valid scan."""
    for r in range(3):
        _write_rank_ckpt(tmp_path, r, 2, val=1.0)
    _write_rank_ckpt(tmp_path, 0, 6, val=3.0)   # only rank 0 got to 6
    report = quorum_resume_step(str(tmp_path), 3)
    assert report["step"] == 2
    # and the supervisor's handshake consumes exactly this scan
    cs = ClusterSupervisor(3, lambda *a: ["true"],
                           str(tmp_path / "hb"),
                           checkpoint_dir=str(tmp_path),
                           per_rank_checkpoints=True)
    assert cs._resume_step() == 2
    assert cs.quorum_reports and cs.quorum_reports[-1]["step"] == 2


# ================================================= 2-process jax gangs
def _worker_env(device_count=4):
    """`device_count` must keep every gang's dp extent dividing its
    global batch: 2-proc gangs shard 32 rows (any count), 3-proc gangs
    shard 30 rows (32//3 * 3) — pass 2 there so dp=6 divides 30."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={device_count}"
    env.pop("JAX_COORDINATOR_ADDRESS", None)
    env.pop("DL4J_TPU_FAULTS", None)
    return env


def _gang_cmd_fn(steps, out_dir, hb_dir, hang_timeout=0.0, extra=()):
    def command_fn(rank, nprocs, port, resume_step):
        ht = (hang_timeout(rank) if callable(hang_timeout)
              else hang_timeout)
        return [sys.executable, HELPER, str(rank), str(nprocs),
                str(port), str(steps), out_dir,
                "--checkpoint-every", "1",
                "--cluster", "--heartbeat-dir", hb_dir,
                "--resume-step", str(resume_step),
                "--hang-timeout", str(ht), *extra]
    return command_fn


def _gang_supervisor(out, steps=6, hang_timeout=0.0, extra=(),
                     nprocs=2, **kw):
    hb_dir = os.path.join(out, "hb")
    kw.setdefault("lease_timeout_s", 120.0)
    kw.setdefault("startup_grace_s", 240.0)
    kw.setdefault("poll_s", 0.2)
    kw.setdefault("restart_backoff_s", 0.2)
    kw.setdefault("env", _worker_env())
    return ClusterSupervisor(
        nprocs, _gang_cmd_fn(steps, out, hb_dir, hang_timeout, extra),
        hb_dir, checkpoint_dir=os.path.join(out, "ckpt"), **kw)


def _final(out):
    data = np.load(os.path.join(out, "final_params.npz"))
    return ([data[k] for k in data.files if k.startswith("arr_")],
            int(data["iteration"]))


def _assert_parity(out, oracle):
    got, iteration = _final(out)
    ref, ref_iter = oracle
    assert iteration == ref_iter
    assert len(got) == len(ref)
    for g, e in zip(got, ref):
        # gang relaunch replays the identical data/rng stream from the
        # shared resume step
        np.testing.assert_allclose(g, e, rtol=1e-6, atol=1e-7)


@pytest.fixture(scope="module")
def gang_oracle(tmp_path_factory):
    """Un-faulted 2-process cluster run: the parity reference for every
    gang-restart drill (and the success-path proof for real workers)."""
    out = str(tmp_path_factory.mktemp("gang_oracle"))
    cs = _gang_supervisor(out)
    stats = cs.run(timeout_s=280.0)
    assert stats["gang_restarts"] == 0
    return _final(out)


@pytest.mark.chaos
@pytest.mark.slow
def test_cluster_gang_restart_after_worker_sigkill(tmp_path_factory,
                                                   gang_oracle):
    """Acceptance: one worker SIGKILLed mid-step (from outside, via the
    pid in its own heartbeat lease). The supervisor detects the death,
    kills the survivor, and relaunches the gang from the newest common
    valid checkpoint; final params match the un-faulted oracle."""
    out = str(tmp_path_factory.mktemp("gang_kill"))
    cs = _gang_supervisor(out, extra=("--spin-ms", "250"),
                          max_restarts_per_worker=2)
    hb_dir = os.path.join(out, "hb")
    killed = {}

    def killer():
        while not killed:
            rec = HeartbeatFile.read(heartbeat_path(hb_dir, 1))
            if (rec and rec.get("status") == "running"
                    and (rec.get("step") or 0) >= 2):
                try:
                    os.kill(rec["pid"], signal.SIGKILL)
                    killed["pid"] = rec["pid"]
                except ProcessLookupError:
                    pass
                return
            time.sleep(0.05)

    th = threading.Thread(target=killer, daemon=True)
    th.start()
    stats = cs.run(timeout_s=280.0)
    th.join(timeout=5.0)
    assert killed, "chaos killer never fired"
    assert stats["gang_restarts"] == 1
    assert any(e["worker"] == 1 and e["reason"] == "killed:sig9"
               for e in stats["ledger"])
    assert stats["resume_steps"] and stats["resume_steps"][0] >= 1
    _assert_parity(out, gang_oracle)


def _one_shot_fault_env(spec, target_rank=0):
    """Arm a DL4J_TPU_FAULTS spec on `target_rank` of the FIRST
    generation only — relaunched gangs get a clean environment, so one
    fault means one gang restart."""
    launches = {"n": 0}

    def env_fn(rank):
        if rank == target_rank:
            launches["n"] += 1
            if launches["n"] == 1:
                return {"DL4J_TPU_FAULTS": spec}
        return {}

    return env_fn


def _one_shot_hang_env(delay_spec):
    return _one_shot_fault_env(delay_spec, target_rank=0)


@pytest.mark.chaos
@pytest.mark.slow
def test_cluster_gang_restart_after_uninterruptible_hang(
        tmp_path_factory, gang_oracle):
    """Acceptance: rank 0 wedges in a SIGUSR1+SIGTERM-immune sleep
    (`train.hang_hard`) with NO in-process watchdog escalation — only
    the supervisor's stale-lease detection can see it. The lease goes
    stale, SIGTERM is ignored (blocked), SIGKILL lands, the gang
    relaunches from the newest common checkpoint, and final params
    match the oracle exactly."""
    out = str(tmp_path_factory.mktemp("gang_hang"))
    cs = _gang_supervisor(
        out, hang_timeout=0.0,         # lease emission only
        lease_timeout_s=15.0, poll_s=0.3, grace_s=1.0,
        max_restarts_per_worker=3,
        env_fn=_one_shot_hang_env("train.hang_hard:delay@3~120.0"))
    stats = cs.run(timeout_s=280.0)
    assert stats["gang_restarts"] == 1
    reasons = {e["worker"]: e["reason"] for e in stats["ledger"]}
    assert "heartbeat_stale" in reasons[0]
    _assert_parity(out, gang_oracle)


@pytest.mark.chaos
@pytest.mark.slow
def test_cluster_hard_hang_watchdog_exit_code(tmp_path_factory,
                                              gang_oracle):
    """The other half of the hard-hang story: with a heartbeat-attached
    StepWatchdog, the monitor thread survives the wedged training
    thread, sees its SIGUSR1 never landed, writes the hang marker, and
    os._exit(EXIT_HANG)s — the supervisor classifies `hang_hard` from
    the exit code and relaunches without waiting out the lease."""
    out = str(tmp_path_factory.mktemp("gang_wd_exit"))
    cs = _gang_supervisor(
        out,
        hang_timeout=lambda rank: 4.0 if rank == 0 else 0.0,
        lease_timeout_s=120.0, grace_s=1.0,
        max_restarts_per_worker=3,
        env_fn=_one_shot_hang_env("train.hang_hard:delay@3~120.0"))
    stats = cs.run(timeout_s=280.0)
    assert stats["gang_restarts"] == 1
    # either observation of the hard-exit escalation counts: the
    # EXIT_HANG code, or the hang marker the watchdog wrote into the
    # lease just before os._exit (whichever the poll sees first)
    assert any(e["worker"] == 0
               and e["reason"] in ("hang_hard", "hang_marker")
               for e in stats["ledger"])
    hb = HeartbeatFile.read(
        heartbeat_path(os.path.join(out, "hb"), 0))
    # the marker from generation 0 was replaced by generation 1's lease
    assert hb["status"] == "done"
    _assert_parity(out, gang_oracle)


# ====================================== elastic gang drills (jax)
def _final_world(out):
    data = np.load(os.path.join(out, "final_params.npz"))
    return int(data["world"])


@pytest.mark.chaos
@pytest.mark.slow
def test_cluster_spare_reschedule_gang(tmp_path_factory, gang_oracle):
    """Acceptance: a quarantined-then-rescheduled worker continues
    training. Rank 1 crashes on an injected `train.step` fault with a
    zero restart budget — quarantined immediately — and its rank is
    rescheduled onto the spare slot; the relaunched gang (same world
    size, fresh coordinator port) resumes from the newest common
    checkpoint and final params match the un-faulted oracle exactly."""
    out = str(tmp_path_factory.mktemp("gang_spare"))
    cs = _gang_supervisor(
        out, max_restarts_per_worker=0, spares=1,
        env_fn=_one_shot_fault_env("train.step:raise@3", target_rank=1))
    stats = cs.run(timeout_s=280.0)
    assert stats["gang_restarts"] == 1
    assert stats["spare_reschedules"] == 1
    assert stats["quarantined"] == [1]
    assert stats["quarantined_slots"] == [1]
    assert stats["slots"][1] == 2          # rank 1 now on spare slot 2
    assert [e["event"] for e in stats["slot_ledger"]] == \
        ["quarantined", "rescheduled"]
    assert stats["resume_steps"] and stats["resume_steps"][0] >= 1
    assert stats["world_size"] == 2        # elastic, but not shrunk
    assert _final_world(out) == 2
    _assert_parity(out, gang_oracle)


@pytest.mark.chaos
@pytest.mark.slow
def test_cluster_shrink_3_to_2_mid_run(tmp_path_factory):
    """Acceptance: a 3-worker gang loses rank 2 for good (no spares,
    zero budget) mid-run and SHRINKS to 2: the relaunched workers
    receive world size 2 through the resume handshake and re-derive
    their data shard + dp-average denominator from it. The loss-
    denominator semantics are pinned exactly: the shrunk run's final
    params are byte-compatible with a NATIVE 2-worker gang resumed
    from the same checkpoint — post-shrink training IS 2-world
    training, loss averaged over the surviving replicas."""
    out = str(tmp_path_factory.mktemp("gang_shrink"))
    cs = _gang_supervisor(
        out, nprocs=3, max_restarts_per_worker=0,
        allow_shrink=True, min_workers=2, env=_worker_env(2),
        env_fn=_one_shot_fault_env("train.step:raise@3", target_rank=2))
    stats = cs.run(timeout_s=280.0)
    assert stats["shrinks"] == 1
    assert stats["world_size"] == 2
    assert stats["quarantined_slots"] == [2]
    assert ("retired_shrink", 2) in [
        (e["event"], e["slot"]) for e in stats["slot_ledger"]]
    s = stats["resume_steps"][-1]
    assert s >= 1
    assert _final_world(out) == 2          # the live world at the end
    assert get_registry().gauge_value("dl4j_cluster_world_size") == 2

    # the 2-world continuation oracle: a NATIVE 2-worker gang resumed
    # from a copy of the pre-shrink checkpoint state (steps > s pruned
    # so its own scan lands on the same shared resume step)
    from deeplearning4j_tpu.resilience import list_all_checkpoints

    oracle_out = str(tmp_path_factory.mktemp("gang_shrink_oracle"))
    oracle_ckpt = os.path.join(oracle_out, "ckpt")
    shutil.copytree(os.path.join(out, "ckpt"), oracle_ckpt)
    for step, fn in list_all_checkpoints(oracle_ckpt):
        if step > s:
            os.remove(os.path.join(oracle_ckpt, fn))
    # same device layout as the shrunk generation (mesh parity)
    cs_oracle = _gang_supervisor(oracle_out, nprocs=2,
                                 env=_worker_env(2))
    ostats = cs_oracle.run(timeout_s=280.0)
    assert ostats["gang_restarts"] == 0
    assert _final_world(oracle_out) == 2
    _assert_parity(out, _final(oracle_out))


@pytest.mark.chaos
@pytest.mark.slow
def test_cluster_shrink_3_to_2_with_sharded_optimizer(
        tmp_path_factory):
    """Acceptance (sharded scale-out): the 3→2 shrink drill with
    ZeRO-1 SHARDED optimizer state. Every rank checkpoints its own
    optimizer-state SLICE next to the quorum-voted replicated main
    copy; when rank 2 dies for good and the gang shrinks to 2, the
    supervisor's sharded quorum votes over the SAVE-time world (rank
    2's dir still votes and still contributes its slice), and the
    relaunched workers reassemble all three slices and re-slice them
    for the smaller world (resharding on resume,
    dl4j_mesh_reshard_total). Final params are byte-compatible with a
    NATIVE 2-worker zero1 gang resumed from the same checkpoint —
    post-shrink training IS 2-world sharded training. The fast no-jax
    twins of the slice/quorum math live in test_mesh.py."""
    out = str(tmp_path_factory.mktemp("gang_shrink_z1"))
    cs = _gang_supervisor(
        out, nprocs=3, max_restarts_per_worker=0,
        allow_shrink=True, min_workers=2, env=_worker_env(2),
        extra=("--per-rank-ckpt", "--zero1"),
        per_rank_checkpoints=True, sharded_optimizer=True,
        env_fn=_one_shot_fault_env("train.step:raise@3", target_rank=2))
    stats = cs.run(timeout_s=280.0)
    assert stats["shrinks"] == 1
    assert stats["world_size"] == 2
    s = stats["resume_steps"][-1]
    assert s >= 1
    assert _final_world(out) == 2
    # the elected step carried a complete slice set over the 3-rank
    # save world
    report = cs.quorum_reports[-1]
    assert report["shard_world"] == 3
    assert sorted(report["slices"]) == [0, 1, 2]
    # sharded layout on disk: every rank wrote main + slice sidecar
    for r in range(3):
        d = rank_checkpoint_dir(os.path.join(out, "ckpt"), r)
        fns = os.listdir(d)
        assert any(fn.endswith(".updshard.npz") for fn in fns)

    # native 2-world zero1 oracle resumed from a copy of the
    # pre-shrink checkpoint state (steps > s pruned per rank dir so
    # its own sharded quorum lands on the same shared resume step)
    oracle_out = str(tmp_path_factory.mktemp("gang_shrink_z1_oracle"))
    oracle_ckpt = os.path.join(oracle_out, "ckpt")
    shutil.copytree(os.path.join(out, "ckpt"), oracle_ckpt)
    from deeplearning4j_tpu.resilience import list_all_checkpoints

    for r in range(3):
        d = rank_checkpoint_dir(oracle_ckpt, r)
        for step, fn in list_all_checkpoints(d):
            if step > s:
                os.remove(os.path.join(d, fn))
                side = os.path.join(
                    d, f"step-{step:08d}.updshard.npz")
                if os.path.exists(side):
                    os.remove(side)
    cs_oracle = _gang_supervisor(
        oracle_out, nprocs=2, env=_worker_env(2),
        extra=("--per-rank-ckpt", "--zero1"),
        per_rank_checkpoints=True, sharded_optimizer=True)
    ostats = cs_oracle.run(timeout_s=280.0)
    assert ostats["gang_restarts"] == 0
    assert _final_world(oracle_out) == 2
    _assert_parity(out, _final(oracle_out))


@pytest.mark.chaos
@pytest.mark.slow
def test_cluster_divergent_checkpoint_healed_by_quorum(
        tmp_path_factory):
    """Acceptance: a deliberately perturbed rank-1 checkpoint (a
    silently forked replica: self-consistent file + manifest, wrong
    state) is OUT-VOTED by the 2-of-3 quorum on resume — quarantined
    aside, healed from the quorum copy — and the resumed run's final
    params match an un-faulted oracle exactly."""
    out = str(tmp_path_factory.mktemp("gang_quorum"))
    ckpt = os.path.join(out, "ckpt")
    # phase A: clean 3-worker run of 4 steps, per-rank checkpoints
    cs_a = _gang_supervisor(out, steps=4, nprocs=3,
                            extra=("--per-rank-ckpt",),
                            env=_worker_env(2),
                            per_rank_checkpoints=True)
    assert cs_a.run(timeout_s=280.0)["gang_restarts"] == 0

    # fork rank 1's newest copy: perturb one param leaf and re-record
    # a SELF-CONSISTENT manifest (file sha + state digest match the
    # new bytes) — only the cross-rank quorum can catch this
    d1 = rank_checkpoint_dir(ckpt, 1)
    fn = "step-00000004.npz"
    p1 = os.path.join(d1, fn)
    with np.load(p1) as z:
        payload = {k: np.array(z[k]) for k in z.files}
    first = sorted(k for k in payload if k.startswith("params"))[0]
    payload[first] = payload[first] + 1.0
    np.savez(p1, **payload)
    record_checksum(d1, fn, sha256_file(p1), os.path.getsize(p1),
                    extra={"step": 4,
                           "state_sha256": compute_state_digest(p1)})

    # phase B: resume to 7 steps — the quorum must heal BEFORE resume
    cs_b = _gang_supervisor(out, steps=7, nprocs=3,
                            extra=("--per-rank-ckpt",),
                            env=_worker_env(2),
                            per_rank_checkpoints=True)
    stats = cs_b.run(timeout_s=280.0)
    assert stats["gang_restarts"] == 0
    report = stats["quorum_reports"][0]
    assert report["step"] == 4 and report["healed"] == [1]
    aside = report["quarantined"][0]
    assert aside.endswith(".divergent") and os.path.exists(aside)

    # all three ranks ended on identical final checkpoints…
    finals = {compute_state_digest(os.path.join(
        rank_checkpoint_dir(ckpt, r), "step-00000007.npz"))
        for r in range(3)}
    assert len(finals) == 1
    # …and the run matches the un-faulted 3-world oracle exactly
    oracle_out = str(tmp_path_factory.mktemp("gang_quorum_oracle"))
    cs_o = _gang_supervisor(oracle_out, steps=7, nprocs=3,
                            extra=("--per-rank-ckpt",),
                            env=_worker_env(2),
                            per_rank_checkpoints=True)
    assert cs_o.run(timeout_s=280.0)["gang_restarts"] == 0
    _assert_parity(out, _final(oracle_out))


# ================================================= stats surfacing
def test_cluster_stats_shape():
    cs = ClusterSupervisor(3, lambda *a: ["true"], "/tmp/_hb_unused",
                           spares=2)
    stats = cs.stats()
    assert stats["nprocs"] == 3
    assert stats["world_size"] == 3
    assert stats["gang_restarts"] == 0
    assert stats["per_worker_restarts"] == {}
    assert stats["quarantined"] == [] and stats["ledger"] == []
    assert stats["quarantined_slots"] == [] and stats["slot_ledger"] == []
    assert stats["spares_left"] == 2
    assert stats["spare_reschedules"] == 0 and stats["shrinks"] == 0
    assert stats["slots"] == {0: 0, 1: 1, 2: 2}
    assert stats["quorum_reports"] == []
