"""Performance introspection tests: StepPhaseProfiler ≥95% wall-time
attribution on the CPU smoke config, labeled phase histograms through
the StepAccumulator, JitCache recompile forensics (shape-shifted trace
ring, /status surface), cross-rank `aggregate_snapshots` exactness
(no-jax drill: summed counters, merged histogram buckets, one fleet
Prometheus exposition), the cluster supervisor's fleet_metrics pull
path, and the dashboard perf line."""

import json
import os
import threading

import numpy as np
import pytest

from deeplearning4j_tpu.observability import (
    MetricsRegistry,
    StepAccumulator,
    get_registry,
)
from deeplearning4j_tpu.observability import perf as perf_mod
from deeplearning4j_tpu.observability.perf import (
    StepPhaseProfiler,
    aggregate_prometheus_text,
    aggregate_snapshots,
    dump_snapshot,
)

pytestmark = pytest.mark.obs

N_IN, N_OUT, ROWS = 4, 3, 16


@pytest.fixture(autouse=True)
def _fresh_registry():
    get_registry().reset()
    yield
    get_registry().reset()


def _net(seed=7):
    from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer

    conf = (NeuralNetConfiguration.Builder().seed(seed).updater("adam")
            .learning_rate(1e-2).activation("tanh").weight_init("xavier")
            .list()
            .layer(DenseLayer(n_out=8))
            .layer(OutputLayer(n_out=N_OUT, loss="mcxent"))
            .set_input_type(InputType.feed_forward(N_IN))
            .build())
    return MultiLayerNetwork(conf).init()


def _batch(step):
    rng = np.random.default_rng(500 + step)
    x = rng.normal(size=(ROWS, N_IN)).astype(np.float32)
    y = np.eye(N_OUT, dtype=np.float32)[rng.integers(0, N_OUT, ROWS)]
    return x, y


# ============================================= labeled histograms
def test_labeled_histograms_snapshot_and_exposition():
    r = MetricsRegistry()
    r.observe("dl4j_train_phase_seconds", 0.004,
              labels={"phase": "dispatch"})
    r.observe("dl4j_train_phase_seconds", 0.002,
              labels={"phase": "data_wait"})
    r.observe("dl4j_train_step_seconds", 0.01)   # unlabeled unchanged
    snap = r.snapshot()
    assert 'dl4j_train_phase_seconds{phase="dispatch"}' \
        in snap["histograms"]
    assert snap["histograms"]["dl4j_train_step_seconds"]["count"] == 1
    text = r.prometheus_text()
    assert ('dl4j_train_phase_seconds_bucket{phase="dispatch",'
            'le="0.005"} 1') in text
    assert 'dl4j_train_phase_seconds_sum{phase="dispatch"}' in text
    assert 'dl4j_train_phase_seconds_count{phase="data_wait"} 1' in text
    # unlabeled histogram exposition is byte-identical to the PR 5 form
    assert 'dl4j_train_step_seconds_bucket{le="+Inf"} 1' in text


def test_step_accumulator_labeled_observe_flush():
    r = get_registry()
    acc = StepAccumulator(flush_every=100)
    for _ in range(3):
        acc.observe("dl4j_train_phase_seconds", 0.001,
                    labels={"phase": "dispatch"})
    acc.observe("dl4j_train_phase_seconds", 0.002,
                labels={"phase": "h2d"})
    acc.flush()
    snap = r.snapshot()
    disp = snap["histograms"][
        'dl4j_train_phase_seconds{phase="dispatch"}']
    assert disp["count"] == 3
    assert disp["sum"] == pytest.approx(0.003)
    assert snap["histograms"][
        'dl4j_train_phase_seconds{phase="h2d"}']["count"] == 1


# ================================================ step phase profiler
def test_phase_profiler_covers_wall_time_on_cpu_smoke():
    """Acceptance: ≥95% of measured wall step time attributed to named
    phases on the CPU smoke config (sampled device sync every step)."""
    from deeplearning4j_tpu.parallel.training_master import (
        TrainingMaster,
    )

    net = _net()
    pp = StepPhaseProfiler(sync_every=1)
    tm = TrainingMaster(net, phase_profiler=pp)
    tm.fit(lambda s: _batch(s), 25)
    rep = pp.report()
    assert rep["steps"] == 25
    assert rep["coverage"] >= 0.95, rep
    assert set(rep["phases"]) <= set(perf_mod.PHASES)
    # phase histograms landed (through the fit loop's accumulator)
    snap = get_registry().snapshot()
    disp = snap["histograms"][
        'dl4j_train_phase_seconds{phase="dispatch"}']
    assert disp["count"] == 25
    # shares sum to 1 over attributed time
    assert sum(p["share"] for p in rep["phases"].values()) \
        == pytest.approx(1.0)
    # the report also rides training_stats
    assert tm.training_stats()["phases"]["steps"] == 25


def test_phase_profiler_sync_sampling_and_checkpoint_phase(tmp_path):
    from deeplearning4j_tpu.parallel.training_master import (
        TrainingMaster,
    )

    net = _net()
    pp = StepPhaseProfiler(sync_every=4)
    tm = TrainingMaster(net, checkpoint_dir=str(tmp_path),
                        checkpoint_every=2, phase_profiler=pp)
    tm.fit(lambda s: _batch(s), 8)
    rep = pp.report()
    assert "checkpoint" in rep["phases"]   # 4 checkpoint steps
    snap = get_registry().snapshot()
    # device_compute observed only on the sampled (every-4th) steps
    dc = snap["histograms"][
        'dl4j_train_phase_seconds{phase="device_compute"}']
    assert dc["count"] == 2   # steps 0 and 4
    ck = snap["histograms"][
        'dl4j_train_phase_seconds{phase="checkpoint"}']
    assert ck["count"] == 4


def test_phase_profiler_in_parallel_wrapper():
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    net = _net()
    pw = ParallelWrapper(net, workers=2, phase_profiler=True)
    x, y = _batch(0)
    pw.fit([(x, y)] * 3)
    rep = pw.phase_profiler.report()
    assert rep["steps"] == 3
    assert rep["coverage"] >= 0.95
    assert "dispatch" in rep["phases"]


# ============================================== recompile forensics
def test_jit_cache_recompile_ring_captures_shape_shift():
    """Acceptance: a deliberately shape-shifted second trace lands in
    the forensics ring with its signature, a positive duration, and
    the dl4j_jit_compiles_total counter."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.jit_cache import JitCache

    cache = JitCache()

    def f(x):
        cache.record_trace("predict")
        return x * 2

    cache["predict"] = jax.jit(f)
    cache["predict"](jnp.ones((4, 3), jnp.float32))
    cache["predict"](jnp.ones((4, 3), jnp.float32))   # cache hit
    cache["predict"](jnp.ones((8, 3), jnp.float32))   # shape shift
    events = cache.compile_events()
    assert len(events) == 2
    assert events[0]["signature"] == "(float32[4,3])"
    assert events[1]["signature"] == "(float32[8,3])"
    assert all(e["duration_s"] > 0 for e in events)
    assert all(e["traces"] == 1 for e in events)
    assert cache.compiles_total() == 2
    assert cache.total_traces() == 2
    assert get_registry().counter_value(
        "dl4j_jit_compiles_total") == 2


def test_net_predict_recompile_forensics_via_trace_stats():
    """A real net's predict path records forensics; ParallelInference
    trace_stats surfaces them (the /status source)."""
    net = _net()
    net.output(np.ones((2, N_IN), np.float32))
    net.output(np.ones((5, N_IN), np.float32))   # second specialization
    events = net._jit_cache.compile_events()
    assert len(events) >= 2
    assert any("[2," in e["signature"] for e in events)
    assert any("[5," in e["signature"] for e in events)

    from deeplearning4j_tpu.parallel.inference import ParallelInference

    pi = ParallelInference(net, batch_limit=4, warmup=False,
                           pipeline_depth=0)
    try:
        stats = pi.trace_stats()
        assert stats["compiles_total"] >= 2
        assert len(stats["compile_events"]) >= 2
    finally:
        pi.shutdown()


def test_status_surfaces_recompile_forensics():
    """ModelServer /status answers "what recompiled": total + recent
    events with signature/duration."""
    from deeplearning4j_tpu.parallel.inference import ParallelInference
    from deeplearning4j_tpu.parallel.serving import (
        ModelClient,
        ModelServer,
    )

    net = _net()
    pi = ParallelInference(net, batch_limit=4, warmup=False,
                           pipeline_depth=0)
    server = ModelServer(pi, port=0).start()
    try:
        client = ModelClient(f"http://127.0.0.1:{server.port}",
                             breaker=None)
        client.predict(np.ones((2, N_IN), np.float32).tolist())
        st = client.status()
        rec = st["recompiles"]
        assert rec["total"] >= 1
        assert rec["recent"], "forensics ring empty on /status"
        ev = rec["recent"][-1]
        assert "signature" in ev and "duration_s" in ev
    finally:
        server.stop()


# ======================================== cross-rank aggregation (no jax)
def _rank_registry(steps, step_s, errors):
    r = MetricsRegistry()
    for i in range(steps):
        r.inc("dl4j_train_steps_total")
        r.observe("dl4j_train_step_seconds", step_s)
    if errors:
        r.inc("dl4j_serving_errors_total", errors,
              labels={"code": "503"})
    r.set_gauge("dl4j_train_loss", 0.1 * (1 + errors),
                labels={"program": "train"})
    return r


def test_aggregate_snapshots_exactness():
    """Acceptance drill (no jax): two hand-built snapshots merge to
    exactly summed counters and merged histogram buckets/counts/sums,
    with gauges distinguishable per rank."""
    r0 = _rank_registry(5, 0.004, errors=0)
    r1 = _rank_registry(7, 0.04, errors=2)
    merged = aggregate_snapshots([
        {"rank": 0, "snapshot": r0.snapshot()},
        {"rank": 1, "snapshot": r1.snapshot()},
    ])
    assert merged["ranks"] == 2
    assert merged["counters"]["dl4j_train_steps_total"][""] == 12
    assert merged["counters"]["dl4j_serving_errors_total"][
        '{code="503"}'] == 2
    h = merged["histograms"]["dl4j_train_step_seconds"]
    assert h["count"] == 12
    assert h["sum"] == pytest.approx(5 * 0.004 + 7 * 0.04)
    # buckets merged per boundary: 0.004 obs land in le=0.005, 0.04 in
    # le=0.05 (boundary counts are per-bucket, cumulated at render)
    assert h["buckets"]["0.005"] == 5
    assert h["buckets"]["0.05"] == 7
    # per-rank gauges stay distinguishable
    g = merged["gauges"]["dl4j_train_loss"]
    assert g['{program="train",rank="0"}'] == pytest.approx(0.1)
    assert g['{program="train",rank="1"}'] == pytest.approx(0.3)


def test_aggregate_snapshot_files_to_fleet_exposition(tmp_path):
    """Acceptance: ≥2 per-rank snapshot FILES → one fleet-level
    Prometheus exposition (tier-1, no jax)."""
    paths = []
    for rank, (steps, errs) in enumerate([(3, 1), (4, 0), (2, 2)]):
        r = _rank_registry(steps, 0.01, errors=errs)
        p = str(tmp_path / f"metrics-rank{rank}.json")
        dump_snapshot(p, registry=r, rank=rank)
        paths.append(p)
    # dump is torn-read-proof (atomic replace): the file parses
    assert json.loads(open(paths[0]).read())["rank"] == 0
    text = aggregate_prometheus_text(paths)
    assert "dl4j_train_steps_total 9" in text
    assert 'dl4j_serving_errors_total{code="503"} 3' in text
    assert "dl4j_train_step_seconds_count 9" in text
    assert 'dl4j_train_loss{program="train",rank="2"}' in text
    # cumulative bucket counts stay monotonic in the merged exposition
    cums = [int(line.rsplit(" ", 1)[1])
            for line in text.splitlines()
            if line.startswith("dl4j_train_step_seconds_bucket")]
    assert cums == sorted(cums) and cums[-1] == 9


def test_cluster_supervisor_fleet_metrics(tmp_path):
    """The supervisor's rank-0 pull path: per-rank dumps in the
    heartbeat dir merge into one fleet view (no workers spawned)."""
    from deeplearning4j_tpu.resilience.cluster import ClusterSupervisor

    sup = ClusterSupervisor(
        nprocs=2, command_fn=lambda *a: ["true"],
        heartbeat_dir=str(tmp_path))
    assert sup.fleet_metrics() is None   # nothing dumped yet
    for rank in range(2):
        dump_snapshot(
            os.path.join(str(tmp_path), f"metrics-rank{rank}.json"),
            registry=_rank_registry(6, 0.002, errors=0), rank=rank)
    fleet = sup.fleet_metrics()
    assert fleet["ranks"] == 2
    assert fleet["snapshot"]["counters"][
        "dl4j_train_steps_total"][""] == 12
    assert "dl4j_train_steps_total 12" in fleet["prometheus"]
    assert sup.stats()["fleet_metric_ranks"] == 2


# ========================================================= dashboard
def test_dashboard_perf_line_pinned():
    """Satellite pin, PR 8 form: the dashboard's metric-name literals
    are pinned by the dl4j-analyze conformance pass (every dl4j_*
    literal it renders from must be a registered name or prefix), and
    the perf line's exact phrasing is pinned behaviorally below."""
    import pathlib

    import deeplearning4j_tpu
    from deeplearning4j_tpu.analysis import analyze
    from deeplearning4j_tpu.stats.dashboard import telemetry_lines

    pkg = pathlib.Path(deeplearning4j_tpu.__file__).parent
    res = analyze(pkg, root=pkg.parent, tests_dir=None,
                  passes=("conformance",))
    dash = [f for f in res.findings
            if f.file.endswith("stats/dashboard.py")]
    assert not dash, "dashboard conformance: " + "; ".join(
        f.render() for f in dash)

    r = get_registry()
    for _ in range(3):
        r.observe("dl4j_train_phase_seconds", 0.030,
                  labels={"phase": "dispatch"})
    r.observe("dl4j_train_phase_seconds", 0.008,
              labels={"phase": "data_wait"})
    r.observe("dl4j_train_phase_seconds", 0.002,
              labels={"phase": "h2d"})
    r.inc("dl4j_jit_compiles_total", 3)
    joined = "\n".join(telemetry_lines(r))
    assert ("perf — phases dispatch 90%, data_wait 8% · "
            "3 recompiles") in joined
    # empty registry → no perf line
    assert all("perf —" not in line
               for line in telemetry_lines(MetricsRegistry()))


# ============================================== concurrency sanity
def test_jit_cache_forensics_thread_safe():
    """Concurrent calls through the shim never corrupt the ring or
    counters (serving completion threads share the cache)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.jit_cache import JitCache

    cache = JitCache()

    def f(x):
        cache.record_trace("predict")
        return x + 1

    cache["predict"] = jax.jit(f)
    cache["predict"](jnp.ones((2, 2)))   # compile once up front
    barrier = threading.Barrier(4)

    def hammer():
        barrier.wait()
        for _ in range(50):
            cache["predict"](jnp.ones((2, 2)))

    ts = [threading.Thread(target=hammer) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert cache.total_traces() == 1
    assert cache.compiles_total() == 1
    assert len(cache.compile_events()) == 1
