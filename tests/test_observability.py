"""Unified telemetry tests (PR 5 tentpole): MetricsRegistry exactness
under concurrent emission, the metric-name pin (emission sites ==
REGISTERED_METRICS == tested), Prometheus exposition + /metrics e2e,
span tracing with cross-thread parenting (serving completion stage,
StepWatchdog monitor thread), Chrome trace export structure, the
`obs.emit` fault domain (telemetry failures must never break a step or
drop a request), TelemetryListener, dashboard telemetry lines, and
ProfilerListener double-stop hardening."""

import json
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.observability import (
    DERIVED_METRICS,
    MetricsRegistry,
    REGISTERED_METRICS,
    TelemetryListener,
    Tracer,
    count,
    get_registry,
    observe,
    parse_prometheus,
    set_gauge,
)
from deeplearning4j_tpu.resilience import injector

pytestmark = pytest.mark.obs

N_IN, N_OUT, ROWS = 4, 3, 16


@pytest.fixture(autouse=True)
def _fresh_registry():
    """Exact-value assertions need a clean default registry; the
    registry is process-global on purpose (monotonic across servers),
    so tests reset it explicitly."""
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


class _StubNet:
    """No-jax inference stand-in: output() echoes 2*x (new array)."""

    def output(self, x):
        return np.asarray(x) * 2.0


# ===================================================== registry basics
def test_counters_gauges_histograms_roundtrip():
    r = MetricsRegistry()
    r.inc("dl4j_serving_requests_total")
    r.inc("dl4j_serving_requests_total", 2)
    r.inc("dl4j_serving_errors_total", labels={"code": "400"})
    r.inc("dl4j_serving_errors_total", labels={"code": "503"})
    r.set_gauge("dl4j_train_loss", 0.75)
    for v in (0.002, 0.004, 0.2):
        r.observe("dl4j_train_step_seconds", v)
    assert r.counter_value("dl4j_serving_requests_total") == 3
    # labels=None sums the series; a specific label set selects one
    assert r.counter_value("dl4j_serving_errors_total") == 2
    assert r.counter_value("dl4j_serving_errors_total",
                           labels={"code": "400"}) == 1
    assert r.gauge_value("dl4j_train_loss") == 0.75
    snap = r.snapshot()
    h = snap["histograms"]["dl4j_train_step_seconds"]
    assert h["count"] == 3
    assert h["sum"] == pytest.approx(0.206)
    assert h["p50"] == pytest.approx(0.004)
    assert snap["uptime_s"] >= 0.0


def test_gauge_fn_pull_provider_and_failure_swallowed():
    r = MetricsRegistry()
    r.gauge_fn("dl4j_jit_traces_total", lambda: 7)
    assert r.gauge_value("dl4j_jit_traces_total") == 7
    assert 'dl4j_jit_traces_total 7' in r.prometheus_text()
    r.gauge_fn("dl4j_jit_traces_total", lambda: 1 / 0)
    # broken provider: scrape survives, failure counted as dropped
    text = r.prometheus_text()
    assert "dl4j_obs_dropped_emissions_total" in text
    assert r.dropped >= 1


def test_prometheus_exposition_format():
    r = MetricsRegistry()
    r.inc("dl4j_serving_requests_total", 5)
    r.observe("dl4j_serving_request_seconds", 0.003)
    text = r.prometheus_text()
    assert "# TYPE dl4j_serving_requests_total counter" in text
    assert "dl4j_serving_requests_total 5" in text
    assert "# TYPE dl4j_serving_request_seconds histogram" in text
    # cumulative buckets end at +Inf == _count
    assert 'dl4j_serving_request_seconds_bucket{le="+Inf"} 1' in text
    assert "dl4j_serving_request_seconds_count 1" in text
    parsed = parse_prometheus(text)
    assert parsed["dl4j_serving_requests_total"] == 5.0
    assert parsed['dl4j_serving_request_seconds_bucket{le="+Inf"}'] == 1.0


def test_step_accumulator_batches_and_flushes_exactly():
    """The hot-loop accumulator (TrainingMaster/ParallelWrapper per-
    step sites): nothing lands before the flush cadence, everything
    lands exactly at/after it, and totals match per-step emission."""
    from deeplearning4j_tpu.observability import StepAccumulator

    r = get_registry()
    acc = StepAccumulator(flush_every=4)
    for i in range(3):
        acc.count_observe("dl4j_train_steps_total",
                          "dl4j_train_step_seconds", 0.001 * (i + 1))
        acc.observe("dl4j_train_data_wait_seconds", 0.0001)
    # below the cadence: registry untouched
    assert r.counter_value("dl4j_train_steps_total") == 0
    acc.count_observe("dl4j_train_steps_total",
                      "dl4j_train_step_seconds", 0.004)
    # 4th count_observe crossed flush_every: everything flushed
    assert r.counter_value("dl4j_train_steps_total") == 4
    snap = r.snapshot()
    assert snap["histograms"]["dl4j_train_step_seconds"]["count"] == 4
    assert snap["histograms"]["dl4j_train_step_seconds"]["sum"] \
        == pytest.approx(0.01)
    assert snap["histograms"]["dl4j_train_data_wait_seconds"]["count"] \
        == 3
    # explicit flush drains a partial batch (the fit-end path)
    acc.count_observe("dl4j_train_steps_total",
                      "dl4j_train_step_seconds", 0.002, n=3)
    acc.flush()
    assert r.counter_value("dl4j_train_steps_total") == 7
    assert r.dropped == 0


def test_step_accumulator_injected_failure_drops_batch_only():
    from deeplearning4j_tpu.observability import StepAccumulator

    r = get_registry()
    acc = StepAccumulator(flush_every=2)
    injector().inject("obs.emit", times=1)
    acc.count_observe("dl4j_train_steps_total",
                      "dl4j_train_step_seconds", 0.001)
    acc.count_observe("dl4j_train_steps_total",
                      "dl4j_train_step_seconds", 0.001)   # flush raises
    assert r.counter_value("dl4j_train_steps_total") == 0
    assert r.dropped == 1
    # the next batch is unaffected
    acc.count_observe("dl4j_train_steps_total",
                      "dl4j_train_step_seconds", 0.001)
    acc.flush()
    assert r.counter_value("dl4j_train_steps_total") == 1


# ============================================== concurrent exactness
def test_concurrent_emission_exact_totals():
    """Satellite: N threads hammering counters + histograms through the
    GUARDED helpers lose nothing — totals are exact, not approximate."""
    threads, per = 8, 2000
    barrier = threading.Barrier(threads)

    def worker(i):
        barrier.wait()
        for k in range(per):
            count("dl4j_serving_requests_total")
            count("dl4j_serving_errors_total",
                  labels={"code": str(400 + (k % 3))})
            observe("dl4j_serving_request_seconds", 0.001 * (k % 7))

    ts = [threading.Thread(target=worker, args=(i,))
          for i in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    r = get_registry()
    assert r.counter_value("dl4j_serving_requests_total") == threads * per
    assert r.counter_value("dl4j_serving_errors_total") == threads * per
    snap = r.snapshot()
    assert snap["histograms"]["dl4j_serving_request_seconds"]["count"] \
        == threads * per
    assert r.dropped == 0


# ===================================================== metric-name pin
def test_metric_registry_matches_emission_sites_and_tests():
    """Satellite pin, PR 8 form: the hand-written regex scan is
    replaced by the dl4j-analyze conformance pass (one source of truth
    with tools/analyze.py and tier-1's test_static_analysis): every
    emission site registered, every registered non-derived name
    emitted, every telemetry-domain literal resolvable, every
    registered name appears in at least one test."""
    import pathlib

    import deeplearning4j_tpu
    from deeplearning4j_tpu.analysis import analyze

    pkg = pathlib.Path(deeplearning4j_tpu.__file__).parent
    res = analyze(pkg, root=pkg.parent,
                  tests_dir=pathlib.Path(__file__).parent,
                  passes=("conformance",))
    bad = [f for f in res.findings
           if f.rule in ("reg-unregistered-metric",
                         "reg-unemitted-metric")
           or (f.rule == "reg-untested-registry-name"
               and "metric" in f.message)]
    assert not bad, "metric conformance: " + "; ".join(
        f.render() for f in bad)
    # the DERIVED_METRICS carve-out stays honest: derived names are
    # registered but need no call site
    assert set(DERIVED_METRICS) <= set(REGISTERED_METRICS)


def test_registered_metrics_cover_required_names():
    """The names the rest of this file leans on, pinned explicitly so a
    rename cannot slip through via the dynamic scan alone."""
    assert {
        "dl4j_train_steps_total", "dl4j_train_step_seconds",
        "dl4j_train_loss", "dl4j_train_data_wait_seconds",
        "dl4j_checkpoint_write_seconds", "dl4j_checkpoint_writes_total",
        "dl4j_checkpoint_restores_total",
        "dl4j_checkpoint_restore_seconds",
        "dl4j_checkpoint_validate_failures_total",
        "dl4j_serving_requests_total", "dl4j_serving_request_seconds",
        "dl4j_serving_batches_total", "dl4j_serving_batch_occupancy",
        "dl4j_serving_bucket_splits_total",
        "dl4j_serving_queue_depth", "dl4j_serving_inflight_batches",
        "dl4j_jit_traces_total",
        "dl4j_train_guard_nonfinite_total",
        "dl4j_train_guard_spikes_total",
        "dl4j_train_guard_skipped_steps_total",
        "dl4j_train_guard_rollbacks_total",
        "dl4j_train_watchdog_hangs_total",
        "dl4j_train_preemptions_total",
        "dl4j_train_supervisor_restarts_total",
        "dl4j_train_data_skipped_steps_total",
        "dl4j_retry_attempts_total", "dl4j_breaker_transitions_total",
        "dl4j_cluster_gang_restarts_total",
        "dl4j_cluster_quarantined_workers_total",
        # performance introspection (observability/perf.py)
        "dl4j_jit_compiles_total",
        "dl4j_train_phase_seconds",
    } <= set(REGISTERED_METRICS)


# ============================================================= tracer
def test_tracer_implicit_nesting_and_explicit_cross_thread_parent():
    tr = Tracer()
    handoff = {}

    with tr.span("request", cat="serving") as req:
        with tr.span("assemble"):
            pass
        handoff["parent"] = req

    def other_thread():
        sp = tr.begin("complete", cat="serving",
                      parent=handoff["parent"])
        sp.end()

    t = threading.Thread(target=other_thread, name="completer")
    t.start()
    t.join()
    spans = {s["name"]: s for s in tr.spans()}
    assert spans["assemble"]["parent_id"] == spans["request"]["id"]
    assert spans["complete"]["parent_id"] == spans["request"]["id"]
    assert spans["complete"]["tid"] != spans["request"]["tid"]


def test_chrome_trace_export_structure(tmp_path):
    """Perfetto-loadable: X complete events, thread-name metadata, and
    an s/f flow pair binding every cross-thread parent edge."""
    tr = Tracer()
    with tr.span("parent") as par:
        pass

    def child():
        tr.begin("child", parent=par).end()

    t = threading.Thread(target=child, name="worker-thread")
    t.start()
    t.join()
    out = tmp_path / "trace.json"
    doc = tr.export_chrome_trace(str(out))
    loaded = json.loads(out.read_text())
    assert loaded == doc
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"parent", "child"}
    for e in xs:
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert e["pid"] > 0 and e["tid"] > 0
    metas = [e for e in evs if e["ph"] == "M"]
    assert "worker-thread" in {e["args"]["name"] for e in metas}
    flows_s = [e for e in evs if e["ph"] == "s"]
    flows_f = [e for e in evs if e["ph"] == "f"]
    assert len(flows_s) == 1 and len(flows_f) == 1
    assert flows_f[0]["bp"] == "e"
    assert flows_s[0]["id"] == flows_f[0]["id"]
    child_ev = next(e for e in xs if e["name"] == "child")
    parent_ev = next(e for e in xs if e["name"] == "parent")
    assert flows_s[0]["tid"] == parent_ev["tid"]
    assert flows_f[0]["tid"] == child_ev["tid"]


def test_tracer_buffer_is_bounded():
    tr = Tracer(max_spans=10)
    for i in range(25):
        tr.instant(f"e{i}")
    st = tr.stats()
    assert st["buffered"] == 10
    assert st["recorded"] == 25
    assert st["dropped"] == 15
    # oldest dropped, newest kept
    assert tr.spans()[-1]["name"] == "e24"


# ================================== serving pipeline span parenting
def test_pipeline_spans_parent_across_completion_thread():
    """Satellite: request → assemble_dispatch (batcher thread) →
    complete_deliver (completion thread) chain, each hop explicitly
    parented, tids differing across the stage boundary."""
    from deeplearning4j_tpu.parallel.inference import ParallelInference

    tr = Tracer()
    pi = ParallelInference(_StubNet(), batch_limit=4, warmup=False,
                           pipeline_depth=2, max_wait_ms=0.0,
                           tracer=tr)
    try:
        out = pi.output(np.ones((2, 3), np.float32))
        np.testing.assert_allclose(out, 2.0 * np.ones((2, 3)))
    finally:
        pi.shutdown()
    spans = tr.spans()
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    req = by_name["request"][0]
    disp = by_name["assemble_dispatch"][0]
    comp = by_name["complete_deliver"][0]
    assert disp["parent_id"] == req["id"]
    assert comp["parent_id"] == disp["id"]
    # the three phases ran on three different threads
    assert req["tid"] != disp["tid"]
    assert comp["tid"] != disp["tid"]
    assert req["dur_us"] is not None and req["dur_us"] > 0
    # and the export binds the cross-thread hops with flow arrows
    doc = tr.export_chrome_trace()
    assert sum(1 for e in doc["traceEvents"] if e["ph"] == "s") >= 2


def test_watchdog_hang_event_parents_to_step_span():
    """Satellite: the StepWatchdog's MONITOR thread records its hang
    event parented to the training thread's current step span."""
    from deeplearning4j_tpu.resilience import StepWatchdog

    tr = Tracer()
    wd = StepWatchdog(timeout_s=0.15, poll_s=0.05,
                      on_hang=lambda phase, age: None)
    wd.tracer = tr
    step_span = tr.begin("train_step", cat="train", args={"step": 0})
    wd.trace_parent = step_span
    wd.start()
    try:
        deadline = time.monotonic() + 5.0
        while (wd.counters["hangs_detected"] == 0
               and time.monotonic() < deadline):
            time.sleep(0.02)
    finally:
        wd.stop()
        step_span.end()
    assert wd.counters["hangs_detected"] >= 1
    hangs = [s for s in tr.spans() if s["name"] == "watchdog_hang"]
    assert hangs and hangs[0]["parent_id"] == step_span.id
    assert hangs[0]["tid"] != step_span.tid
    assert get_registry().counter_value(
        "dl4j_train_watchdog_hangs_total") >= 1


# ================================================== /metrics e2e
def test_model_server_metrics_and_status_telemetry():
    """Tentpole e2e: POST /predict → GET /metrics serves Prometheus
    text covering the serving domain; /status carries uptime_s and the
    registry-sourced monotonic request/error counters; ModelClient
    exposes the parsed exposition."""
    from deeplearning4j_tpu.parallel.inference import ParallelInference
    from deeplearning4j_tpu.parallel.serving import (
        ModelClient,
        ModelServer,
    )

    tr = Tracer()
    pi = ParallelInference(_StubNet(), batch_limit=4, warmup=False,
                           pipeline_depth=2, max_wait_ms=0.0, tracer=tr)
    server = ModelServer(pi, port=0, tracer=tr).start()
    try:
        client = ModelClient(f"http://127.0.0.1:{server.port}",
                             breaker=None)
        for _ in range(3):
            res = client.predict([[1.0, 2.0, 3.0]])
            assert np.allclose(res["outputs"], [[2.0, 4.0, 6.0]])
        with pytest.raises(Exception):
            client.predict("not-a-matrix")   # 400 → errors counter

        m = client.metrics()
        assert m["dl4j_serving_requests_total"] == 4.0
        assert m['dl4j_serving_errors_total{code="400"}'] == 1.0
        assert m["dl4j_serving_request_seconds_count"] == 3.0
        assert m["dl4j_serving_batches_total"] >= 1.0
        assert "dl4j_serving_queue_depth" in m
        assert "dl4j_jit_traces_total" in m
        assert m["dl4j_serving_batch_occupancy_count"] >= 1.0
        text = client.metrics_text()
        assert "# TYPE dl4j_serving_request_seconds histogram" in text

        st = client.status()
        assert st["uptime_s"] >= 0.0
        assert st["requests_total"] == 4
        assert st["errors_total"] == 1
        assert st["telemetry"]["enabled"] is True
        assert st["telemetry"]["spans"]["recorded"] > 0
    finally:
        server.stop()


# ============================================== obs.emit fault domain
@pytest.mark.chaos
def test_injected_emission_failure_never_breaks_training(tmp_path):
    """`obs.emit` raise armed for EVERY emission: a TrainingMaster fit
    (with checkpointing) still runs to completion, and the failures are
    visible as dropped emissions."""
    from deeplearning4j_tpu.parallel.training_master import (
        TrainingMaster,
    )

    injector().inject("obs.emit", times=10_000_000)
    net = _net()
    tm = TrainingMaster(net, checkpoint_dir=str(tmp_path),
                        checkpoint_every=2)
    tm.fit(lambda s: _batch(s), 3)
    assert injector().hits("obs.emit") > 0
    assert get_registry().dropped > 0
    # nothing landed, nothing crashed
    assert get_registry().counter_value("dl4j_train_steps_total") == 0


@pytest.mark.chaos
def test_injected_emission_failure_never_drops_a_request():
    from deeplearning4j_tpu.parallel.inference import ParallelInference
    from deeplearning4j_tpu.parallel.serving import (
        ModelClient,
        ModelServer,
    )

    injector().inject("obs.emit", times=10_000_000)
    pi = ParallelInference(_StubNet(), batch_limit=4, warmup=False,
                           pipeline_depth=2, max_wait_ms=0.0)
    server = ModelServer(pi, port=0).start()
    try:
        client = ModelClient(f"http://127.0.0.1:{server.port}",
                             breaker=None)
        res = client.predict([[1.0, 1.0, 1.0]])
        assert np.allclose(res["outputs"], [[2.0, 2.0, 2.0]])
    finally:
        server.stop()
    assert get_registry().dropped > 0


# ============================================ training-loop emission
def test_training_master_emits_step_and_checkpoint_metrics(tmp_path):
    from deeplearning4j_tpu.parallel.training_master import (
        TrainingMaster,
    )

    net = _net()
    tr = Tracer()
    tm = TrainingMaster(net, checkpoint_dir=str(tmp_path),
                        checkpoint_every=2, tracer=tr)
    tm.fit(lambda s: _batch(s), 4)
    r = get_registry()
    assert r.counter_value("dl4j_train_steps_total") == 4
    snap = r.snapshot()
    assert snap["histograms"]["dl4j_train_step_seconds"]["count"] == 4
    assert snap["histograms"]["dl4j_train_data_wait_seconds"]["count"] == 4
    assert r.counter_value("dl4j_checkpoint_writes_total") == 2
    assert snap["histograms"]["dl4j_checkpoint_write_seconds"]["count"] == 2
    # resume restores through the instrumented path
    net2 = _net()
    tm2 = TrainingMaster(net2, checkpoint_dir=str(tmp_path))
    tm2.fit(lambda s: _batch(s), 4)
    assert r.counter_value("dl4j_checkpoint_restores_total") >= 1
    # spans: every step recorded, with fetch/dispatch children and the
    # checkpoint save parented to its step span
    names = [s["name"] for s in tr.spans()]
    assert names.count("train_step") == 4
    assert "fetch_and_stage" in names and "dispatch" in names
    ck = [s for s in tr.spans() if s["name"] == "checkpoint_save"]
    steps = {s["id"]: s for s in tr.spans() if s["name"] == "train_step"}
    assert ck and ck[0]["parent_id"] in steps


def test_parallel_wrapper_emits_steps():
    """Every ParallelWrapper step funnels through _run_guarded → one
    emission site covers single-step, local-SGD, and multi-io paths.
    (The local-SGD group path itself needs jax.shard_map, which this
    environment lacks — same pre-existing drift the seed suite
    carries.)"""
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    net = _net()
    pw = ParallelWrapper(net, workers=2)
    x, y = _batch(0)
    pw.fit([(x, y)] * 3)
    r = get_registry()
    assert r.counter_value("dl4j_train_steps_total") == 3
    assert r.snapshot()["histograms"][
        "dl4j_train_step_seconds"]["count"] == 3


def test_telemetry_listener_on_plain_fit():
    net = _net()
    net.listeners.append(TelemetryListener(frequency=2))
    x, y = _batch(1)
    net.fit([(x, y)] * 5)
    r = get_registry()
    assert r.counter_value("dl4j_train_steps_total") == 5
    assert r.gauge_value("dl4j_train_loss") is not None
    snap = r.snapshot()
    assert snap["histograms"]["dl4j_train_step_seconds"]["count"] == 4


def test_guard_counters_land_in_registry():
    """NaN-guard triggers flow to the registry (skip policy drill via
    the existing grad-poison fault)."""
    from deeplearning4j_tpu.parallel.training_master import (
        TrainingMaster,
    )
    from deeplearning4j_tpu.resilience import NonFiniteGuard

    injector().inject("train.grad_nonfinite", at_hit=2)
    net = _net()
    tm = TrainingMaster(net, guard=NonFiniteGuard(policy="skip_step",
                                                  check_every=1))
    tm.fit(lambda s: _batch(s), 3)
    r = get_registry()
    assert r.counter_value("dl4j_train_guard_checks_total") == 3
    assert r.counter_value("dl4j_train_guard_nonfinite_total") == 1
    assert r.counter_value("dl4j_train_guard_skipped_steps_total") == 1
    assert r.gauge_value("dl4j_train_loss") is not None


# ======================================================== dashboard
def test_dashboard_telemetry_lines_pinned():
    """Satellite pin: the self-healing, cluster, and serving lines
    render from a registry snapshot (exact phrasing pinned)."""
    from deeplearning4j_tpu.stats.dashboard import telemetry_lines

    r = get_registry()
    for name, n in (
            ("dl4j_train_guard_checks_total", 5),
            ("dl4j_train_guard_nonfinite_total", 1),
            ("dl4j_train_guard_skipped_steps_total", 1),
            ("dl4j_train_watchdog_hangs_total", 2),
            ("dl4j_train_preemptions_total", 1),
            ("dl4j_train_supervisor_restarts_total", 3),
            ("dl4j_train_data_skipped_steps_total", 1),
            ("dl4j_cluster_gang_restarts_total", 2),
            ("dl4j_cluster_quarantined_workers_total", 1),
            ("dl4j_serving_requests_total", 10),
            ("dl4j_serving_errors_total", 2),
            ("dl4j_serving_batches_total", 4),
    ):
        r.inc(name, n)
    r.set_gauge("dl4j_serving_queue_depth", 3)
    r.observe("dl4j_serving_batch_occupancy", 8)
    lines = telemetry_lines(r)
    joined = "\n".join(lines)
    assert ("self-healing — guard: 5 checks, 1 non-finite, 0 spikes, "
            "1 skipped, 0 rollbacks") in joined
    assert "watchdog: 2 hangs detected" in joined
    assert "preemptions: 1" in joined
    assert "supervisor restarts: 3" in joined
    assert "data-skipped steps: 1" in joined
    assert "cluster — 2 gang restarts · 1 quarantined workers" in joined
    assert "serving — 10 requests (2 errors)" in joined
    assert "queue depth 3" in joined and "4 batches" in joined
    assert "occupancy p50 8" in joined
    # empty registry → no lines at all
    assert telemetry_lines(MetricsRegistry()) == []


# ============================================ retry / breaker metrics
def test_retry_and_breaker_emit():
    from deeplearning4j_tpu.resilience import Retry
    from deeplearning4j_tpu.resilience.retry import CircuitBreaker

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    assert Retry(max_attempts=3, initial_backoff_s=0.001,
                 sleep=lambda s: None).call(flaky) == "ok"
    r = get_registry()
    assert r.counter_value("dl4j_retry_attempts_total") == 2

    br = CircuitBreaker(failure_threshold=1, reset_timeout_s=0.0)
    with pytest.raises(ValueError):
        br.call(lambda: (_ for _ in ()).throw(ValueError("x")))
    assert br.state in ("open", "half_open")
    br.call(lambda: "fine")   # half-open probe succeeds → closed
    assert r.counter_value("dl4j_breaker_transitions_total",
                           labels={"to": "open"}) == 1
    assert r.counter_value("dl4j_breaker_transitions_total",
                           labels={"to": "closed"}) == 1


def test_checkpoint_validate_failure_emits(tmp_path):
    from deeplearning4j_tpu.resilience import checkpoint_integrity as ci

    p = tmp_path / "f.bin"
    p.write_bytes(b"hello")
    ci.record_checksum(str(tmp_path), "f.bin",
                       ci.sha256_file(str(p)), 5)
    assert ci.validate_file(str(tmp_path), "f.bin")
    p.write_bytes(b"h3llo")   # same size, torn content
    assert not ci.validate_file(str(tmp_path), "f.bin")
    assert get_registry().counter_value(
        "dl4j_checkpoint_validate_failures_total") == 1


# ===================================== profiler listener hardening
def test_profiler_listener_double_stop_guard(monkeypatch):
    """Satellite: overlapping epoch-end / abort / __del__ paths call
    stop() freely — jax.profiler.stop_trace runs exactly once, and the
    device-trace window registers on the shared timeline."""
    import jax

    from deeplearning4j_tpu.optimize.listeners import ProfilerListener

    calls = {"start": 0, "stop": 0}
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.__setitem__(
                            "start", calls["start"] + 1))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.__setitem__(
                            "stop", calls["stop"] + 1))

    class _Model:
        def score(self):
            return 0.5

    tr = Tracer()
    pl = ProfilerListener("/tmp/prof_test", start_iteration=1,
                          num_iterations=1, tracer=tr)
    m = _Model()
    pl.iteration_done(m, 0)
    assert calls["start"] == 0
    pl.iteration_done(m, 1)          # starts the trace
    assert calls["start"] == 1 and pl._active
    pl.iteration_done(m, 2)          # stops it
    assert calls["stop"] == 1 and not pl._active
    assert pl.trace_dir == "/tmp/prof_test"
    # overlapping epoch-end + explicit stop + __del__: all no-ops now
    pl.on_epoch_end(m)
    pl.stop()
    pl.__del__()
    assert calls["stop"] == 1
    spans = [s for s in tr.spans() if s["name"] == "jax_device_trace"]
    assert spans and spans[0]["args"]["trace_dir"] == "/tmp/prof_test"


def test_trace_dir_surfaces_through_training_stats(monkeypatch):
    import jax

    from deeplearning4j_tpu.optimize.listeners import ProfilerListener
    from deeplearning4j_tpu.parallel.training_master import (
        TrainingMaster,
    )

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    net = _net()
    pl = ProfilerListener("/tmp/prof_tm", start_iteration=1,
                          num_iterations=1)
    net.listeners.append(pl)
    tm = TrainingMaster(net)
    tm.fit(lambda s: _batch(s), 3)
    prof = tm.training_stats()["profiler"]
    assert prof is not None
    assert prof["trace_dir"] == "/tmp/prof_tm"
    assert prof["done"] is True and prof["active"] is False


# ================================================== off-switch cost
def test_enable_false_suppresses_everything():
    from deeplearning4j_tpu.observability import enable, telemetry_enabled

    enable(False)
    try:
        assert not telemetry_enabled()
        count("dl4j_serving_requests_total")
        observe("dl4j_train_step_seconds", 0.1)
        set_gauge("dl4j_train_loss", 1.0)
        r = get_registry()
        assert r.counter_value("dl4j_serving_requests_total") == 0
        assert r.gauge_value("dl4j_train_loss") is None
    finally:
        enable(True)
