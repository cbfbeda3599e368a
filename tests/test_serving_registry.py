"""Multi-model serving control plane (PR 6 tentpole): ModelRegistry
lifecycle (verified loads, zero-downtime hot-swap, rollback, retire),
tenant admission (token buckets, priority shedding — shed lowest class
first), ReplicaRouter (least-outstanding picking + failover), the
/v1/models HTTP surface, multi-input/dict coalescing, the multi-stream
completion stage, and the new per-tenant/per-model metrics.

The centerpiece chaos drill hot-swaps a version mid-soak (and rejects a
corrupted upload) while clients hammer /v1/models/<name>/predict —
zero failed requests, zero mixed-version responses."""

import json
import os
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf import InputType
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.parallel.inference import ParallelInference
from deeplearning4j_tpu.parallel.serving import ModelClient, ModelServer
from deeplearning4j_tpu.resilience import (
    CheckpointIntegrityError,
    CircuitBreaker,
    ModelNotFoundError,
    NoHealthyReplicaError,
    QuotaExceededError,
    Retry,
    ServingError,
)
from deeplearning4j_tpu.serving import (
    AdmissionController,
    ModelRegistry,
    ReplicaRouter,
    TenantConfig,
    TokenBucket,
)
from deeplearning4j_tpu.util import model_serializer

pytestmark = pytest.mark.serving


def _net(seed=7, n_in=8, n_out=6):
    conf = (NeuralNetConfiguration.Builder().seed(seed).updater("sgd")
            .learning_rate(0.1).activation("tanh").weight_init("xavier")
            .list()
            .layer(DenseLayer(n_out=16))
            .layer(OutputLayer(n_out=n_out, loss="mcxent"))
            .set_input_type(InputType.feed_forward(n_in))
            .build())
    return MultiLayerNetwork(conf).init()


def _two_input_graph(seed=3):
    conf = (NeuralNetConfiguration.Builder().seed(seed).updater("sgd")
            .learning_rate(0.1).activation("tanh").weight_init("xavier")
            .graph_builder()
            .add_inputs("a", "b")
            .set_input_types(a=InputType.feed_forward(4),
                             b=InputType.feed_forward(3))
            .add_layer("da", DenseLayer(n_out=8), "a")
            .add_layer("db", DenseLayer(n_out=8), "b")
            .add_layer("out", OutputLayer(n_out=5, loss="mcxent"),
                       "da", "db")
            .set_outputs("out").build())
    return ComputationGraph(conf).init()


class _EchoNet:
    """Synchronous echo stub; optional per-dispatch delay."""

    def __init__(self, delay_s=0.0):
        self.delay_s = delay_s

    def output(self, x):
        if self.delay_s:
            time.sleep(self.delay_s)
        return np.asarray(x)


class _MultiIONet:
    """Two-input/two-output echo stub: output(a, b) -> [a, b]."""

    def output(self, a, b):
        return [np.asarray(a), np.asarray(b)]


def _no_retry_client(port, **kw):
    return ModelClient(f"http://127.0.0.1:{port}",
                       retry=Retry(max_attempts=1), breaker=None, **kw)


# ================================================= registry lifecycle
def test_registry_register_swap_rollback_retire():
    reg = ModelRegistry(batch_limit=4, warmup=False, max_wait_ms=0.0)
    try:
        v1 = reg.register("m", _EchoNet())
        assert v1 == "v1"
        e = reg.entry("m")
        with e.lease() as (ver, pi):
            assert ver == "v1"
            np.testing.assert_allclose(
                pi.output(np.ones((1, 2), np.float32)), 1.0)
        v2 = reg.register("m", _EchoNet())
        assert v2 == "v2" and e.active == "v2" and e.previous == "v1"
        assert e.versions["v1"].state == "standby"
        # rollback flips back to the still-warm previous version
        assert reg.rollback("m") == "v1"
        assert e.active == "v1" and e.previous == "v2"
        with e.lease() as (ver, _):
            assert ver == "v1"
        # deleting the ACTIVE version is a lifecycle conflict
        with pytest.raises(ValueError, match="active"):
            reg.delete_version("m", "v1")
        reg.delete_version("m", "v2")
        deadline = time.monotonic() + 5.0
        while (e.versions.get("v2") is not None
               or "v2" in e.versions) and time.monotonic() < deadline:
            time.sleep(0.01)
        assert "v2" not in e.versions
        with pytest.raises(ModelNotFoundError):
            reg.entry("nope")
        with pytest.raises(ModelNotFoundError):
            reg.rollback("m")   # previous was deleted
    finally:
        reg.shutdown()


def test_registry_load_rejects_corrupted_upload(tmp_path):
    """The integrity gate: a corrupted/torn model zip can NEVER become
    a servable version."""
    reg = ModelRegistry(batch_limit=4, warmup=False)
    try:
        # torn bytes behind a stale sha256 sidecar
        bad = tmp_path / "bad.zip"
        bad.write_bytes(b"not a zip at all")
        (tmp_path / "bad.zip.sha256").write_text("0" * 64)
        with pytest.raises(CheckpointIntegrityError):
            reg.load_version("m", "v1", str(bad))
        # a real model written atomically, then truncated after the
        # sidecar was recorded (the classic torn write)
        good = tmp_path / "good.zip"
        model_serializer.write_model(_net(), str(good))
        raw = good.read_bytes()
        good.write_bytes(raw[:len(raw) // 2])
        with pytest.raises(CheckpointIntegrityError):
            reg.load_version("m", "v1", str(good))
        assert reg.model_names() == ["m"] \
            and reg.entry("m").versions == {}
        # the versionless entry left by the rejected upload must NOT
        # gate liveness: a PUT of a bad zip to a fresh name flipping
        # /healthz 503 would get the pod killed by its liveness probe
        reg.register("live", _EchoNet())
        assert reg.healthy()
    finally:
        reg.shutdown()


def test_registry_load_version_and_auto_model_type(tmp_path):
    reg = ModelRegistry(batch_limit=4)
    try:
        net = _net(seed=5)
        p = tmp_path / "m.zip"
        model_serializer.write_model(net, str(p))
        reg.load_version("m", "v1", str(p))
        x = np.random.default_rng(0).normal(size=(2, 8)) \
            .astype(np.float32)
        with reg.entry("m").lease() as (ver, pi):
            np.testing.assert_allclose(
                pi.output(x), np.asarray(net.output(x)),
                rtol=1e-4, atol=1e-5)
    finally:
        reg.shutdown()


# =============================================== hot-swap chaos soak
@pytest.mark.chaos
def test_hot_swap_mid_soak_zero_failed_zero_mixed(tmp_path):
    """THE acceptance drill: clients hammer /v1/models/m/predict while
    v2 is hot-swapped in (a verified upload) and a corrupted upload is
    rejected. Every request succeeds, and every response was computed
    END TO END by exactly one version (outputs match that version's
    reference bit-for-bit tolerance)."""
    net1, net2 = _net(seed=1), _net(seed=2)
    x = np.random.default_rng(0).normal(size=(2, 8)).astype(np.float32)
    refs = {"v1": np.asarray(net1.output(x)),
            "v2": np.asarray(net2.output(x))}
    p2 = tmp_path / "m2.zip"
    model_serializer.write_model(net2, str(p2))
    bad = tmp_path / "bad.zip"
    bad.write_bytes(b"corrupted upload bytes")
    (bad.parent / "bad.zip.sha256").write_text("f" * 64)

    server = ModelServer(net1, model_name="m", queue_limit=256).start()
    stop = threading.Event()
    failures, responses = [], []
    lock = threading.Lock()

    def hammer():
        client = _no_retry_client(server.port)
        while not stop.is_set():
            try:
                t_sent = time.perf_counter()
                r = client.predict(x, model="m")
                t_back = time.perf_counter()
                with lock:
                    responses.append(
                        (r["version"],
                         np.asarray(r["outputs"], np.float32),
                         t_sent, t_back))
            except Exception as e:   # noqa: BLE001 - recorded, asserted 0
                with lock:
                    failures.append(repr(e))

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    try:
        for t in threads:
            t.start()
        time.sleep(0.4)
        admin = _no_retry_client(server.port)
        # corrupted upload mid-soak: REJECTED, traffic unaffected
        with pytest.raises(ServingError) as ei:
            admin.put_version("m", "vbad", str(bad))
        assert ei.value.status == 409
        assert ei.value.error_class == "CheckpointIntegrityError"
        # the real hot-swap
        admin.put_version("m", "v2", str(p2))
        time.sleep(0.4)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
        server.stop()

    assert failures == [], f"requests failed during swap: {failures[:5]}"
    assert len(responses) > 50
    seen = {r[0] for r in responses}
    assert seen == {"v1", "v2"}, f"swap never took traffic: {seen}"
    for version, out, _, _ in responses:
        # a mixed-version response would match NEITHER reference
        np.testing.assert_allclose(out, refs[version],
                                   rtol=1e-4, atol=1e-5)
    # order sanity: once v2 has answered, v1 never comes back (no
    # flapping). By each request's own clock, not by the order the
    # four threads reached the list: a thread that holds a v1 answer
    # can lose the CPU before it appends it.
    first_v2_back = min(t_back for v, _, _, t_back in responses
                        if v == "v2")
    assert not [t_sent - first_v2_back for v, _, t_sent, _ in responses
                if v == "v1" and t_sent > first_v2_back]


# ==================================================== tenant admission
def test_token_bucket_refills():
    tb = TokenBucket(rate=100.0, burst=2)
    assert tb.try_take() and tb.try_take()
    assert not tb.try_take()          # burst spent
    assert 0.0 < tb.retry_after_s() <= 1.0
    time.sleep(0.03)                  # 100/s refills ~3 tokens worth
    assert tb.try_take()


def test_admission_sheds_lowest_class_first():
    """Exact shed semantics, no timing: under rising queue pressure
    the LOW class sheds at 50%, NORMAL at 85%, HIGH only never
    (the bounded queue itself is high's only limit)."""
    adm = AdmissionController({
        "gold": TenantConfig("gold", priority="high"),
        "silver": TenantConfig("silver", priority="normal"),
        "bronze": TenantConfig("bronze", priority="low"),
    })
    limit = 100
    for depth, admitted in [(0, {"gold", "silver", "bronze"}),
                            (50, {"gold", "silver"}),
                            (85, {"gold"}),
                            (99, {"gold"})]:
        for tenant in ("gold", "silver", "bronze"):
            if tenant in admitted:
                adm.admit(tenant, "m", depth, limit)
            else:
                with pytest.raises(QuotaExceededError):
                    adm.admit(tenant, "m", depth, limit)
    stats = adm.stats()
    assert stats["admitted"] == 7 and stats["shed_pressure"] == 5


def test_admission_quota_over_http_and_retry_after():
    server = ModelServer(_EchoNet(), tenants={
        "burst2": {"rate": 0.5, "burst": 2, "priority": "normal"},
        "vip": {"priority": "high"},
    }).start()
    try:
        client = _no_retry_client(server.port)
        x = [[1.0, 2.0]]
        # binary wire: outputs come back as numpy arrays, so assert on
        # size rather than (ambiguous) array truthiness
        assert np.asarray(client.predict(x, tenant="burst2")["outputs"]).size
        assert np.asarray(client.predict(x, tenant="burst2")["outputs"]).size
        with pytest.raises(ServingError) as ei:
            client.predict(x, tenant="burst2")
        assert ei.value.status == 429
        assert ei.value.error_class == "QuotaExceededError"
        assert ei.value.retry_after_s >= 1
        # vip is unmetered; unknown tenants fall back to default
        assert np.asarray(client.predict(x, tenant="vip")["outputs"]).size
        assert np.asarray(client.predict(x)["outputs"]).size
        st = client.status()
        assert st["admission"]["shed_quota"] == 1
    finally:
        server.stop()


@pytest.mark.chaos
def test_overload_sheds_mostly_lowest_class():
    """Integration mini-soak: under sustained overload of a slow model
    with a small bounded queue, pressure shedding lands on the lowest
    priority class first — gold keeps flowing."""
    server = ModelServer(
        _EchoNet(delay_s=0.004), batch_limit=2, queue_limit=8,
        max_wait_ms=0.0, tenants={
            "gold": {"priority": "high"},
            "silver": {"priority": "normal"},
            "bronze": {"priority": "low"},
        }).start()
    counts = {t: {"ok": 0, "shed": 0}
              for t in ("gold", "silver", "bronze")}
    lock = threading.Lock()
    stop = threading.Event()

    def load(tenant):
        client = _no_retry_client(server.port)
        x = [[1.0, 2.0]]
        while not stop.is_set():
            try:
                client.predict(x, tenant=tenant)
                with lock:
                    counts[tenant]["ok"] += 1
            except ServingError as e:
                assert e.status in (429, 503)
                with lock:
                    counts[tenant]["shed"] += 1

    threads = [threading.Thread(target=load, args=(t,))
               for t in ("gold", "silver", "bronze") for _ in range(4)]
    try:
        for t in threads:
            t.start()
        time.sleep(1.5)
        # on a loaded box the clients can take longer than that to
        # fill the queue: soak on until the first shed, within reason
        deadline = time.monotonic() + 10.0
        while (counts["bronze"]["shed"] == 0
               and time.monotonic() < deadline):
            time.sleep(0.25)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
        server.stop()

    assert counts["gold"]["ok"] > 0 and counts["bronze"]["shed"] > 0

    def shed_rate(tenant):
        total = counts[tenant]["ok"] + counts[tenant]["shed"]
        return counts[tenant]["shed"] / max(1, total)

    # lowest class absorbs the highest shed FRACTION, highest the
    # least. (Per-attempt rates, not absolute counts: with PR 10's
    # priority-aware dequeue an admitted bronze request also WAITS
    # longest, so these closed-loop generators attempt bronze less
    # often and absolute counts no longer order reliably — the
    # admission thresholds order the per-attempt probability by
    # construction.)
    assert shed_rate("bronze") >= shed_rate("silver") \
        >= shed_rate("gold")


# ===================================================== replica router
class _StubReplicaClient:
    """ModelClient stand-in: scripted failures, call recording."""

    def __init__(self, url, fail=0, exc=ConnectionError):
        self.url = url
        self.breaker = CircuitBreaker(failure_threshold=3,
                                      reset_timeout_s=60.0)
        self.calls = 0
        self._fail = fail
        self._exc = exc

    def predict(self, inputs, decode_top=0, model=None, tenant=None):
        self.calls += 1
        if self.calls <= self._fail:
            self.breaker.record_failure()
            raise self._exc(f"{self.url} down")
        self.breaker.record_success()
        return {"outputs": [[1.0]], "url": self.url}


def test_router_least_outstanding_and_failover():
    clients = {}

    def factory(url):
        clients[url] = _StubReplicaClient(url,
                                          fail=4 if "bad" in url else 0)
        return clients[url]

    router = ReplicaRouter(["http://bad:1", "http://ok-a:1",
                            "http://ok-b:1"], client_factory=factory)
    for _ in range(6):
        assert router.predict([[1.0]])["outputs"]
    st = router.stats()
    by_url = {r["url"]: r for r in st["replicas"]}
    # the dead replica was failed over, its breaker opened after 3
    # counted failures, and it was SKIPPED thereafter (3 calls, not 6)
    assert clients["http://bad:1"].calls == 3
    assert by_url["http://bad:1"]["breaker"] == "open"
    assert st["failovers"] == 3
    # survivors share the load
    assert clients["http://ok-a:1"].calls >= 2
    assert clients["http://ok-b:1"].calls >= 2
    assert sum(c.calls for c in clients.values()) == 6 + 3


def test_router_no_healthy_replica():
    router = ReplicaRouter(
        ["http://a:1", "http://b:1"],
        client_factory=lambda u: _StubReplicaClient(u, fail=10 ** 9))
    with pytest.raises(NoHealthyReplicaError) as ei:
        router.predict([[1.0]])
    assert isinstance(ei.value.cause, ConnectionError)
    # breakers opened; the next call cannot even pick a replica
    with pytest.raises(NoHealthyReplicaError):
        router.predict([[1.0]])


def test_router_non_retryable_errors_surface_immediately():
    class _Client400(_StubReplicaClient):
        def predict(self, *a, **kw):
            self.calls += 1
            raise ServingError(status=400, message="bad inputs")

    made = {}

    def factory(url):
        made[url] = _Client400(url)
        return made[url]

    router = ReplicaRouter(["http://a:1", "http://b:1"],
                           client_factory=factory)
    with pytest.raises(ServingError) as ei:
        router.predict([[1.0]])
    assert ei.value.status == 400
    # a 400 proves the server answered: NO failover was attempted
    assert sum(c.calls for c in made.values()) == 1


def test_router_against_real_servers():
    s1 = ModelServer(_EchoNet()).start()
    s2 = ModelServer(_EchoNet()).start()
    try:
        router = ReplicaRouter(
            [f"http://127.0.0.1:{s1.port}", "http://127.0.0.1:9",
             f"http://127.0.0.1:{s2.port}"],
            client_factory=lambda u: ModelClient(
                u, timeout=2.0, retry=Retry(max_attempts=1)))
        for i in range(6):
            r = router.predict([[float(i), 0.0]])
            assert r["outputs"][0][0] == float(i)
        st = router.stats()
        live = [r for r in st["replicas"] if ":9" not in r["url"]]
        assert all(r["requests"] >= 2 for r in live)
        assert st["failovers"] >= 1   # the dead replica was skipped over
    finally:
        s1.stop()
        s2.stop()


# ===================================== multi-input / dict coalescing
def test_multi_input_graph_batches_through_pooled_buckets():
    g = _two_input_graph()
    pi = ParallelInference(g, batch_limit=8, max_wait_ms=5.0)
    try:
        # warmup derived per-input shapes from the graph conf
        assert pi.stats()["warmed_buckets"] == [1, 2, 4, 8]
        # the in-loop DIRECT g.output reference calls use raw (non-pow2)
        # batch sizes and share g's jit cache — trace them now so `base`
        # isolates the pi path
        for n in range(1, 6):
            np.asarray(g.output(np.zeros((n, 4), np.float32),
                                np.zeros((n, 3), np.float32)))
        base = pi.trace_stats()["total_traces"]
        rng = np.random.default_rng(0)
        import concurrent.futures as cf

        def one(seed):
            r = np.random.default_rng(seed)
            n = int(r.integers(1, 6))
            a = r.normal(size=(n, 4)).astype(np.float32)
            b = r.normal(size=(n, 3)).astype(np.float32)
            out = pi.output(a, b)
            np.testing.assert_allclose(
                out, np.asarray(g.output(a, b)), rtol=1e-4, atol=1e-5)
            return n

        with cf.ThreadPoolExecutor(8) as ex:
            sizes = list(ex.map(one, range(24)))
        assert sum(sizes) > 24
        # the PR 2 compile-once property holds for multi-input batches
        assert pi.trace_stats()["total_traces"] == base
        assert pi.stats()["batches_dispatched"] < 24   # coalesced
    finally:
        pi.shutdown()


def test_multi_input_split_and_multi_output_reassembly():
    """An oversized multi-input request splits across buckets and both
    OUTPUT streams reassemble per caller, resolving as a list."""
    pi = ParallelInference(_MultiIONet(), batch_limit=8, warmup=False,
                           max_wait_ms=0.0)
    try:
        a = np.arange(20 * 4, dtype=np.float32).reshape(20, 4)
        b = np.arange(20 * 3, dtype=np.float32).reshape(20, 3) * -1.0
        out = pi.output(a, b)
        assert isinstance(out, list) and len(out) == 2
        np.testing.assert_allclose(out[0], a)
        np.testing.assert_allclose(out[1], b)
        with pytest.raises(ValueError, match="batch dim"):
            pi.output(a, b[:3])
    finally:
        pi.shutdown()


def test_dict_inputs_over_http_ordered_by_graph():
    g = _two_input_graph()
    server = ModelServer(g, model_name="two-tower").start()
    try:
        client = _no_retry_client(server.port)
        rng = np.random.default_rng(1)
        a = rng.normal(size=(3, 4)).astype(np.float32)
        b = rng.normal(size=(3, 3)).astype(np.float32)
        r = client.predict({"a": a, "b": b}, model="two-tower")
        np.testing.assert_allclose(
            np.asarray(r["outputs"], np.float32),
            np.asarray(g.output(a, b)), rtol=1e-4, atol=1e-5)
        with pytest.raises(ServingError) as ei:
            client.predict({"a": a}, model="two-tower")
        assert ei.value.status == 400
        assert "missing named inputs" in ei.value.message
    finally:
        server.stop()


# ================================== multi-stream completion (PR 2 gap)
def test_completion_stage_fetches_concurrently():
    """k=2 completion streams pay two host-fetch RTTs AT ONCE: both
    in-flight batches enter __array__ before either finishes. With the
    old single completer the second fetch could only start after the
    first returned, and this barrier would time out."""
    barrier = threading.Barrier(2)
    entered = []

    class _BarrierNet:
        def output(self, x):
            arr = np.asarray(x)

            class _V:
                def __array__(self, dtype=None):
                    entered.append(time.monotonic())
                    barrier.wait(timeout=10.0)   # needs BOTH fetchers
                    return arr if dtype is None else arr.astype(dtype)

            return _V()

    pi = ParallelInference(_BarrierNet(), batch_limit=1, warmup=False,
                           max_wait_ms=0.0, pipeline_depth=2,
                           completion_streams=2, default_timeout_s=15.0)
    try:
        results = []
        threads = [threading.Thread(
            target=lambda i=i: results.append(
                pi.output(np.full((1, 4), float(i), np.float32))))
            for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=15.0)
        assert len(results) == 2 and len(entered) == 2
        assert pi.stats()["completion_streams"] == 2
    finally:
        pi.shutdown()


def test_blocking_mode_has_no_completion_streams():
    pi = ParallelInference(_EchoNet(), batch_limit=2, warmup=False,
                           max_wait_ms=0.0, pipeline_depth=0)
    try:
        np.testing.assert_allclose(
            pi.output(np.ones((1, 3), np.float32)), 1.0)
        assert pi.stats()["completion_streams"] == 0
        assert pi._completer is None
    finally:
        pi.shutdown()


# ============================== continuous span flush (PR 5 gap close)
@pytest.mark.obs
def test_tracer_background_flush_drains_ring(tmp_path):
    from deeplearning4j_tpu.observability import Tracer

    path = str(tmp_path / "spans.jsonl")
    tr = Tracer(max_spans=8, flush_path=path, flush_interval_s=0.05)
    for i in range(100):
        with tr.span(f"s{i}", cat="test"):
            pass
    written = tr.stop_background_flush()
    assert written >= 0
    spans = Tracer.load_flushed(path)
    st = tr.stats()
    # ring holds 8; the continuous flush kept ALL 100 (pressure flush
    # beats ring wrap-around)
    assert len(spans) == 100 and st["dropped"] == 0, st
    assert {s["name"] for s in spans} == {f"s{i}" for i in range(100)}
    assert all(s["dur_us"] is not None for s in spans)
    # flush-on-stop is idempotent and restartable
    assert tr.stop_background_flush() == 0
    tr.start_background_flush(path, interval_s=0.05)
    with tr.span("late"):
        pass
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        if any(s["name"] == "late" for s in Tracer.load_flushed(path)):
            break
        time.sleep(0.02)
    else:
        pytest.fail("interval flush never wrote the late span")
    tr.stop_background_flush()


# ===================== heartbeat lease embedded wall-clock (PR 4 gap)
def test_heartbeat_age_uses_embedded_time_on_coarse_mtime(tmp_path):
    """Forced-coarse-mtime drill: the record's embedded wall clock
    keeps the lease fresh even when the filesystem reports an ancient
    mtime (NFS coarse-granularity shape); torn records fall back to
    mtime so any write still proves liveness."""
    from deeplearning4j_tpu.resilience.cluster import HeartbeatFile

    path = str(tmp_path / "hb.json")
    hb = HeartbeatFile(path, min_interval_s=0.0)
    hb.write(step=3, force=True)
    # simulate coarse/skewed mtime: the fs says the file is 120s old
    old = time.time() - 120.0
    os.utime(path, (old, old))
    age = HeartbeatFile.age_s(path)
    assert age is not None and age < 5.0, \
        f"embedded record time ignored; mtime fallback won: {age}"
    # torn record: mtime is the only signal left
    with open(path, "w") as f:
        f.write("{torn json")
    os.utime(path, (old, old))
    age = HeartbeatFile.age_s(path)
    assert age is not None and age > 100.0
    # future-skewed record time: fall back to mtime, never negative
    with open(path, "w") as f:
        json.dump({"pid": 1, "time": time.time() + 999.0}, f)
    os.utime(path, (old, old))
    age = HeartbeatFile.age_s(path)
    assert age is not None and age > 100.0
    assert HeartbeatFile.age_s(str(tmp_path / "missing")) is None


# ========================================= metrics: per-tenant/model
def test_new_metrics_registered():
    """Pin: the control-plane metric names ride REGISTERED_METRICS (the
    dynamic emission-site scan in test_observability enforces the
    rest)."""
    from deeplearning4j_tpu.observability import REGISTERED_METRICS

    assert {
        "dl4j_serving_model_requests_total",
        "dl4j_serving_admitted_total",
        "dl4j_serving_shed_total",
        "dl4j_serving_swaps_total",
        "dl4j_serving_rollbacks_total",
        "dl4j_serving_load_rejected_total",
        "dl4j_serving_active_models",
        "dl4j_serving_replica_failovers_total",
    } <= set(REGISTERED_METRICS)


def test_per_tenant_per_model_metrics_on_scrape(tmp_path):
    """GET /metrics carries the new control-plane series WITH labels:
    per-model/per-version request counts, per-tenant admission and
    shed counts, swap/rollback/rejected-load counters."""
    net2 = _net(seed=9)
    p2 = tmp_path / "v2.zip"
    model_serializer.write_model(net2, str(p2))
    bad = tmp_path / "bad.zip"
    bad.write_bytes(b"garbage")
    (tmp_path / "bad.zip.sha256").write_text("0" * 64)

    server = ModelServer(_net(seed=8), model_name="m", tenants={
        "gold": {"priority": "high"},
        "bronze": {"rate": 1.0, "burst": 1, "priority": "low"},
    }).start()
    try:
        client = _no_retry_client(server.port)
        x = np.zeros((1, 8), np.float32)
        client.predict(x, model="m", tenant="gold")
        client.predict(x, model="m", tenant="bronze")
        with pytest.raises(ServingError):        # bronze quota burst=1
            client.predict(x, model="m", tenant="bronze")
        with pytest.raises(ServingError):        # corrupt upload
            client.put_version("m", "vbad", str(bad))
        client.put_version("m", "v2", str(p2))   # swap
        client.predict(x, model="m", tenant="gold")
        client.rollback("m")

        m = client.metrics()
        mk = 'dl4j_serving_model_requests_total' \
             '{model="m",version="%s"}'
        assert m[mk % "v1"] >= 2
        assert m[mk % "v2"] >= 1
        assert m['dl4j_serving_admitted_total'
                 '{priority="high",tenant="gold"}'] >= 2
        assert m['dl4j_serving_shed_total'
                 '{priority="low",reason="quota",tenant="bronze"}'] >= 1
        assert m['dl4j_serving_swaps_total{model="m"}'] >= 1
        assert m['dl4j_serving_rollbacks_total{model="m"}'] >= 1
        assert m['dl4j_serving_load_rejected_total{model="m"}'] >= 1
        assert m['dl4j_serving_active_models'] >= 1
    finally:
        server.stop()

    # the router counter is registered + emitted on its own path
    router = ReplicaRouter(
        ["http://a:1", "http://b:1"],
        client_factory=lambda u: _StubReplicaClient(
            u, fail=1 if "//a:" in u else 0))
    router.predict([[1.0]])
    from deeplearning4j_tpu.observability import get_registry

    assert get_registry().counter_value(
        "dl4j_serving_replica_failovers_total") >= 1


# ======================================= compat: single-model surface
def test_single_model_compat_surface_unchanged():
    """The PR 1-5 single-model constructor is a thin wrapper over the
    registry: /predict, /status shape, and pre-built-ParallelInference
    ownership semantics all survive."""
    net = _net()
    server = ModelServer(net).start()
    try:
        client = ModelClient(f"http://127.0.0.1:{server.port}")
        x = np.random.default_rng(2).normal(size=(3, 8)) \
            .astype(np.float32)
        r = client.predict(x)
        np.testing.assert_allclose(
            np.asarray(r["outputs"], np.float32),
            np.asarray(net.output(x)), rtol=1e-4, atol=1e-5)
        assert r["model"] == "default" and r["version"] == "v1"
        st = client.status()
        assert st["model"] == "MultiLayerNetwork"
        assert st["models"] == ["default"]
        assert st["pipeline"]["pipeline_depth"] == 2
        assert server.pi is not None and server.pi.healthy
    finally:
        server.stop()

    # caller-supplied ParallelInference is NOT shut down by the server
    pi = ParallelInference(_EchoNet(), batch_limit=2, warmup=False,
                           max_wait_ms=0.0)
    server = ModelServer(pi).start()
    server.stop()
    assert pi.healthy
    np.testing.assert_allclose(
        pi.output(np.ones((1, 2), np.float32)), 1.0)
    pi.shutdown()
