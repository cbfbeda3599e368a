"""Resilience subsystem: fault injection, crash-safe checkpoints, and
graceful degradation on the serving path.

The SURVEY (§5.3) asserts "a killed job relaunches with the same
arguments and resumes from the latest checkpoint"; these tests are the
first to actually kill something and check. Chaos cases are driven by
the deterministic FaultInjector (resilience/faults.py) — the same
mechanism an operator can arm via DL4J_TPU_FAULTS."""

import os
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf import InputType
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.resilience import (
    CheckpointIntegrityError,
    CircuitBreaker,
    CircuitOpenError,
    DeadlineExceededError,
    FaultInjectedError,
    FaultInjector,
    InferenceUnavailableError,
    OverloadedError,
    RetriesExhaustedError,
    Retry,
    ServingError,
    ShutdownError,
    apply_retention,
    atomic_writer,
    injector,
    newest_valid_checkpoint,
    record_checksum,
    sha256_file,
    validate_file,
)


def _net(seed=3, n_in=4, n_out=3):
    conf = (NeuralNetConfiguration.Builder().seed(seed).updater("sgd")
            .learning_rate(0.05).activation("tanh").weight_init("xavier")
            .list()
            .layer(DenseLayer(n_out=8))
            .layer(OutputLayer(n_out=n_out, loss="mcxent"))
            .set_input_type(InputType.feed_forward(n_in))
            .build())
    return MultiLayerNetwork(conf).init()


def _data(steps=20, rows=8, n_in=4, n_out=3, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(steps, rows, n_in)).astype(np.float32)
    Y = np.eye(n_out, dtype=np.float32)[
        rng.integers(0, n_out, size=(steps, rows))]
    return lambda s: (X[s % steps], Y[s % steps])


def _params_of(net):
    import jax
    return [np.asarray(leaf) for leaf in
            jax.tree_util.tree_leaves(net.params)]


# ===================================================== fault injector
def test_fault_injector_is_deterministic():
    inj = FaultInjector()
    inj.inject("p", mode="raise", at_hit=3)
    inj.fire("p")
    inj.fire("p")
    with pytest.raises(FaultInjectedError) as ei:
        inj.fire("p")
    assert ei.value.point == "p" and ei.value.hit == 3
    inj.fire("p")   # times=1: only the 3rd hit triggers
    assert inj.hits("p") == 4


def test_fault_injector_env_grammar():
    inj = FaultInjector()
    inj.load_spec_string(
        "checkpoint.write:truncate@2,serve.request:raise@1x3,x.y:delay~0.01")
    spec = inj._specs["checkpoint.write"][0]
    assert (spec.mode, spec.at_hit) == ("truncate", 2)
    spec = inj._specs["serve.request"][0]
    assert (spec.mode, spec.at_hit, spec.times) == ("raise", 1, 3)
    assert inj._specs["x.y"][0].delay_s == pytest.approx(0.01)


def test_fault_injector_arms_from_env(monkeypatch):
    """DL4J_TPU_FAULTS arms faults lazily on first fire — the chaos
    config a test exercises is the one an operator can replay."""
    from deeplearning4j_tpu.resilience.faults import ENV_VAR

    monkeypatch.setenv(ENV_VAR, "p.q:raise@2")
    inj = FaultInjector()
    inj.fire("p.q")
    with pytest.raises(FaultInjectedError):
        inj.fire("p.q")


def test_fault_injector_noop_and_clear():
    inj = FaultInjector()
    inj.fire("never.armed")   # must be a no-op
    inj.inject("p", mode="raise")
    inj.clear("p")
    inj.fire("p")             # cleared: no raise


def test_fault_injector_seeded_probability():
    a = FaultInjector(seed=7)
    a.inject("p", mode="raise", at_hit=1, times=1000, probability=0.5,
             seed=7)
    hits_a = []
    for i in range(50):
        try:
            a.fire("p")
            hits_a.append(False)
        except FaultInjectedError:
            hits_a.append(True)
    b = FaultInjector(seed=7)
    b.inject("p", mode="raise", at_hit=1, times=1000, probability=0.5,
             seed=7)
    hits_b = []
    for i in range(50):
        try:
            b.fire("p")
            hits_b.append(False)
        except FaultInjectedError:
            hits_b.append(True)
    assert hits_a == hits_b and any(hits_a) and not all(hits_a)


# ============================================== retry / circuit breaker
def test_retry_recovers_from_transient_errors():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return 42

    assert Retry(max_attempts=4, initial_backoff_s=0.001).call(flaky) == 42
    assert len(calls) == 3


def test_retry_exhaustion_and_passthrough():
    with pytest.raises(RetriesExhaustedError) as ei:
        Retry(max_attempts=2, initial_backoff_s=0.001).call(
            lambda: (_ for _ in ()).throw(OSError("down")))
    assert ei.value.attempts == 2
    assert isinstance(ei.value.cause, OSError)
    # non-retryable exceptions pass through on the first attempt
    calls = []

    def boom():
        calls.append(1)
        raise ValueError("logic bug")

    with pytest.raises(ValueError):
        Retry(max_attempts=5, initial_backoff_s=0.001).call(boom)
    assert len(calls) == 1


def test_retry_backoff_deterministic_for_seed():
    a = list(Retry(max_attempts=5, seed=9).backoffs())
    b = list(Retry(max_attempts=5, seed=9).backoffs())
    assert a == b
    assert all(x > 0 for x in a)


def test_retry_deadline():
    fake_now = [0.0]
    with pytest.raises(DeadlineExceededError):
        Retry(max_attempts=10, initial_backoff_s=5.0, deadline_s=1.0,
              sleep=lambda s: fake_now.__setitem__(0, fake_now[0] + s),
              clock=lambda: fake_now[0]).call(
            lambda: (_ for _ in ()).throw(OSError("down")))


def test_circuit_breaker_open_halfopen_close():
    now = [0.0]
    cb = CircuitBreaker(failure_threshold=2, reset_timeout_s=10.0,
                        clock=lambda: now[0])

    def fail():
        raise OSError("down")

    for _ in range(2):
        with pytest.raises(OSError):
            cb.call(fail)
    assert cb.state == CircuitBreaker.OPEN
    with pytest.raises(CircuitOpenError) as ei:
        cb.call(lambda: 1)
    assert ei.value.retry_after_s > 0
    now[0] = 11.0   # past reset_timeout: one probe allowed
    assert cb.state == CircuitBreaker.HALF_OPEN
    assert cb.call(lambda: "ok") == "ok"
    assert cb.state == CircuitBreaker.CLOSED


# =========================================== atomic writes + manifests
def test_atomic_writer_publishes_nothing_on_crash(tmp_path):
    target = str(tmp_path / "file.bin")
    with pytest.raises(RuntimeError):
        with atomic_writer(target) as tmp:
            with open(tmp, "wb") as f:
                f.write(b"half a paylo")
            raise RuntimeError("kill -9 mid-write")
    assert not os.path.exists(target)
    assert not os.path.exists(target + ".tmp")


def test_checksum_manifest_detects_torn_write(tmp_path):
    d = str(tmp_path)
    p = os.path.join(d, "step-00000002.npz")
    with atomic_writer(p, suffix=".tmp.npz") as tmp:
        with open(tmp, "wb") as f:
            np.savez(f, a=np.arange(5))
        digest, size = sha256_file(tmp), os.path.getsize(tmp)
    record_checksum(d, os.path.basename(p), digest, size)
    assert validate_file(d, os.path.basename(p))
    with open(p, "r+b") as f:
        f.truncate(10)
    assert not validate_file(d, os.path.basename(p))
    assert newest_valid_checkpoint(d) is None


def test_retention_prunes_oldest(tmp_path):
    d = str(tmp_path)
    for step in (1, 2, 3, 4):
        p = os.path.join(d, f"step-{step:08d}.npz")
        with atomic_writer(p, suffix=".tmp.npz") as tmp:
            with open(tmp, "wb") as f:
                np.savez(f, a=np.arange(step))
            record_checksum(d, os.path.basename(p), sha256_file(tmp),
                            os.path.getsize(tmp))
    assert apply_retention(d, keep_last=2) == [1, 2]
    assert newest_valid_checkpoint(d) == 4
    assert sorted(os.listdir(d)) == [
        "manifest.json", "step-00000003.npz", "step-00000004.npz"]


# ================================== crash-safe TrainingMaster resume
@pytest.mark.chaos
def test_resume_skips_corrupt_newest_checkpoint(tmp_path):
    """Truncate the newest checkpoint on disk: resume must fall back to
    the previous valid one instead of crashing (or trusting it)."""
    from deeplearning4j_tpu.parallel.training_master import TrainingMaster

    batch = _data()
    ck = str(tmp_path / "ck")
    TrainingMaster(_net(), checkpoint_dir=ck, checkpoint_every=2).fit(
        batch, 4)
    with open(os.path.join(ck, "step-00000004.npz"), "r+b") as f:
        f.truncate(20)
    tm = TrainingMaster(_net(), checkpoint_dir=ck, checkpoint_every=2)
    assert tm.load_latest_checkpoint() == 2


@pytest.mark.chaos
def test_checkpoint_kill_mid_write_resumes_identically(tmp_path):
    """Chaos case (a): a FaultInjector 'raise' at checkpoint.write kills
    the step-4 save mid-flight. Nothing partial is published, relaunch
    resumes from step 2, and the finished run's params are IDENTICAL to
    an uninterrupted run's."""
    from deeplearning4j_tpu.parallel.training_master import TrainingMaster

    batch = _data()
    # uninterrupted reference
    ref_dir = str(tmp_path / "ref")
    ref_net = _net()
    TrainingMaster(ref_net, checkpoint_dir=ref_dir,
                   checkpoint_every=2).fit(batch, 6)
    ref_params = _params_of(ref_net)

    # chaos run: the 2nd checkpoint write (step 4) dies mid-flight
    ck = str(tmp_path / "chaos")
    injector().inject("checkpoint.write", mode="raise", at_hit=2)
    with pytest.raises(FaultInjectedError):
        TrainingMaster(_net(), checkpoint_dir=ck,
                       checkpoint_every=2).fit(batch, 6)
    injector().clear()
    # the kill published nothing for step 4
    assert sorted(f for f in os.listdir(ck) if f.startswith("step-")) \
        == ["step-00000002.npz"]

    # relaunch with the same arguments (SURVEY §5.3)
    tm = TrainingMaster(_net(), checkpoint_dir=ck, checkpoint_every=2)
    net = tm.net
    tm.fit(batch, 6)
    for got, want in zip(_params_of(net), ref_params):
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


@pytest.mark.chaos
def test_checkpoint_torn_write_falls_back_and_resumes(tmp_path):
    """FaultInjector 'truncate' models a torn write that slips past the
    atomic publish (bad NFS, power loss after replace): the checksum
    catches it on load and resume uses the previous valid step, ending
    with params identical to an uninterrupted run."""
    from deeplearning4j_tpu.parallel.training_master import TrainingMaster

    batch = _data()
    ref_net = _net()
    TrainingMaster(ref_net, checkpoint_dir=str(tmp_path / "ref"),
                   checkpoint_every=2).fit(batch, 6)

    ck = str(tmp_path / "chaos")
    injector().inject("checkpoint.write", mode="truncate", at_hit=2,
                      truncate_to=16)
    TrainingMaster(_net(), checkpoint_dir=ck, checkpoint_every=2).fit(
        batch, 4)   # completes; step-4 file is silently torn
    injector().clear()

    tm = TrainingMaster(_net(), checkpoint_dir=ck, checkpoint_every=2)
    assert tm.load_latest_checkpoint() == 2   # torn step 4 rejected
    tm.fit(batch, 6)
    for got, want in zip(_params_of(tm.net), _params_of(ref_net)):
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


def test_keep_last_retention_through_training(tmp_path):
    from deeplearning4j_tpu.parallel.training_master import TrainingMaster

    tm = TrainingMaster(_net(), checkpoint_dir=str(tmp_path / "ck"),
                        checkpoint_every=1, keep_last=2)
    tm.fit(_data(), 5)
    assert tm.list_checkpoints() == [4, 5]


def test_retention_covers_orbax_directories(tmp_path):
    """Satellite (orbax retention parity): keep_last pruning must see
    npz files and orbax checkpoint DIRECTORIES on one step timeline."""
    d = str(tmp_path)
    for step in (1, 2):
        p = os.path.join(d, f"step-{step:08d}.npz")
        with atomic_writer(p, suffix=".tmp.npz") as tmp:
            with open(tmp, "wb") as f:
                np.savez(f, a=np.arange(step))
            record_checksum(d, os.path.basename(p), sha256_file(tmp),
                            os.path.getsize(tmp))
    for step in (3, 4):
        od = os.path.join(d, f"step-{step}.orbax")
        os.makedirs(od)
        with open(os.path.join(od, "payload"), "w") as f:
            f.write("x")
    assert apply_retention(d, keep_last=2) == [1, 2]
    left = sorted(f for f in os.listdir(d) if f.startswith("step-"))
    assert left == ["step-3.orbax", "step-4.orbax"]
    # newest-2 across formats: orbax dirs pruned too
    assert apply_retention(d, keep_last=1) == [3]
    assert not os.path.exists(os.path.join(d, "step-3.orbax"))


def test_orbax_training_retention_and_fallback_scan(tmp_path):
    """Satellite (ROADMAP open item): orbax-format checkpoints honor
    keep_last AND the newest-valid fallback scan — a missing latest
    pointer or a damaged newest directory must not lose the run."""
    import shutil

    pytest.importorskip("orbax.checkpoint")
    from deeplearning4j_tpu.parallel.training_master import TrainingMaster

    batch = _data()
    ck = str(tmp_path / "ck")
    tm = TrainingMaster(_net(), checkpoint_dir=ck, checkpoint_every=1,
                        checkpoint_format="orbax", keep_last=2)
    tm.fit(batch, 5)
    assert tm.list_checkpoints() == [4, 5]   # retention pruned 1..3

    # fallback parity (a): latest.json gone -> scan finds step 5 and
    # restores position from the self-describing payload
    os.remove(os.path.join(ck, "latest.json"))
    tm2 = TrainingMaster(_net(), checkpoint_dir=ck, checkpoint_every=1,
                         checkpoint_format="orbax", keep_last=2)
    assert tm2.load_latest_checkpoint() == 5
    assert tm2.net.iteration == 5

    # fallback parity (b): the newest directory is damaged -> the scan
    # falls back to the previous valid step instead of crashing
    shutil.rmtree(os.path.join(ck, "step-5.orbax"))
    os.makedirs(os.path.join(ck, "step-5.orbax"))   # empty husk
    tm3 = TrainingMaster(_net(), checkpoint_dir=ck, checkpoint_every=1,
                         checkpoint_format="orbax", keep_last=2)
    assert tm3.load_latest_checkpoint() == 4
    assert tm3.net.iteration == 4


# ====================================== serializer + earlystopping saver
def test_write_model_is_atomic_and_checksummed(tmp_path):
    from deeplearning4j_tpu.util.model_serializer import (
        ModelSerializer,
        verify_model,
    )

    net = _net()
    p = str(tmp_path / "model.zip")
    ModelSerializer.write_model(net, p)
    assert verify_model(p)
    assert os.path.exists(p + ".sha256")
    restored = ModelSerializer.restore_multi_layer_network(p)
    for got, want in zip(_params_of(restored), _params_of(net)):
        np.testing.assert_allclose(got, want)
    # torn write detected on restore
    with open(p, "r+b") as f:
        f.truncate(30)
    assert not verify_model(p)
    with pytest.raises(CheckpointIntegrityError):
        ModelSerializer.restore_multi_layer_network(p)


@pytest.mark.chaos
def test_write_model_kill_mid_write_keeps_previous(tmp_path):
    from deeplearning4j_tpu.util.model_serializer import (
        restore_multi_layer_network,
        write_model,
    )

    p = str(tmp_path / "model.zip")
    first = _net(seed=1)
    write_model(first, p)
    injector().inject("checkpoint.write", mode="raise", at_hit=1)
    with pytest.raises(FaultInjectedError):
        write_model(_net(seed=2), p)
    injector().clear()
    # the previous model survived the mid-write kill, bytes intact
    restored = restore_multi_layer_network(p)
    for got, want in zip(_params_of(restored), _params_of(first)):
        np.testing.assert_allclose(got, want)


def test_earlystopping_saver_detects_corruption(tmp_path):
    from deeplearning4j_tpu.earlystopping.saver import LocalFileModelSaver

    saver = LocalFileModelSaver(str(tmp_path))
    saver.save_best_model(_net(), 0.5)
    assert saver.get_best_model() is not None
    with open(os.path.join(str(tmp_path), "bestModel.zip"), "r+b") as f:
        f.truncate(25)
    with pytest.raises(CheckpointIntegrityError):
        saver.get_best_model()
    assert saver.get_latest_model() is None   # never written


# ===================================== serving: graceful degradation
class _SlowNet:
    """Stand-in model whose output blocks until released — lets tests
    hold requests in flight deterministically. Hits the `model.forward`
    fault point after unblocking, so chaos tests can fail the in-flight
    batch at a precise moment."""

    def __init__(self, release=None):
        self.release = release
        self.started = threading.Event()

    def output(self, x):
        from deeplearning4j_tpu.resilience.faults import fire

        self.started.set()
        if self.release is not None:
            self.release.wait(timeout=10.0)
        fire("model.forward")
        return np.asarray(x)


def test_output_sheds_load_when_queue_full():
    from deeplearning4j_tpu.parallel.inference import ParallelInference

    release = threading.Event()
    net = _SlowNet(release=release)
    pi = ParallelInference(net, batch_limit=1, queue_limit=1,
                           max_wait_ms=0.0, default_timeout_s=5.0)
    try:
        results = []
        t = threading.Thread(target=lambda: results.append(
            pi.output(np.ones((1, 2), np.float32))))
        t.start()
        net.started.wait(timeout=5.0)   # batcher is now busy in output()
        # fill the single queue slot, then the next submit must shed
        t2 = threading.Thread(target=lambda: results.append(
            pi.output(np.ones((1, 2), np.float32))))
        t2.start()
        deadline = time.monotonic() + 5.0
        while pi.queue_depth() < 1 and time.monotonic() < deadline:
            time.sleep(0.005)
        with pytest.raises(OverloadedError):
            pi.output(np.ones((1, 2), np.float32))
        release.set()
        t.join(timeout=5.0)
        t2.join(timeout=5.0)
        assert len(results) == 2
    finally:
        release.set()
        pi.shutdown()


def test_output_deadline_instead_of_hang():
    from deeplearning4j_tpu.parallel.inference import ParallelInference

    release = threading.Event()
    pi = ParallelInference(_SlowNet(release=release), batch_limit=1,
                           max_wait_ms=0.0)
    try:
        with pytest.raises(DeadlineExceededError):
            pi.output(np.ones((1, 2), np.float32), timeout_s=0.2)
    finally:
        release.set()
        pi.shutdown()


def test_shutdown_signals_queued_requests():
    """Satellite: shutdown() must drain the queue and fail every pending
    caller with ShutdownError — nobody hangs."""
    from deeplearning4j_tpu.parallel.inference import ParallelInference

    release = threading.Event()
    net = _SlowNet(release=release)
    pi = ParallelInference(net, batch_limit=1, queue_limit=8,
                           max_wait_ms=0.0, default_timeout_s=10.0)
    errors = []

    def call():
        try:
            pi.output(np.ones((1, 2), np.float32))
        except Exception as e:   # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=call) for _ in range(4)]
    for t in threads:
        t.start()
    assert net.started.wait(timeout=5.0)
    deadline = time.monotonic() + 5.0
    while pi.queue_depth() < 3 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert pi.queue_depth() == 3
    # shut down while one batch is STILL held inside the model and three
    # requests are queued — the old code left all four hanging forever
    pi.shutdown()
    for t in threads:
        t.join(timeout=5.0)
        assert not t.is_alive(), "caller hung through shutdown"
    assert len(errors) == 4   # in-flight + queued all signaled
    assert all(isinstance(e, ShutdownError) for e in errors)
    with pytest.raises(ShutdownError):
        pi.output(np.ones((1, 2), np.float32))
    release.set()   # let the parked batcher thread exit


@pytest.mark.chaos
def test_batcher_death_fails_all_inflight_and_flips_healthz(tmp_path):
    """Chaos case (b): a FaultInjector 'raise' kills the batcher thread
    while clients are in flight. Every client gets an error (no hang)
    and /healthz flips unhealthy.

    Deterministic sequencing: client A's batch is held inside the model
    until the queue holds clients B..F, THEN two faults are armed — one
    fails A's in-flight batch, the next kills the batcher loop itself,
    which drains B..F with InferenceUnavailableError."""
    import concurrent.futures as cf

    from deeplearning4j_tpu.parallel.inference import ParallelInference
    from deeplearning4j_tpu.parallel.serving import ModelClient, ModelServer

    release = threading.Event()
    net = _SlowNet(release=release)
    pi = ParallelInference(net, batch_limit=1, queue_limit=16,
                           max_wait_ms=0.0, default_timeout_s=10.0)
    server = ModelServer(pi).start()
    try:
        client = ModelClient(f"http://127.0.0.1:{server.port}",
                             retry=Retry(max_attempts=1))
        assert client.healthz()

        x = np.ones((1, 2), np.float32)
        with cf.ThreadPoolExecutor(6) as ex:
            futures = [ex.submit(client.predict, x) for _ in range(6)]
            # hold until A is inside the model and B..F are queued
            assert net.started.wait(timeout=10.0)
            deadline = time.monotonic() + 10.0
            while pi.queue_depth() < 5 and time.monotonic() < deadline:
                time.sleep(0.005)
            assert pi.queue_depth() >= 5
            # arm: A's batch fails, then the batcher loop itself dies
            injector().inject("model.forward", mode="raise",
                              at_hit=1, times=1 << 30)
            injector().inject("inference.batch", mode="raise",
                              at_hit=1, times=1 << 30)
            release.set()
            outcomes = [f.exception(timeout=20.0) for f in futures]
        # every in-flight client got a RESPONSE — an error, not a hang
        assert all(o is not None for o in outcomes)
        statuses = sorted(o.status for o in outcomes
                          if isinstance(o, ServingError))
        assert all(isinstance(o, ServingError) for o in outcomes)
        # A: 500 (its batch failed); B..F: 503 (batcher died under them)
        assert statuses == [500, 503, 503, 503, 503, 503]
        assert not pi.healthy
        assert client.healthz() is False   # /healthz flipped unhealthy
        assert client.readyz() is False
        # direct calls now fail fast too
        with pytest.raises(InferenceUnavailableError):
            pi.output(x)
    finally:
        injector().clear()
        release.set()
        server.stop()


def test_http_error_classes(tmp_path):
    """Satellite: 404 unknown route, 400 malformed payload, 500 model
    crash, 503 shutdown — with error_class in every body."""
    from deeplearning4j_tpu.parallel.serving import ModelClient, ModelServer

    class _BoomNet:
        def output(self, x):
            raise RuntimeError("model exploded")

    server = ModelServer(_net()).start()
    client = ModelClient(f"http://127.0.0.1:{server.port}",
                         retry=Retry(max_attempts=1))
    try:
        with pytest.raises(ServingError) as ei:
            client._request("/nope", {})
        assert ei.value.status == 404
        with pytest.raises(ServingError) as ei:
            client._request("/predict", {"not_inputs": 1})
        assert ei.value.status == 400
        assert "inputs" in ei.value.message
        with pytest.raises(ServingError) as ei:
            client.predict(np.zeros((1, 4), np.float32), decode_top=3)
        assert ei.value.status == 400   # client error, not server fault
    finally:
        server.stop()

    boom = ModelServer(_BoomNet(), inference_mode="sequential").start()
    client = ModelClient(f"http://127.0.0.1:{boom.port}",
                         retry=Retry(max_attempts=1))
    try:
        with pytest.raises(ServingError) as ei:
            client.predict(np.zeros((1, 4), np.float32))
        assert ei.value.status == 500
        assert ei.value.error_class == "RuntimeError"
        assert "model exploded" in ei.value.message
    finally:
        boom.stop()


def test_client_surfaces_503_with_retry_after_and_retries():
    """Satellite: ModelClient parses the server's JSON error payload
    into ServingError, and its Retry policy re-attempts 503s."""
    import http.server
    import socketserver

    from deeplearning4j_tpu.parallel.serving import ModelClient

    hits = []

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            hits.append(1)
            n = int(self.headers.get("Content-Length", 0))
            self.rfile.read(n)
            if len(hits) < 3:
                body = (b'{"error": "queue full", '
                        b'"error_class": "OverloadedError"}')
                self.send_response(503)
                self.send_header("Retry-After", "1")
            else:
                body = b'{"outputs": [[1.0]]}'
                self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    class _S(socketserver.ThreadingMixIn, http.server.HTTPServer):
        daemon_threads = True

    httpd = _S(("127.0.0.1", 0), Handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        url = f"http://127.0.0.1:{httpd.server_address[1]}"
        # no-retry client surfaces the typed error + parsed body
        with pytest.raises(ServingError) as ei:
            ModelClient(url, retry=Retry(max_attempts=1)).predict([[1.0]])
        assert ei.value.status == 503
        assert ei.value.error_class == "OverloadedError"
        assert ei.value.message == "queue full"
        assert ei.value.retry_after_s == 1.0
        assert ei.value.retryable
        # a retrying client rides through the 503s and succeeds
        hits.clear()
        out = ModelClient(url, retry=Retry(
            max_attempts=4, initial_backoff_s=0.01,
            retryable=ModelClient._retryable)).predict([[1.0]])
        assert out["outputs"] == [[1.0]]
        assert len(hits) == 3
    finally:
        httpd.shutdown()
        httpd.server_close()


def _stub_http_server(handler_fn):
    """Minimal HTTP server whose POST behavior is `handler_fn(hits) ->
    (status, body_bytes, headers)`."""
    import http.server
    import socketserver

    hits = []

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            hits.append(1)
            n = int(self.headers.get("Content-Length", 0))
            self.rfile.read(n)
            status, body, headers = handler_fn(len(hits))
            self.send_response(status)
            for k, v in headers:
                self.send_header(k, v)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    class _S(socketserver.ThreadingMixIn, http.server.HTTPServer):
        daemon_threads = True

    httpd = _S(("127.0.0.1", 0), Handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, hits


def test_model_client_has_circuit_breaker_by_default():
    """Satellite: CircuitBreaker is wired into ModelClient BY DEFAULT
    (was exported-but-unused); breaker=None opts out."""
    from deeplearning4j_tpu.parallel.serving import ModelClient

    assert isinstance(ModelClient("http://x").breaker, CircuitBreaker)
    assert ModelClient("http://x", breaker=None).breaker is None


def test_model_client_breaker_opens_on_503s_and_half_opens():
    """Satellite: repeated 503s open the breaker (requests fail fast
    WITHOUT hitting the server); after the cooldown one probe goes
    through (half-open) and its success closes the circuit."""
    from deeplearning4j_tpu.parallel.serving import ModelClient

    ok = [False]

    def handler(nth):
        if ok[0]:
            return 200, b'{"outputs": [[1.0]]}', []
        return (503, b'{"error": "queue full", '
                b'"error_class": "OverloadedError"}',
                [("Retry-After", "1")])

    httpd, hits = _stub_http_server(handler)
    try:
        now = [0.0]
        breaker = CircuitBreaker(failure_threshold=3,
                                 reset_timeout_s=10.0,
                                 clock=lambda: now[0])
        client = ModelClient(
            f"http://127.0.0.1:{httpd.server_address[1]}",
            retry=Retry(max_attempts=1,
                        retryable=lambda e: False),
            breaker=breaker)
        for _ in range(3):
            with pytest.raises(ServingError):
                client.predict([[1.0]])
        assert breaker.state == CircuitBreaker.OPEN
        server_hits = len(hits)
        # open circuit: fail fast, the drowning server is NOT hit
        with pytest.raises(CircuitOpenError) as ei:
            client.predict([[1.0]])
        assert ei.value.retry_after_s > 0
        assert len(hits) == server_hits
        # cooldown elapses -> half-open -> a healthy response closes it
        now[0] = 11.0
        ok[0] = True
        assert client.predict([[1.0]])["outputs"] == [[1.0]]
        assert breaker.state == CircuitBreaker.CLOSED
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_model_client_4xx_does_not_trip_breaker():
    """A 4xx/500 response proves the server is ALIVE — it must not
    open the breaker (only unavailability counts)."""
    from deeplearning4j_tpu.parallel.serving import ModelClient

    def handler(nth):
        return 400, b'{"error": "bad", "error_class": "ValueError"}', []

    httpd, hits = _stub_http_server(handler)
    try:
        breaker = CircuitBreaker(failure_threshold=2)
        client = ModelClient(
            f"http://127.0.0.1:{httpd.server_address[1]}",
            retry=Retry(max_attempts=1), breaker=breaker)
        for _ in range(4):
            with pytest.raises(ServingError) as ei:
                client.predict([[1.0]])
            assert ei.value.status == 400
        assert breaker.state == CircuitBreaker.CLOSED
        assert len(hits) == 4
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_status_and_probes_report_degradation_facts():
    from deeplearning4j_tpu.parallel.serving import ModelClient, ModelServer

    server = ModelServer(_net()).start()
    client = ModelClient(f"http://127.0.0.1:{server.port}")
    try:
        st = client.status()
        assert st["healthy"] and st["ready"]
        assert st["queue_depth"] == 0
        assert client.healthz() and client.readyz()
    finally:
        server.stop()
