"""Pipelined serving data plane: overlap, bucket-cap guards, warmup /
recompile regression, adaptive batching wait, and the CPU serving-perf
smoke test (pipelined dispatch must beat blocking dispatch on a stub
net with an artificial device RTT — a regression here means the
batcher went back to blocking on the host fetch)."""

import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf import InputType
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.parallel.inference import ParallelInference
from deeplearning4j_tpu.resilience import (
    InferenceUnavailableError,
    injector,
)


def _net(seed=7, n_in=8, n_out=6):
    conf = (NeuralNetConfiguration.Builder().seed(seed).updater("sgd")
            .learning_rate(0.1).activation("tanh").weight_init("xavier")
            .list()
            .layer(DenseLayer(n_out=16))
            .layer(OutputLayer(n_out=n_out, loss="mcxent"))
            .set_input_type(InputType.feed_forward(n_in))
            .build())
    return MultiLayerNetwork(conf).init()


class _EchoNet:
    """Synchronous echo stub; records every dispatched batch shape."""

    def __init__(self):
        self.batch_shapes = []

    def output(self, x):
        self.batch_shapes.append(tuple(np.asarray(x).shape))
        return np.asarray(x)


class _LazyValue:
    """Device-value stand-in: np.asarray blocks until `release` (and
    optionally an artificial RTT), like an in-flight async result."""

    def __init__(self, arr, release=None, rtt_s=0.0, on_fetch=None):
        self._arr = arr
        self._release = release
        self._rtt_s = rtt_s
        self._on_fetch = on_fetch

    def __array__(self, dtype=None):
        if self._release is not None:
            assert self._release.wait(timeout=10.0), "never released"
        if self._rtt_s:
            time.sleep(self._rtt_s)
        if self._on_fetch is not None:
            self._on_fetch()
        return (self._arr if dtype is None
                else self._arr.astype(dtype, copy=False))


class _AsyncStubNet:
    """Async-dispatch stub: output() returns immediately; the host
    fetch blocks until `release` is set. Records dispatch order."""

    def __init__(self):
        self.release = threading.Event()
        self.dispatched = []          # dispatch index -> monotonic time
        self.fetched = []             # completion order

    def output(self, x):
        i = len(self.dispatched)
        self.dispatched.append(time.monotonic())
        return _LazyValue(np.asarray(x), release=self.release,
                          on_fetch=lambda: self.fetched.append(i))


class _RTTNet:
    """Echo stub charging an artificial per-fetch device RTT (the
    PERF.md 4-6 ms dispatch round trip) + serialized compute time —
    the accelerator-backend shape the pipeline overlaps."""

    def __init__(self, rtt_ms=5.0, compute_ms=3.0):
        self.rtt_s = rtt_ms / 1000.0
        self.compute_s = compute_ms / 1000.0
        self._busy_until = 0.0

    def output(self, x):
        now = time.perf_counter()
        self._busy_until = max(self._busy_until, now) + self.compute_s
        t_ready = self._busy_until
        arr = np.asarray(x)
        rtt = self.rtt_s

        class _V:
            def __array__(self, dtype=None):
                time.sleep(max(0.0, t_ready - time.perf_counter()) + rtt)
                return arr if dtype is None else arr.astype(dtype)

        return _V()


# ================================================= pipelining overlap
def test_batches_overlap_dispatch_and_completion():
    """Tentpole property: batch N+1 is DISPATCHED while batch N is
    still computing — completion of batch N resolves only after batch
    N+1 went out."""
    net = _AsyncStubNet()
    pi = ParallelInference(net, batch_limit=1, queue_limit=8,
                           max_wait_ms=0.0, pipeline_depth=2,
                           default_timeout_s=10.0)
    try:
        results = []
        threads = [threading.Thread(
            target=lambda: results.append(
                pi.output(np.full((1, 4), float(i), np.float32))))
            for i in range(2)]
        for t in threads:
            t.start()
        # both batches must dispatch while NEITHER has completed (the
        # host fetch is still blocked on `release`)
        deadline = time.monotonic() + 5.0
        while len(net.dispatched) < 2 and time.monotonic() < deadline:
            time.sleep(0.002)
        assert len(net.dispatched) == 2, \
            "second batch not dispatched while first was in flight"
        assert net.fetched == []      # nothing completed yet
        net.release.set()
        for t in threads:
            t.join(timeout=5.0)
        assert len(results) == 2
        # every dispatch completed; with completion_streams=2 the two
        # fetches run concurrently, so completion ORDER is unspecified
        assert sorted(net.fetched) == [0, 1]
    finally:
        net.release.set()
        pi.shutdown()


def test_blocking_mode_does_not_overlap():
    """pipeline_depth=0 is the serialized baseline: the second batch
    cannot dispatch until the first completes."""
    net = _AsyncStubNet()
    net.release.set()   # don't block fetches, just record order
    pi = ParallelInference(net, batch_limit=1, queue_limit=8,
                           max_wait_ms=0.0, pipeline_depth=0)
    try:
        for i in range(3):
            pi.output(np.full((1, 4), float(i), np.float32))
        # interleaved strictly: dispatch i, fetch i, dispatch i+1 ...
        assert net.fetched == [0, 1, 2]
    finally:
        pi.shutdown()


# ========================================== bucket cap + split guard
def test_bucket_never_exceeds_cap():
    """Satellite: coalescing must not push a batch past
    next_pow2(batch_limit) — the overflow rides the next batch."""
    net = _EchoNet()
    pi = ParallelInference(net, batch_limit=8, queue_limit=64,
                           max_wait_ms=20.0, adaptive_wait=False,
                           pipeline_depth=2)
    try:
        import concurrent.futures as cf

        rng = np.random.default_rng(3)
        # 5-row requests: 8 = 5 + 3(split), worst-case overshoot bait
        inputs = [rng.normal(size=(5, 4)).astype(np.float32)
                  for _ in range(12)]
        with cf.ThreadPoolExecutor(12) as ex:
            outs = list(ex.map(pi.output, inputs))
        for x, o in zip(inputs, outs):
            np.testing.assert_allclose(o, x)   # echo: rows intact
        assert net.batch_shapes, "nothing dispatched"
        assert max(s[0] for s in net.batch_shapes) <= 8
    finally:
        pi.shutdown()


def test_oversized_request_is_split_and_reassembled():
    """A single request larger than the cap is chunked across batches
    and reassembled in order — no oversized bucket shape is compiled."""
    net = _EchoNet()
    pi = ParallelInference(net, batch_limit=8, queue_limit=16,
                           max_wait_ms=0.0, pipeline_depth=2)
    try:
        x = np.arange(20 * 3, dtype=np.float32).reshape(20, 3)
        out = pi.output(x)
        np.testing.assert_allclose(out, x)
        assert max(s[0] for s in net.batch_shapes) <= 8
        assert sum(min(s[0], 8) for s in net.batch_shapes) >= 20
    finally:
        pi.shutdown()


# =========================================== warmup + recompile guard
def test_warmup_pretraces_all_buckets():
    net = _net()
    pi = ParallelInference(net, batch_limit=8, queue_limit=8)
    try:
        assert pi.stats()["warmed_buckets"] == [1, 2, 4, 8]
        assert pi.trace_stats()["trace_counts"]["predict"] == 4
    finally:
        pi.shutdown()


def test_warmup_opt_out():
    net = _net()
    pi = ParallelInference(net, batch_limit=8, warmup=False)
    try:
        assert pi.stats()["warmed_buckets"] == []
        assert pi.trace_stats().get("total_traces", 0) == 0
    finally:
        pi.shutdown()


def test_zero_new_traces_after_warmup_under_mixed_load():
    """Satellite (recompile regression): after warmup, a mixed-size
    request load — including requests larger than the cap — causes
    ZERO new jit traces. Every trace is a full XLA recompile on TPU;
    this pins the compile-once property the bucket cap + warmup
    guarantee."""
    import concurrent.futures as cf

    net = _net()
    pi = ParallelInference(net, batch_limit=8, queue_limit=64)
    try:
        base = pi.trace_stats()["total_traces"]
        assert base > 0   # warmup actually traced
        rng = np.random.default_rng(0)
        sizes = list(rng.integers(1, 20, size=40))   # mixed, some > cap
        inputs = [rng.normal(size=(int(s), 8)).astype(np.float32)
                  for s in sizes]
        with cf.ThreadPoolExecutor(8) as ex:
            outs = list(ex.map(pi.output, inputs))
        assert all(o.shape[0] == x.shape[0]
                   for o, x in zip(outs, inputs))
        assert pi.trace_stats()["total_traces"] == base, \
            "mixed-size load caused a recompile after warmup"
    finally:
        pi.shutdown()


# ================================================== adaptive max_wait
def test_adaptive_wait_shrinks_deep_grows_idle():
    import concurrent.futures as cf

    net = _EchoNet()
    pi = ParallelInference(net, batch_limit=4, queue_limit=128,
                           max_wait_ms=4.0, pipeline_depth=2)
    try:
        assert pi.stats()["current_wait_ms"] == pytest.approx(4.0)
        # deep queue: full batches -> the wait shrinks
        rng = np.random.default_rng(1)
        inputs = [rng.normal(size=(1, 4)).astype(np.float32)
                  for _ in range(64)]
        with cf.ThreadPoolExecutor(16) as ex:
            list(ex.map(pi.output, inputs))
        shrunk = pi.stats()["current_wait_ms"]
        assert shrunk < 4.0
        # idle traffic: the wait grows back toward max_wait_ms
        for _ in range(12):
            pi.output(np.zeros((1, 4), np.float32))
        assert pi.stats()["current_wait_ms"] > shrunk
        assert pi.stats()["current_wait_ms"] <= 4.0
    finally:
        pi.shutdown()


# ===================================== completion-stage chaos parity
@pytest.mark.chaos
def test_completion_stage_death_fails_callers_and_flips_health():
    """PR 1's batcher-death guarantee re-proven for the NEW thread: a
    dead completion stage fails callers fast (no hang) and flips
    `healthy`."""
    net = _EchoNet()
    pi = ParallelInference(net, batch_limit=2, queue_limit=8,
                           max_wait_ms=0.0, pipeline_depth=2,
                           default_timeout_s=5.0)
    try:
        injector().inject("inference.complete", mode="raise", at_hit=1,
                          times=1 << 30)
        deadline = time.monotonic() + 5.0
        while pi._completer.is_alive() and time.monotonic() < deadline:
            time.sleep(0.005)
        with pytest.raises(InferenceUnavailableError):
            pi.output(np.zeros((1, 4), np.float32))
        assert not pi.healthy
    finally:
        injector().clear()
        pi.shutdown()


# ====================================== CPU serving-perf smoke test
def test_pipelined_throughput_beats_blocking_dispatch():
    """CI smoke: on a stub net with an artificial per-dispatch RTT
    (5 ms, a synthetic figure), the pipelined data plane
    must out-throughput serialized dispatch-then-fetch. Catches a
    regression to blocking dispatch."""
    import concurrent.futures as cf

    def run(depth):
        pi = ParallelInference(_RTTNet(rtt_ms=5.0, compute_ms=3.0),
                               batch_limit=8, queue_limit=64,
                               max_wait_ms=1.0, pipeline_depth=depth,
                               default_timeout_s=20.0)
        try:
            rng = np.random.default_rng(0)
            inputs = [rng.normal(size=(int(s), 4)).astype(np.float32)
                      for s in rng.integers(1, 5, size=80)]
            t0 = time.perf_counter()
            with cf.ThreadPoolExecutor(16) as ex:
                outs = list(ex.map(pi.output, inputs))
            dt = time.perf_counter() - t0
            assert all(o.shape[0] == x.shape[0]
                       for o, x in zip(outs, inputs))
            return len(inputs) / dt
        finally:
            pi.shutdown()

    blocking = run(0)
    pipelined = run(2)
    # expected ~1.6-1.9x; 1.1 leaves CI headroom while still failing
    # hard on a return to serialized dispatch
    assert pipelined >= 1.1 * blocking, (
        f"pipelined {pipelined:.0f} req/s did not beat blocking "
        f"{blocking:.0f} req/s")


# ===================================== priority-aware queue ordering
def test_request_queue_priority_ordering_unit():
    """Satellite (ROADMAP item 4 ordering gap): the bounded request
    queue dequeues high-before-normal-before-low, FIFO within one
    class — and stays a real queue.Queue (bounded put_nowait raises
    Full, qsize/empty consistent)."""
    import queue as _q

    from deeplearning4j_tpu.parallel.inference import (
        _Pending,
        _RequestQueue,
    )

    rq = _RequestQueue(maxsize=6)

    def pend(pri, tag):
        return _Pending((np.full((1, 2), tag, np.float32),),
                        priority_idx=pri)

    for pri, tag in ((2, 1), (2, 2), (1, 3), (0, 4), (1, 5), (0, 6)):
        rq.put_nowait(pend(pri, tag))
    assert rq.qsize() == 6
    with pytest.raises(_q.Full):
        rq.put_nowait(pend(1, 7))
    got = [float(rq.get_nowait().xs[0][0, 0]) for _ in range(6)]
    # highs (4, 6) first in arrival order, then normals (3, 5),
    # then lows (1, 2)
    assert got == [4.0, 6.0, 3.0, 5.0, 1.0, 2.0]
    assert rq.empty()
    with pytest.raises(_q.Empty):
        rq.get_nowait()


class _GateNet:
    """Blocks every output() until `gate` opens; records the tag (first
    element) of each dispatched batch — the dequeue-order probe."""

    def __init__(self):
        self.gate = threading.Event()
        self.seen = []

    def output(self, x):
        x = np.asarray(x)
        self.seen.append(float(x[0, 0]))
        assert self.gate.wait(timeout=10.0), "gate never opened"
        return x


def test_priority_dequeue_under_deep_queue():
    """Satellite acceptance (deep-queue pin): with the batcher stalled
    on an in-flight batch, a deep queue of admitted low/normal
    requests does NOT delay a later-admitted high request — on resume
    the highs dispatch first, then normals, then lows."""
    net = _GateNet()
    pi = ParallelInference(net, batch_limit=1, queue_limit=16,
                           warmup=False, pipeline_depth=0,
                           max_wait_ms=0.0, adaptive_wait=False)
    try:
        results = {}

        def call(tag, priority):
            def run():
                out = pi.output(np.full((1, 2), tag, np.float32),
                                priority=priority, timeout_s=30.0)
                results[tag] = np.asarray(out)[0, 0]

            t = threading.Thread(target=run, daemon=True,
                                 name=f"req-{tag}")
            t.start()
            return t

        threads = [call(0.5, "normal")]          # the stall filler
        while not net.seen:                      # batcher holds it
            time.sleep(0.005)
        # deep queue builds while the batcher is stalled: lows and
        # normals FIRST, highs admitted LAST
        order = [(1, "low"), (2, "low"), (3, "normal"), (4, "low"),
                 (5, "normal"), (6, "high"), (7, "high")]
        for tag, pri in order:
            threads.append(call(float(tag), pri))
            while pi.queue_depth() < len(threads) - 1:
                time.sleep(0.005)
        net.gate.set()                           # resume the batcher
        for t in threads:
            t.join(timeout=20.0)
            assert not t.is_alive()
        # dispatch order: filler, then strict class order
        assert net.seen[0] == 0.5
        assert net.seen[1:] == [6.0, 7.0, 3.0, 5.0, 1.0, 2.0, 4.0]
        assert set(results) == {0.5} | {float(t) for t, _ in order}
    finally:
        pi.shutdown()


# ======================================== /status surfacing contract
def test_status_surfaces_pipeline_and_trace_counters():
    from deeplearning4j_tpu.parallel.serving import (
        ModelClient,
        ModelServer,
    )

    server = ModelServer(_net(), batch_limit=8).start()
    try:
        client = ModelClient(f"http://127.0.0.1:{server.port}")
        client.predict(np.zeros((3, 8), np.float32))
        st = client.status()
        assert st["pipeline"]["warmed_buckets"] == [1, 2, 4, 8]
        assert st["pipeline"]["pipeline_depth"] == 2
        assert st["pipeline"]["bucket_cap"] == 8
        assert st["pipeline"]["batches_dispatched"] >= 1
        assert st["total_traces"] == 4          # warmup traces only
        assert st["trace_counts"] == {"predict": 4}
    finally:
        server.stop()
