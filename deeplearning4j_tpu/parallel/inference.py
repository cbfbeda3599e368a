"""ParallelInference: high-throughput inference serving.

Parity: deeplearning4j-scaleout-parallelwrapper/.../ParallelInference.java
(380 LoC; InferenceMode.java:7-8 SEQUENTIAL/BATCHED, dynamic batching via
observable queue in observers/BatchedInferenceObservable.java).

TPU-native design: the reference round-robins requests over per-device
model replicas. On TPU one compiled program already uses every chip in
the mesh, so SEQUENTIAL degenerates to direct calls; the valuable part is
BATCHED mode — coalescing concurrent small requests into one padded
batch so the MXU runs full tiles. Batch sizes are bucketed to powers of
two (hard-capped at next_pow2(batch_limit)) to bound XLA recompilation.

Pipelined data plane (perf): the batcher is a two-stage pipeline.
The ASSEMBLER stage coalesces requests directly into a preallocated
padded bucket buffer (one copy, no intermediate np.concatenate),
dispatches `net.output` and hands the *in-flight device value* to the
COMPLETION stage without blocking on the host fetch — JAX dispatch is
async, so batch N+1 assembles and dispatches while batch N computes.
The completion stage performs the host fetch (the 4-6 ms per-dispatch
RTT measured in PERF.md), slices rows back to their callers, and
returns the staging buffer to the pool. `completion_streams` (default
2) completion threads pay fetch RTTs CONCURRENTLY — with one stream a
slow fetch serializes the window even though the device is free.
Completions may land out of dispatch order; per-row-range delivery
makes that harmless. The in-flight window is bounded
(`pipeline_depth`), so backpressure still cascades: window full ->
assembler stalls -> request queue fills -> `output()` sheds load.
`pipeline_depth=0` degrades to the serialized dispatch-then-fetch loop.

Multi-input coalescing: a request may carry one array per network
input (`output(x_a, x_b)` — ComputationGraph-style named inputs), all
sharing the batch dim. Each input stream coalesces into its own pooled
bucket buffer and the batch dispatches as `net.output(*bufs)`;
multi-output models deliver a list of arrays per caller.

Compile-once guards: `warmup=True` pre-traces `net.output` for every
power-of-two bucket up to the cap at construction (shape derived from
the net's configured InputType), and `stats()` surfaces the net's
JitCache trace counters so "zero new traces under mixed-size load" is
an asserted regression property. `adaptive_wait` shrinks the batching
wait when the queue is deep (a full batch is already waiting — waiting
adds latency, not throughput) and grows it back while idle.

Graceful degradation (resilience subsystem): the request queue is
bounded and `output()` sheds load with OverloadedError instead of
blocking when it fills; every wait carries a deadline so a dead
pipeline thread surfaces as InferenceUnavailableError rather than a
hang; `shutdown()` fails fast — queued, in-flight, and carried requests
are signaled with ShutdownError, and the front-end reports itself
unhealthy via `healthy` (the /healthz source of truth in serving.py).
Death of EITHER pipeline stage (fault points `inference.batch` and
`inference.complete`) drains every waiter.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.observability import metrics as _obs
from deeplearning4j_tpu.observability.metrics import COUNT_BUCKETS
from deeplearning4j_tpu.resilience.errors import (
    DeadlineExceededError,
    InferenceUnavailableError,
    OverloadedError,
    ShutdownError,
)
from deeplearning4j_tpu.resilience.faults import fire as _fire

logger = logging.getLogger("deeplearning4j_tpu")

# warn once per process when warmup is silently impossible (underivable
# input shape) — tests may reset this to re-observe the warning
_WARMUP_SKIP_WARNED = False


class InferenceMode:
    SEQUENTIAL = "sequential"
    BATCHED = "batched"


# priority classes (mirrors serving/admission.py PRIORITY_CLASSES —
# kept literal here so the data plane never imports the control plane)
_PRIORITY_IDX = {"high": 0, "normal": 1, "low": 2}


class _RequestQueue(queue.Queue):
    """Bounded request queue with priority-class ordering: admitted
    requests dequeue high-before-normal-before-low, FIFO within one
    class — under a deep queue an admitted high-priority request no
    longer waits behind a wall of admitted normals.

    Built on queue.Queue's documented `_init/_qsize/_put/_get`
    extension points (the same mechanism queue.PriorityQueue uses), so
    the mutex and condition variables stay the stdlib-created C locks —
    load-bearing: daemon pipeline threads wait on them through
    interpreter finalization, where a pure-Python acquire frame is
    fatal (see analysis/sanitizers.py DEFAULT_SCOPE)."""

    def _init(self, maxsize: int) -> None:
        self._by_class = tuple(deque() for _ in range(3))

    def _qsize(self) -> int:
        return sum(len(d) for d in self._by_class)

    def _put(self, item) -> None:
        self._by_class[getattr(item, "priority_idx", 1)].append(item)

    def _get(self):
        for d in self._by_class:
            if d:
                return d.popleft()
        raise queue.Empty   # unreachable: guarded by queue.Queue's CV


class _Pending:
    """One caller's request — one or more equal-row input arrays (a
    multi-input ComputationGraph request is a tuple of named-input
    streams sharing one batch dim). Large requests may be split across
    several dispatched batches (bucket-cap overshoot guard); `deliver`
    collects row ranges per output stream and resolves once every row
    has arrived. Deliveries for one request never race (each row range
    lives in exactly one batch and batches touch disjoint ranges), so
    no lock of its own is needed."""

    __slots__ = ("xs", "event", "result", "_left", "_out", "span",
                 "priority_idx")

    def __init__(self, xs, priority_idx: int = 1):
        self.xs = xs               # tuple of per-input arrays
        self.event = threading.Event()
        self.result = None
        self._left = xs[0].shape[0]
        self._out = None           # list of per-output buffers (splits)
        self.span = None   # open request span (tracer attached only)
        self.priority_idx = priority_idx   # dequeue class (0 first)

    @property
    def rows(self) -> int:
        return self.xs[0].shape[0]

    def resolve(self, result):
        if not self.event.is_set():
            self.result = result
            self.event.set()
            if self.span is not None:
                try:
                    self.span.end(
                        error=type(result).__name__
                        if isinstance(result, Exception) else None)
                except Exception:   # noqa: BLE001 - telemetry best-effort
                    pass

    def deliver(self, start: int, rows_list: List[np.ndarray],
                multi: bool) -> bool:
        """Hand this request `rows_list` (one array per model OUTPUT)
        covering its rows [start, start+n). Returns True when the
        delivery completed the request. `multi` keeps the resolved
        shape honest: single-output models resolve to a bare array."""
        if self.event.is_set():
            return False
        n = self.xs[0].shape[0]
        got = rows_list[0].shape[0]
        if self._out is None and start == 0 and got == n:
            # whole request in one batch (the common case)
            self.resolve(list(rows_list) if multi else rows_list[0])
            return True
        if self._out is None:
            self._out = [np.empty((n,) + r.shape[1:], r.dtype)
                         for r in rows_list]
        for out, r in zip(self._out, rows_list):
            out[start:start + got] = r
        self._left -= got
        if self._left <= 0:
            self.resolve(self._out if multi else self._out[0])
            return True
        return False


# slot = (pending, src_row_start, n_rows): one contiguous row range of a
# request placed in the batch currently being assembled
_Slot = Tuple[_Pending, int, int]


class ParallelInference:
    """Thread-safe inference front-end over a trained network.

    Builder parity: workers ~ mesh size (implicit), batch_limit,
    queue_limit. `default_timeout_s` bounds every `output()` call
    (per-call override via the `timeout_s` kwarg)."""

    def __init__(self, net, inference_mode: str = InferenceMode.BATCHED,
                 batch_limit: int = 32, queue_limit: int = 64,
                 max_wait_ms: float = 2.0,
                 default_timeout_s: float = 30.0,
                 pipeline_depth: int = 2,
                 warmup: bool = True,
                 adaptive_wait: bool = True,
                 min_wait_ms: float = 0.0,
                 warmup_inputs=None,
                 completion_streams: int = 2,
                 tracer=None):
        """`warmup_inputs`: per-example input shapes for nets whose
        shape is underivable from the conf (stub nets, graphs without
        input types) — a sequence with one entry per network input,
        each either a shape tuple (no batch dim) or an example array
        whose leading dim is the batch. Multi-input ComputationGraphs
        with configured input types derive their shapes automatically;
        without either, warmup is skipped (warned once per process).

        `completion_streams`: how many completion-stage threads pay
        host-fetch RTTs concurrently (default 2 — one fetch at a time
        was the recorded PR 2 gap). Only meaningful with
        pipeline_depth > 0; completions may finish out of dispatch
        order, which per-row delivery makes harmless.

        `tracer` (observability.Tracer, optional): records per-request
        spans (enqueue→…→deliver) and per-batch spans on BOTH pipeline
        stages, explicitly parented across the assembler / completion
        threads. None (default) costs the hot path nothing."""
        self.net = net
        self.tracer = tracer
        self.warmup_inputs = warmup_inputs
        self.mode = inference_mode
        self.batch_limit = batch_limit
        self.max_wait_ms = max_wait_ms
        self.min_wait_ms = min_wait_ms
        self.adaptive_wait = adaptive_wait
        self.default_timeout_s = default_timeout_s
        self.pipeline_depth = max(0, int(pipeline_depth))
        self.completion_streams = max(1, int(completion_streams))
        self._cap = self._bucket(batch_limit)   # hard bucket-shape ceiling
        self._queue: "queue.Queue[_Pending]" = _RequestQueue(
            maxsize=queue_limit)
        self._lock = threading.Lock()
        self._count_lock = threading.Lock()   # _inflight_n (k completers)
        self._stop = threading.Event()
        self._shutdown = False
        self._failure: Optional[BaseException] = None
        self._worker: Optional[threading.Thread] = None
        self._completer: Optional[threading.Thread] = None
        self._completers: List[threading.Thread] = []
        self._inflight: Optional["queue.Queue"] = None
        # dispatched-but-not-completed batches, INCLUDING the one the
        # completion stage is currently fetching (queue size alone
        # undercounts it, which would let the assembler over-dispatch
        # undersized batches while the device is already saturated).
        # _slot_free wakes the assembler the moment a batch completes,
        # so the device never idles on a polling interval.
        self._inflight_n = 0
        self._slot_free = threading.Event()
        self._carry: Optional[Tuple[_Pending, int]] = None
        self._buf_pool: Dict[tuple, List[np.ndarray]] = {}
        self._wait_ms = float(max_wait_ms)
        self._warmed_buckets: List[int] = []
        self._batches_dispatched = 0
        self._requests_completed = 0
        # bucket -> [dispatches, real rows]: the pow2 fill accounting
        # the program lint's prog-excess-padding rule reads
        self._bucket_fill: Dict[int, List[int]] = {}
        if self.mode == InferenceMode.BATCHED:
            if warmup:
                self.warmup()
            if self.pipeline_depth > 0:
                self._inflight = queue.Queue()
                for i in range(self.completion_streams):
                    t = threading.Thread(
                        target=self._completion_loop, daemon=True,
                        name=f"ParallelInference-completer-{i}")
                    t.start()
                    self._completers.append(t)
                self._completer = self._completers[0]
            self._worker = threading.Thread(
                target=self._batch_loop, daemon=True,
                name="ParallelInference-batcher")
            self._worker.start()

    # ------------------------------------------------------------------
    @property
    def healthy(self) -> bool:
        """False once shut down or either pipeline thread has died."""
        if self._shutdown or self._failure is not None:
            return False
        if self.mode == InferenceMode.BATCHED:
            if self._worker is None or not self._worker.is_alive():
                return False
            if any(not t.is_alive() for t in self._completers):
                return False
        return True

    def queue_depth(self) -> int:
        return self._queue.qsize()

    @property
    def queue_limit(self) -> int:
        return self._queue.maxsize

    def trace_stats(self) -> dict:
        """The net's JitCache trace counters (empty for nets without
        one) — the recompile-regression observable — plus the compile-
        event forensics ring (signature, duration, cost digest per new
        trace) so /status can answer "what recompiled, and why"."""
        cache = getattr(self.net, "_jit_cache", None)
        if cache is None or not hasattr(cache, "trace_counts"):
            return {}
        out = {"trace_counts": cache.trace_counts(),
               "total_traces": cache.total_traces()}
        if hasattr(cache, "compile_events"):
            out["compiles_total"] = cache.compiles_total()
            out["compile_events"] = cache.compile_events()
        return out

    def bucket_fill(self) -> Dict[int, dict]:
        """Per-bucket padding accounting: {bucket: {dispatches, rows,
        fill}} where fill = real rows / (dispatches * bucket). The pow2
        coalescer guarantees fill > 0.5 per dispatch; the program
        lint's prog-excess-padding rule pins that invariant."""
        return {b: {"dispatches": d, "rows": r,
                    "fill": (r / (d * b)) if d else 0.0}
                for b, (d, r) in sorted(self._bucket_fill.items())}

    def lint_records(self) -> list:
        """ProgramRecords for the serving data plane: the net's cached
        predict program at the largest warmed bucket signature (with
        its registered precision policy) plus one fill-ratio record per
        dispatched bucket — the `--programs` registry entries for this
        front-end (analysis/program_lint)."""
        from deeplearning4j_tpu.analysis.program_lint import (
            ProgramRecord,
        )

        source = "deeplearning4j_tpu/parallel/inference.py"
        records = []
        cache = getattr(self.net, "_jit_cache", None)
        fn = cache.get("predict") if cache is not None else None
        shapes = self._warmup_shapes()
        if fn is not None and shapes:
            b = max(self._warmed_buckets or [self._cap])
            xs = [np.zeros((b,) + s, np.float32) for s in shapes]
            names = getattr(self.net.conf, "network_inputs", None)
            if names:   # ComputationGraph predict takes {name: x}
                args = (self.net.params, self.net.states,
                        dict(zip(names, xs)))
            else:
                args = (self.net.params, self.net.states, xs[0])
            records.append(ProgramRecord(
                name="serving_predict", fn=getattr(fn, "__wrapped__", fn),
                example_args=args,
                precision_policy=(cache.policy("predict")
                                  if hasattr(cache, "policy") else None),
                source=source))
        for b, agg in self.bucket_fill().items():
            records.append(ProgramRecord(
                name=f"serving_bucket_{b}", source=source,
                bucket_capacity=b,
                bucket_rows_per_dispatch=(
                    agg["rows"] / agg["dispatches"]
                    if agg["dispatches"] else 0.0)))
        return records

    def stats(self) -> dict:
        """Pipeline + compile-guard facts (surfaced on /status)."""
        out = {
            "pipeline_depth": self.pipeline_depth,
            "completion_streams": (self.completion_streams
                                   if self.pipeline_depth > 0 else 0),
            "in_flight": self._inflight_n,
            "queue_depth": self._queue.qsize(),
            "batches_dispatched": self._batches_dispatched,
            "requests_completed": self._requests_completed,
            "bucket_cap": self._cap,
            "warmed_buckets": list(self._warmed_buckets),
            "bucket_fill": self.bucket_fill(),
            "current_wait_ms": round(self._wait_ms, 4),
            "adaptive_wait": self.adaptive_wait,
        }
        out.update(self.trace_stats())
        return out

    # ------------------------------------------------------------ warmup
    def _warmup_tail_shape(self) -> Optional[tuple]:
        """Per-example input shape from the net's configured InputType
        (None when underivable, e.g. stub nets / multi-input graphs)."""
        conf = getattr(self.net, "conf", None)
        input_type = getattr(conf, "input_type", None)
        if input_type is None:
            return None
        try:
            return tuple(input_type.batch_shape(1))[1:]
        except Exception:   # noqa: BLE001 - underivable shape: skip
            return None

    def _warmup_shapes(self) -> Optional[List[tuple]]:
        """Per-example shape for every network input: explicit
        `warmup_inputs` first, then multi-input ComputationGraph input
        types, then the single-input conf InputType; None when
        underivable every way."""
        if self.warmup_inputs is not None:
            shapes = []
            for w in self.warmup_inputs:
                if isinstance(w, (tuple, list)) and all(
                        isinstance(d, (int, np.integer)) for d in w):
                    shapes.append(tuple(int(d) for d in w))
                else:
                    shapes.append(tuple(np.asarray(w).shape[1:]))
            return shapes
        conf = getattr(self.net, "conf", None)
        names = getattr(conf, "network_inputs", None)
        itypes = getattr(conf, "input_types", None)
        if names and itypes and set(itypes) >= set(names):
            try:
                return [tuple(itypes[n].batch_shape(1))[1:]
                        for n in names]
            except Exception:   # noqa: BLE001 - underivable shape: skip
                pass
        tail = self._warmup_tail_shape()
        return None if tail is None else [tail]

    def warmup(self) -> List[int]:
        """Pre-trace `net.output` for every power-of-two bucket up to
        the cap, so a mixed-size request load causes ZERO new traces
        (each one a full XLA recompile on TPU). Returns the buckets
        traced; skipped (with a once-per-process warning) when the
        input shape is underivable and no `warmup_inputs` were given."""
        shapes = self._warmup_shapes()
        if shapes is None:
            global _WARMUP_SKIP_WARNED
            if not _WARMUP_SKIP_WARNED:
                _WARMUP_SKIP_WARNED = True
                logger.warning(
                    "ParallelInference: warmup skipped — per-example "
                    "input shape underivable (multi-input graph or "
                    "stub net); pass warmup_inputs=[shape, ...] to "
                    "pre-trace buckets and avoid first-request "
                    "recompiles")
            return []
        done = []
        b = 1
        while b <= self._cap:
            xs = [np.zeros((b,) + s, np.float32) for s in shapes]
            with self._lock:
                out = (self.net.output(*xs) if len(xs) > 1
                       else self.net.output(xs[0]))
                for o in (out if isinstance(out, (list, tuple))
                          else [out]):
                    np.asarray(o)            # block: compile now
            done.append(b)
            b <<= 1
        self._warmed_buckets = done
        return done

    # ------------------------------------------------------------------
    def _check_available(self):
        if self._shutdown:
            raise ShutdownError("ParallelInference is shut down")
        if self._failure is not None:
            raise InferenceUnavailableError(
                f"batcher thread died: {self._failure!r}")
        if self.mode == InferenceMode.BATCHED and self._threads_dead():
            raise InferenceUnavailableError("batcher thread is not running")

    def _threads_dead(self) -> bool:
        if self._worker is None or not self._worker.is_alive():
            return True
        return (self._completer is not None
                and not self._completer.is_alive())

    def output(self, *xs, timeout_s: Optional[float] = None,
               priority: Optional[str] = None):
        """Run inference; raises OverloadedError when the bounded queue
        is full (shed load, don't queue unbounded latency) and
        DeadlineExceededError / InferenceUnavailableError instead of
        hanging when the pipeline stalls or dies.

        `priority` ("high"/"normal"/"low", default normal — the
        admission layer passes the tenant's class): admitted requests
        DEQUEUE high-before-normal-before-low under a deep queue, FIFO
        within a class; admission sheds by class before the queue,
        this orders within it.

        Multi-input graphs pass one array per network input
        (`pi.output(x_a, x_b)`), all sharing the batch dim — the
        streams coalesce through the same pooled-bucket path, one
        bucket buffer per input. Multi-output models resolve to a list
        of arrays (single-output stays a bare array)."""
        xs = tuple(np.asarray(x) for x in xs)
        if not xs:
            raise ValueError("output() needs at least one input array")
        if any(x.shape[0] != xs[0].shape[0] for x in xs[1:]):
            raise ValueError(
                "all inputs must share the batch dim: "
                f"{[x.shape[0] for x in xs]}")
        if timeout_s is None:
            timeout_s = self.default_timeout_s
        if self.mode == InferenceMode.SEQUENTIAL:
            self._check_available()
            with self._lock:
                out = self.net.output(*xs)
                return ([np.asarray(o) for o in out]
                        if isinstance(out, (list, tuple))
                        else np.asarray(out))
        self._check_available()
        p = _Pending(xs, priority_idx=_PRIORITY_IDX.get(priority, 1))
        if self.tracer is not None:
            try:
                p.span = self.tracer.begin(
                    "request", cat="serving",
                    args={"rows": int(xs[0].shape[0])})
            except Exception:   # noqa: BLE001 - telemetry best-effort
                p.span = None
        try:
            self._queue.put_nowait(p)
        except queue.Full:
            if p.span is not None:
                p.span.end(error="OverloadedError")
            raise OverloadedError(
                f"inference queue full ({self._queue.maxsize} waiting); "
                "retry later") from None
        deadline = time.monotonic() + timeout_s
        # poll in slices: a pipeline thread that dies *after* the put but
        # before its own drain would otherwise strand this waiter
        while not p.event.wait(timeout=min(
                0.05, max(0.0, deadline - time.monotonic()))):
            if p.event.is_set():
                break
            if (self._failure is not None or self._shutdown
                    or self._threads_dead()):
                self._drain(self._unavailable_error())
                if not p.event.is_set():
                    p.resolve(self._unavailable_error())
            elif time.monotonic() >= deadline:
                raise DeadlineExceededError(
                    f"inference did not complete within {timeout_s}s")
        if isinstance(p.result, Exception):
            raise p.result
        return p.result

    def _unavailable_error(self) -> Exception:
        if self._shutdown and self._failure is None:
            return ShutdownError(
                "ParallelInference shut down with requests in flight")
        return InferenceUnavailableError(
            f"batcher thread died: {self._failure!r}")

    def shutdown(self):
        """Fail fast: stop both pipeline stages, then signal every
        queued / in-flight request with ShutdownError so no caller is
        left hanging."""
        self._shutdown = True
        self._stop.set()
        if self._worker is not None:
            self._worker.join(timeout=2.0)
        for t in self._completers:
            t.join(timeout=2.0)
        err = ShutdownError(
            "ParallelInference shut down with requests in flight")
        self._drain(err)
        self._drain_inflight(err)

    def _drain(self, error: Exception):
        """Signal everything still queued (and any carried split
        request) with `error`."""
        carry = self._carry
        self._carry = None
        if carry is not None and not carry[0].event.is_set():
            carry[0].resolve(error)
        while True:
            try:
                p = self._queue.get_nowait()
            except queue.Empty:
                return
            if not p.event.is_set():
                p.resolve(error)

    def _drain_inflight(self, error: Exception):
        if self._inflight is None:
            return
        while True:
            try:
                _, slots, keys, bufs, _ = self._inflight.get_nowait()
            except queue.Empty:
                return
            with self._count_lock:
                self._inflight_n -= 1
            for p, _, _ in slots:
                p.resolve(error)
            self._put_buffers(keys, bufs)

    # ------------------------------------------------------------------
    @staticmethod
    def _bucket(n: int) -> int:
        b = 1
        while b < n:
            b <<= 1
        return b

    # ------------------------------------------------------- bucket pool
    def _get_buffer(self, key: tuple) -> np.ndarray:
        pool = self._buf_pool.get(key)
        if pool:
            return pool.pop()
        bucket, tail, dtype_str = key
        return np.zeros((bucket,) + tail, np.dtype(dtype_str))

    def _put_buffer(self, key: tuple, buf: np.ndarray):
        # bounded: at most window+1 buffers alive per bucket shape
        pool = self._buf_pool.setdefault(key, [])
        if len(pool) <= self.pipeline_depth:
            pool.append(buf)

    def _put_buffers(self, keys: List[tuple], bufs: List[np.ndarray]):
        for key, buf in zip(keys, bufs):
            self._put_buffer(key, buf)

    # --------------------------------------------------- adaptive wait
    def _current_wait_s(self) -> float:
        if not self.adaptive_wait:
            return self.max_wait_ms / 1000.0
        if self._queue.qsize() >= self.batch_limit:
            return 0.0   # a full batch is already waiting
        return self._wait_ms / 1000.0

    def _adapt_wait(self, rows: int):
        if not self.adaptive_wait:
            return
        if rows >= self.batch_limit:
            # deep queue: batches fill instantly — waiting only adds
            # latency, so shrink toward min_wait_ms
            self._wait_ms = max(self.min_wait_ms, self._wait_ms * 0.5)
        elif self._queue.qsize() == 0:
            # idle: grow back toward max_wait_ms so sparse traffic still
            # coalesces into full tiles
            self._wait_ms = min(self.max_wait_ms,
                                self._wait_ms * 1.5 + 0.05)

    # ------------------------------------------------------- assembler
    def _collect(self) -> Tuple[List[_Slot], int]:
        """Gather up to batch_limit rows: the carried remainder of a
        split request first, then queued requests. A request that would
        push past batch_limit is split — its overflow rows carry into
        the NEXT batch, so no bucket ever exceeds the cap."""
        slots: List[_Slot] = []
        rows = 0
        limit = self.batch_limit
        if self._carry is not None:
            p, src = self._carry
            self._carry = None
            take = min(p.rows - src, limit)
            slots.append((p, src, take))
            rows += take
            if src + take < p.rows:
                self._carry = (p, src + take)
                return slots, rows
        else:
            try:
                first = self._queue.get(timeout=0.05)
            except queue.Empty:
                return slots, 0
            take = min(first.rows, limit)
            slots.append((first, 0, take))
            rows += take
            if take < first.rows:
                self._carry = (first, take)
                _obs.count("dl4j_serving_bucket_splits_total")
                return slots, rows
        wait_s = self._current_wait_s()
        t0 = time.monotonic()
        while rows < limit:
            # while the in-flight window is full the device is the
            # bottleneck — dispatching a partial batch now would only
            # shrink coalescing, so keep collecting until a slot frees
            window_full = (self._inflight is not None
                           and self._inflight_n >= self.pipeline_depth)
            if window_full:
                try:
                    p = self._queue.get_nowait()
                except queue.Empty:
                    if self._stop.is_set() or self._failure is not None:
                        break
                    self._slot_free.clear()
                    if self._inflight_n >= self.pipeline_depth:
                        self._slot_free.wait(timeout=0.05)
                    continue
            else:
                remaining = wait_s - (time.monotonic() - t0)
                if remaining <= 0 and self._queue.empty():
                    break
                try:
                    p = self._queue.get(timeout=max(0.0, remaining))
                except queue.Empty:
                    break
            take = min(p.rows, limit - rows)
            slots.append((p, 0, take))
            rows += take
            if take < p.rows:
                self._carry = (p, take)
                _obs.count("dl4j_serving_bucket_splits_total")
                break
        return slots, rows

    def _assemble(self, slots: List[_Slot], rows: int):
        """Coalesce request rows directly into pooled padded bucket
        buffers — ONE copy per input stream, no intermediate
        concatenate allocations. Multi-input requests fill one buffer
        per network input; every request in the batch must carry the
        same input arity."""
        n_inputs = len(slots[0][0].xs)
        if any(len(p.xs) != n_inputs for p, _, _ in slots):
            raise ValueError(
                "mixed input arity in one batch: all requests to a "
                f"model must carry {n_inputs} input(s)")
        bucket = self._bucket(rows)
        keys: List[tuple] = []
        bufs: List[np.ndarray] = []
        for i in range(n_inputs):
            x0 = slots[0][0].xs[i]
            tail = x0.shape[1:]
            dtype = np.result_type(*[p.xs[i].dtype
                                     for p, _, _ in slots]) \
                if len(slots) > 1 else x0.dtype
            key = (bucket, tail, np.dtype(dtype).str)
            buf = self._get_buffer(key)
            ofs = 0
            for p, src, n in slots:
                buf[ofs:ofs + n] = p.xs[i][src:src + n]
                ofs += n
            if bucket > rows:
                buf[rows:bucket] = 0   # pooled buffers carry stale rows
            keys.append(key)
            bufs.append(buf)
        return keys, bufs

    def _batch_loop(self):
        try:
            while not self._stop.is_set() and self._failure is None:
                # chaos hook: a 'raise' here kills the assembler thread —
                # the graceful-degradation drill for the serving path
                _fire("inference.batch")
                slots, rows = self._collect()
                if not slots:
                    continue
                # assembler-stage span: explicitly parented to the
                # FIRST request's span — the request started on a
                # caller thread, this stage runs on the batcher thread
                dspan = None
                if self.tracer is not None:
                    try:
                        dspan = self.tracer.begin(
                            "assemble_dispatch", cat="serving",
                            parent=slots[0][0].span,
                            args={"rows": rows, "slots": len(slots)})
                    except Exception:   # noqa: BLE001 - telemetry
                        dspan = None
                try:
                    keys, bufs = self._assemble(slots, rows)
                except Exception as e:   # per-batch: propagate to callers
                    for p, _, _ in slots:
                        p.resolve(e)
                    if dspan is not None:
                        dspan.end(error=type(e).__name__)
                    continue
                try:
                    with self._lock:
                        # async dispatch: hand the in-flight device value
                        # to the completion stage; do NOT block on the
                        # host fetch here
                        out = self.net.output(
                            *[jnp.asarray(b) for b in bufs])
                except Exception as e:   # per-batch: propagate to callers
                    for p, _, _ in slots:
                        p.resolve(e)
                    self._put_buffers(keys, bufs)
                    if dspan is not None:
                        dspan.end(error=type(e).__name__)
                    continue
                self._batches_dispatched += 1
                agg = self._bucket_fill.setdefault(keys[0][0], [0, 0])
                agg[0] += 1
                agg[1] += rows
                _obs.count_observe(
                    "dl4j_serving_batches_total",
                    "dl4j_serving_batch_occupancy", rows,
                    buckets=COUNT_BUCKETS)
                _obs.set_gauge("dl4j_serving_queue_depth",
                               self._queue.qsize())
                if dspan is not None:
                    dspan.end()
                self._adapt_wait(rows)
                if self._completer is None:
                    self._complete_batch(out, slots, keys, bufs, dspan)
                else:
                    self._submit_inflight((out, slots, keys, bufs, dspan))
        except BaseException as e:   # noqa: BLE001 - loop-level death
            # assembler death is a degradation event, not a hang: record
            # it (flips `healthy` and /healthz), then fail every waiter
            self._failure = e
        finally:
            if self._failure is not None:
                self._drain(self._unavailable_error())
            elif self._stop.is_set():
                self._drain(ShutdownError(
                    "ParallelInference shut down with requests in flight"))

    def _submit_inflight(self, item):
        """Bounded in-flight window: block until the completion stage
        frees a slot (backpressure), never past stop/death."""
        while True:
            if self._stop.is_set() or self._failure is not None or any(
                    not t.is_alive() for t in self._completers):
                _, slots, keys, bufs, _ = item
                err = self._unavailable_error() \
                    if not self._stop.is_set() else ShutdownError(
                        "ParallelInference shut down with requests "
                        "in flight")
                for p, _, _ in slots:
                    p.resolve(err)
                self._put_buffers(keys, bufs)
                return
            if self._inflight_n >= self.pipeline_depth:
                self._slot_free.clear()
                if self._inflight_n >= self.pipeline_depth:
                    self._slot_free.wait(timeout=0.05)
                continue
            with self._count_lock:
                self._inflight_n += 1
            _obs.set_gauge("dl4j_serving_inflight_batches",
                           self._inflight_n)
            self._inflight.put(item)
            return

    # ------------------------------------------------------- completion
    def _complete_batch(self, out, slots: List[_Slot], keys, bufs,
                        dspan=None):
        # completion-stage span: parented to the assembler's dispatch
        # span — a cross-THREAD edge when the completer is running
        cspan = None
        if self.tracer is not None and dspan is not None:
            try:
                cspan = self.tracer.begin(
                    "complete_deliver", cat="serving", parent=dspan,
                    args={"slots": len(slots)})
            except Exception:   # noqa: BLE001 - telemetry best-effort
                cspan = None
        multi = isinstance(out, (list, tuple))
        outs = list(out) if multi else [out]
        hosts: List[np.ndarray] = []
        try:
            for o in outs:
                hosts.append(np.asarray(o))  # host fetch: blocks here
        except Exception as e:   # per-batch: propagate to callers
            for p, _, _ in slots:
                p.resolve(e)
            self._put_buffers(keys, bufs)
            if cspan is not None:
                cspan.end(error=type(e).__name__)
            return
        for i, h in enumerate(hosts):
            if any(np.may_share_memory(h, b) for b in bufs):
                # jnp.asarray can zero-copy-alias the staging buffer on
                # CPU and identity-ish models can echo it back: never
                # hand callers views into a buffer the pool will
                # overwrite
                hosts[i] = h.copy()
        self._put_buffers(keys, bufs)   # compute done: buffers reusable
        ofs = 0
        done = 0
        for p, src, n in slots:
            if p.deliver(src, [h[ofs:ofs + n] for h in hosts], multi):
                done += 1
            ofs += n
        if done:
            with self._count_lock:   # k completers share this counter
                self._requests_completed += done
        if cspan is not None:
            cspan.end()

    def _completion_loop(self):
        try:
            while not self._stop.is_set() and self._failure is None:
                # chaos hook: completion-stage death must degrade as
                # gracefully as assembler death
                _fire("inference.complete")
                try:
                    item = self._inflight.get(timeout=0.05)
                except queue.Empty:
                    continue
                try:
                    self._complete_batch(*item)
                finally:
                    with self._count_lock:
                        self._inflight_n -= 1
                    _obs.set_gauge("dl4j_serving_inflight_batches",
                                   self._inflight_n)
                    self._slot_free.set()
        except BaseException as e:   # noqa: BLE001 - loop-level death
            self._failure = e
        finally:
            if self._failure is not None:
                self._drain_inflight(self._unavailable_error())
                self._drain(self._unavailable_error())
            elif self._stop.is_set():
                self._drain_inflight(ShutdownError(
                    "ParallelInference shut down with requests in flight"))
