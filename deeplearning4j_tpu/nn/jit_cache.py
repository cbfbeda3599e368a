"""JitCache: a jit-program cache that counts traces AND explains them.

XLA compiles one program per (function, input signature); an unexpected
shape reaching a cached `jax.jit` function silently triggers a retrace
plus a full recompile — the compile-once concern the TPU-compilation
literature identifies as make-or-break for serving latency. The cache
is still a plain dict of jitted callables with a thread-safe trace
counter incremented from *inside* each traced function body (a Python
side effect in a traced function runs exactly once per trace), so "did
this load cause a recompile?" is an asserted property.

Recompile FORENSICS (the "why did step 1042 take 8s" instrument):
`__setitem__` wraps every stored callable in a thin timing shim. A call
whose trace counter advanced included a trace+compile; the shim records
a compile event — the concrete shape/dtype signature of the args that
caused it, the call's wall duration (dominated by trace+compile on a
compile call) and a wall-clock timestamp — into a bounded ring surfaced
on /status, and bumps `dl4j_jit_compiles_total`. Calls that hit the
compiled cache pay two perf_counter reads and one int compare.

    cache = JitCache()
    def f(x):
        cache.record_trace("predict")
        return x * 2
    cache["predict"] = jax.jit(f)
    ...
    cache.compile_events()   # [{key, signature, duration_s, ...}]
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from deeplearning4j_tpu.observability import metrics as _obs

COMPILE_RING = 16

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """Where XLA's persistent compilation cache lives — the ONE place
    this repository decides it; every entry point (chip_smoke.py,
    benchmark/run.py, __graft_entry__, tests/conftest.py) calls this
    and sets no directory itself. `JAX_COMPILATION_CACHE_DIR` wins: jax
    reads it on its own, so nothing is set here and whoever runs the
    program places the cache. Otherwise `<checkout>/.jax_cache`
    (git-ignored). The path is part of every entry's key, so it is
    fixed: never built from a temp name, a pid, a version or a time.
    An entry's key takes in the program's metadata: the names that
    `jax.named_scope` gives the operations are what a device trace is
    summed by (benchmark/timeline.py), and jax leaves them out of the
    key by default, so that an executable cached before a scope was
    added or renamed would come back with its old names. An operation's
    location is then cut to its own source line, without its callers'
    (one program reached from two call sites would be two entries).
    Not by `jax_include_full_tracebacks_in_locations=False`: that takes
    the scopes out of the compiled operations' names (measured on the
    chip, PERF.md PR 26).
    Returns the directory in use."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      True)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def policy_name(compute_dtype) -> str:
    """Canonical short name of a net's compute-precision policy:
    'bf16'/'f16' for mixed precision, 'f32' when no compute dtype is
    set. This is the DECLARED intent the program lint checks lowered
    programs against (prog-fp32-matmul-under-policy) — a declared fact
    at registration time, never a guess from the jaxpr."""
    if compute_dtype is None:
        return "f32"
    import numpy as np

    try:
        name = np.dtype(compute_dtype).name
    except TypeError:
        name = getattr(compute_dtype, "__name__", str(compute_dtype))
    return {"bfloat16": "bf16", "float16": "f16", "float32": "f32",
            "float64": "f64"}.get(name, name)


def _describe(a, depth: int = 0) -> str:
    """Compact signature of one argument: arrays as dtype[shape],
    containers abbreviated to their first few entries."""
    shape = getattr(a, "shape", None)
    dtype = getattr(a, "dtype", None)
    if shape is not None and dtype is not None:
        dims = ",".join(str(int(d)) for d in shape)
        return f"{dtype}[{dims}]"
    if depth >= 3:
        return type(a).__name__
    if isinstance(a, (list, tuple)):
        head = ",".join(_describe(v, depth + 1) for v in a[:3])
        tail = f",…+{len(a) - 3}" if len(a) > 3 else ""
        return f"[{head}{tail}]"
    if isinstance(a, dict):
        head = ",".join(f"{k}:{_describe(v, depth + 1)}"
                        for k, v in list(a.items())[:3])
        tail = f",…+{len(a) - 3}" if len(a) > 3 else ""
        return "{" + head + tail + "}"
    if a is None:
        return "None"
    return type(a).__name__


def describe_signature(args, kwargs=None) -> str:
    parts = [_describe(a) for a in args]
    for k, v in (kwargs or {}).items():
        parts.append(f"{k}={_describe(v)}")
    return "(" + ", ".join(parts) + ")"


class JitCache(dict):
    """Dict of jitted programs + per-key trace counters + a compile-
    event forensics ring.

    Counters survive `clear()` of the program dict deliberately: a
    cleared cache that re-traces is exactly the recompile event the
    counters exist to expose."""

    def __init__(self, *args, compile_ring: int = COMPILE_RING,
                 **kwargs):
        super().__init__()
        self._trace_lock = threading.Lock()
        self._trace_counts: Dict[str, int] = {}
        # lock-free fast-path read for the call shim (GIL-atomic int);
        # writes stay under the lock
        self._total = 0
        self._compiles = 0
        self._compile_events: deque = deque(
            maxlen=max(1, int(compile_ring)))
        self._policies: Dict[str, str] = {}
        for k, v in dict(*args, **kwargs).items():
            self[k] = v

    def record_trace(self, key: str) -> None:
        """Call from inside a to-be-jitted function body: runs once per
        trace (= once per compiled specialization), never at runtime."""
        with self._trace_lock:
            self._trace_counts[key] = self._trace_counts.get(key, 0) + 1
            self._total += 1

    def trace_counts(self) -> Dict[str, int]:
        with self._trace_lock:
            return dict(self._trace_counts)

    def total_traces(self) -> int:
        with self._trace_lock:
            return sum(self._trace_counts.values())

    # ------------------------------------------------------- forensics
    def __setitem__(self, key, fn):
        if callable(fn) and not getattr(fn, "_jit_cache_shim", False):
            fn = self._instrument(key, fn)
        super().__setitem__(key, fn)

    def _instrument(self, key, fn):
        """Timing shim: a call during which the trace counter advanced
        included a trace+compile — record the forensics event. The
        no-compile fast path pays two clock reads and an int compare."""
        def call(*args, **kwargs):
            before = self._total
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            if self._total != before:
                self._note_compile(key, self._total - before,
                                   time.perf_counter() - t0,
                                   args, kwargs)
            return out

        call._jit_cache_shim = True
        call.__wrapped__ = fn
        return call

    def _note_compile(self, key, traces: int, duration_s: float,
                      args, kwargs) -> None:
        try:
            signature = describe_signature(args, kwargs)
        except Exception:   # noqa: BLE001 - forensics is best-effort
            signature = "<unavailable>"
        event = {
            "key": str(key),
            "signature": signature,
            "duration_s": round(duration_s, 6),
            "traces": int(traces),
            "wall_time": time.time(),
        }
        with self._trace_lock:
            self._compiles += int(traces)
            self._compile_events.append(event)
        _obs.count("dl4j_jit_compiles_total", n=int(traces))

    def register_policy(self, key, policy: str) -> None:
        """Declare the compute-precision policy of the program stored
        at `key` ('bf16'/'f16'/'f32' — see `policy_name`). The program
        lint reads this back so 'intended dtype' is a registered fact
        the lowered program is checked against."""
        with self._trace_lock:
            self._policies[str(key)] = str(policy)

    def policy(self, key) -> Optional[str]:
        with self._trace_lock:
            return self._policies.get(str(key))

    def policies(self) -> Dict[str, str]:
        with self._trace_lock:
            return dict(self._policies)

    def compile_events(self) -> List[dict]:
        """Snapshot of the recent-compiles ring, oldest first."""
        with self._trace_lock:
            return [dict(ev) for ev in self._compile_events]

    def compiles_total(self) -> int:
        with self._trace_lock:
            return self._compiles
