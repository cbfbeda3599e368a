"""Test configuration: the CPU platform with an 8-device virtual mesh.

Tests run on the CPU (the chip is checked by `chip_smoke.py`, through
the builder's chip tool); sharding/parallelism tests require multiple
devices, which XLA's host-platform device count simulates.
MUST run before the first `import jax` anywhere in the test session.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pathlib  # noqa: E402
import shutil  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from deeplearning4j_tpu.nn.jit_cache import place_compile_cache  # noqa: E402

# Persistent compile cache: repeat test runs skip XLA compilation.
_JAX_CACHE = pathlib.Path(place_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
_CACHE_SENTINEL = _JAX_CACHE / ".clean-exit"


def _owns_cache(config) -> bool:
    return (not hasattr(config, "workerinput")
            and "JAX_COMPILATION_CACHE_DIR" not in os.environ)


def pytest_configure(config):
    """A run killed mid-cache-write leaves a torn entry (jax writes
    entries in place), so a cache whose last session did not END is
    wiped, not trusted: the clean-exit sentinel is removed here and
    rewritten at session finish. Once per run — the xdist controller
    (or a plain single-process run) does it before any worker exists;
    a worker that did it as it imported would delete the cache the
    earlier workers are already writing. Only the checkout's own
    directory is ever wiped: one placed from outside through
    JAX_COMPILATION_CACHE_DIR is its owner's to clean.

    DL4J_TPU_SANITIZE=locks arms the runtime lock-order sanitizer
    for the whole session (the sanitized chaos-sweep recipe in
    pytest.ini): every threading.Lock/RLock created from here on is
    tracked, and _lock_order_check below fails any test on whose
    watch a new acquisition-order cycle appeared."""
    if _owns_cache(config):
        if _JAX_CACHE.exists() and not _CACHE_SENTINEL.exists():
            shutil.rmtree(_JAX_CACHE, ignore_errors=True)
        _JAX_CACHE.mkdir(parents=True, exist_ok=True)
        _CACHE_SENTINEL.unlink(missing_ok=True)
    if os.environ.get("DL4J_TPU_SANITIZE"):
        from deeplearning4j_tpu.analysis import sanitizers

        sanitizers.install_from_env()


def pytest_sessionfinish(session, exitstatus):
    # only a session that ENDED marks its cache trustworthy
    if _owns_cache(session.config):
        try:
            _CACHE_SENTINEL.touch()
        except OSError:
            pass


# GC-during-tracing hardening. The full suite intermittently died with
# "Fatal Python error: Segmentation fault ... Garbage-collecting" inside
# pjit partial-eval, always in the thread-heavy training tests (prefetch
# producers / inference batchers run JAX ops concurrently with
# main-thread tracing): a cyclic-GC pass landing mid-trace races
# jax's weakref-keyed caches. Freeze the post-import heap (the ~190
# extension modules are permanent; scanning them every collection is
# pure risk). Raising gen0's threshold only made mid-trace collections
# RARE — on a loaded box they still landed inside pjit staging (crash
# dumps at varying tests, always "Garbage-collecting" under
# partial_eval). Automatic collection is now OFF entirely: the only
# cyclic-GC passes are the explicit per-test ones below, on the main
# thread after teardown, when any leaked worker thread is idle in a
# queue wait rather than mid-trace. Memory stays bounded — every
# test's cyclic garbage is collected at its own finish line.
def pytest_sessionstart(session):
    import gc

    gc.collect()
    gc.freeze()
    gc.disable()


def pytest_runtest_logfinish(nodeid, location):
    import gc

    gc.collect()


@pytest.fixture
def rng():
    return np.random.default_rng(12345)


# ------------------------------------------------------------ hang guards
# pytest.ini's faulthandler_timeout dumps tracebacks on a stuck test but
# does not end it; this watchdog turns the hang into a TimeoutError so
# one bad test fails instead of eating the tier-1 time budget. SIGALRM
# interrupts even a bare `threading.Event().wait()` on the main thread.
_PER_TEST_TIMEOUT_S = 300


@pytest.fixture(autouse=True)
def _hang_guard(request):
    import signal
    import threading

    if (not hasattr(signal, "SIGALRM")
            or threading.current_thread() is not threading.main_thread()):
        yield
        return

    def _on_timeout(signum, frame):
        raise TimeoutError(
            f"test exceeded {_PER_TEST_TIMEOUT_S}s hang guard "
            f"({request.node.nodeid})")

    old = signal.signal(signal.SIGALRM, _on_timeout)
    signal.setitimer(signal.ITIMER_REAL, _PER_TEST_TIMEOUT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture(autouse=True)
def _reap_cluster_workers():
    """Chaos isolation for PROCESSES: a failing/interrupted cluster
    chaos test must not leak supervised worker processes (each spawned
    in its own process group) into later tier-1 runs — kill any process
    group the ClusterSupervisor still tracks on teardown. Lazy: touches
    nothing unless the cluster module was actually imported."""
    import sys as _sys

    yield
    mod = _sys.modules.get("deeplearning4j_tpu.resilience.cluster")
    if mod is not None:
        mod.reap_stray_workers()


@pytest.fixture(autouse=True)
def _reap_decode_engines():
    """Chaos isolation for DECODE LOOPS: a failing/interrupted decode
    durability test must not leak a DecodeEngine loop thread or armed
    StepWatchdog into later tests — stop every engine the continuous
    module still tracks on teardown (threads are named and joined).
    Lazy: touches nothing unless the module was actually imported."""
    import sys as _sys

    yield
    mod = _sys.modules.get("deeplearning4j_tpu.serving.continuous")
    if mod is not None:
        mod.reap_stray_engines()


@pytest.fixture(autouse=True)
def _reap_journals():
    """Chaos isolation for DURABLE STATE: a failing/interrupted journal
    drill must not leak an open write-ahead segment handle or an
    ephemeral journal temp dir into later tests — close every journal
    the module still tracks and remove the scratch dirs it minted.
    Lazy: touches nothing unless the module was actually imported."""
    import sys as _sys

    yield
    mod = _sys.modules.get("deeplearning4j_tpu.serving.journal")
    if mod is not None:
        mod.reap_stray_journals()


@pytest.fixture(autouse=True)
def _reap_flight_dumps():
    """Chaos isolation for POSTMORTEMS: a quarantine/restart drill (or
    an interrupted one) leaves flight-recorder dump files behind —
    remove every dump written on this test's watch so no postmortem
    litter leaks into later runs. Lazy, like the journal reaper."""
    import sys as _sys

    yield
    mod = _sys.modules.get("deeplearning4j_tpu.serving.flight")
    if mod is not None:
        mod.reap_stray_flight_dumps()


@pytest.fixture(autouse=True)
def _clear_faults():
    """Chaos isolation: no armed fault may leak into the next test."""
    from deeplearning4j_tpu.resilience.faults import injector

    injector().clear()
    yield
    injector().clear()


@pytest.fixture(autouse=True)
def _lock_order_check(request):
    """With the sanitizer armed, a test that introduces a lock-order
    cycle (potential deadlock) FAILS — even if the interleaving never
    actually wedged this run."""
    if not os.environ.get("DL4J_TPU_SANITIZE"):
        yield
        return
    from deeplearning4j_tpu.analysis import sanitizers

    san = sanitizers.active_sanitizer()
    if san is None or "test_static_analysis" in request.node.nodeid:
        # the sanitizer's own drills construct cycles on purpose
        yield
        return
    before = {tuple(c) for c in san.cycles()}
    yield
    new = [c for c in san.cycles() if tuple(c) not in before]
    if new:
        pytest.fail(
            "lock-order sanitizer: new acquisition cycle(s) "
            f"(potential deadlock): {new}")


@pytest.fixture(autouse=True)
def _restore_signal_handlers():
    """Chaos isolation for signals: preemption/watchdog tests install
    SIGTERM/SIGINT/SIGUSR1/SIGUSR2 handlers (PreemptionHandler,
    StepWatchdog, flight-recorder install_signal_dump); whatever a
    test leaves behind is restored so no handler leaks into the next
    test. (SIGALRM is owned by _hang_guard above.)"""
    import signal
    import threading

    if threading.current_thread() is not threading.main_thread():
        yield
        return
    names = [n for n in ("SIGTERM", "SIGINT", "SIGUSR1", "SIGUSR2")
             if hasattr(signal, n)]
    saved = {n: signal.getsignal(getattr(signal, n)) for n in names}
    yield
    for n, handler in saved.items():
        try:
            signal.signal(getattr(signal, n), handler)
        except (ValueError, OSError, TypeError):
            pass
