"""Harness-owned input pipeline tests (PR 12 tentpole): data_wait +
h2d overlap device_compute in every fit loop.

Parity pins: byte-identical final params AND updater state with
pipeline ON vs OFF for all three entry points (TrainingMaster,
ParallelWrapper, EarlyStoppingTrainer), including the k-group
(steps_per_dispatch) and masked-window paths. Chaos: the `data.next`
skip/retry/rollback drills re-prove exact parity against un-faulted
oracles through the PREFETCHED path (the producer side owns the fault
point, so a poisoned batch condemns the right step). Satellites:
DevicePrefetchIterator close() propagation (the wrapped producer is
joined on harness teardown), donation safety (a staged array consumed
by a donating call is never re-yielded), masked run_group parity, the
StepPhaseProfiler data_wait collapse, the `pipeline` facts block +
`dl4j_pipeline_*` metrics (dl4j_pipeline_batches_total,
dl4j_pipeline_wait_seconds, dl4j_pipeline_reseeks_total,
dl4j_pipeline_depth)."""

import time

import numpy as np
import pytest

from deeplearning4j_tpu.engine import StepPrefetcher, StepProgram
from deeplearning4j_tpu.parallel.training_master import TrainingMaster
from deeplearning4j_tpu.resilience import (
    FaultInjectedError,
    NonFiniteGuard,
    Retry,
    injector,
)

pytestmark = pytest.mark.engine

N_IN, N_OUT, ROWS = 4, 3, 16


def _net(seed=7, lr=1e-2):
    from deeplearning4j_tpu import (
        MultiLayerNetwork,
        NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.conf import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer

    conf = (NeuralNetConfiguration.Builder().seed(seed).updater("adam")
            .learning_rate(lr).activation("tanh").weight_init("xavier")
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


def _masked_batch(step):
    x, y = _batch(step)
    rng = np.random.default_rng(900 + step)
    lm = (rng.random(ROWS) > 0.25).astype(np.float32)
    return x, y, None, lm


def _leaves(tree):
    import jax

    return [np.asarray(TrainingMaster._host_leaf(l))
            for l in jax.tree_util.tree_leaves(tree)]


def _assert_trees_equal(tree_a, tree_b):
    la, lb = _leaves(tree_a), _leaves(tree_b)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(a, b)


def _assert_nets_equal(a, b):
    _assert_trees_equal(a.params, b.params)
    _assert_trees_equal(a.updater_states, b.updater_states)


# ========================== parity: pipeline on vs off, three entries
def test_training_master_pipeline_parity():
    on, off = _net(), _net()
    TrainingMaster(on, pipeline=True).fit(lambda s: _batch(s), 6)
    TrainingMaster(off, pipeline=False).fit(lambda s: _batch(s), 6)
    _assert_nets_equal(on, off)


def test_training_master_grouped_pipeline_parity():
    """steps_per_dispatch=4 with the pipeline's DEVICE-side k-window
    stack ends byte-identical to the host-stacked synchronous path."""
    on, off = _net(), _net()
    TrainingMaster(on, steps_per_dispatch=4, pipeline=True).fit(
        lambda s: _batch(s), 8)
    TrainingMaster(off, steps_per_dispatch=4, pipeline=False).fit(
        lambda s: _batch(s), 8)
    _assert_nets_equal(on, off)


def test_training_master_local_sgd_pipeline_parity():
    """The local-SGD rendezvous path (averaging_frequency=k) through
    the prefetched producer matches the synchronous fetch exactly."""
    import jax

    if not hasattr(jax, "shard_map"):
        pytest.skip("jax.shard_map unavailable in this environment")
    on, off = _net(), _net()
    TrainingMaster(on, averaging_frequency=2, pipeline=True).fit(
        lambda s: _batch(s), 6)
    TrainingMaster(off, averaging_frequency=2, pipeline=False).fit(
        lambda s: _batch(s), 6)
    _assert_nets_equal(on, off)


def test_parallel_wrapper_pipeline_parity():
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    data = [_batch(s) for s in range(6)]
    on, off = _net(), _net()
    ParallelWrapper(on, mesh=make_mesh(dp=1), pipeline=True).fit(data)
    ParallelWrapper(off, mesh=make_mesh(dp=1), pipeline=False).fit(data)
    _assert_nets_equal(on, off)


def test_parallel_wrapper_masked_pipeline_parity():
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    data = [_masked_batch(s) for s in range(5)]
    on, off = _net(), _net()
    ParallelWrapper(on, mesh=make_mesh(dp=1), pipeline=True).fit(data)
    ParallelWrapper(off, mesh=make_mesh(dp=1), pipeline=False).fit(data)
    _assert_nets_equal(on, off)


def test_early_stopping_pipeline_parity():
    from deeplearning4j_tpu.earlystopping import (
        EarlyStoppingConfiguration,
        EarlyStoppingTrainer,
        InMemoryModelSaver,
        MaxEpochsTerminationCondition,
    )

    def cfg():
        return EarlyStoppingConfiguration(
            epoch_termination_conditions=[
                MaxEpochsTerminationCondition(1)],
            model_saver=InMemoryModelSaver(),
            evaluate_every_n_epochs=1)

    data = [_batch(s) for s in range(6)]
    on, off = _net(), _net()
    EarlyStoppingTrainer(cfg(), on, data, pipeline=True).fit()
    EarlyStoppingTrainer(cfg(), off, data, pipeline=False).fit()
    _assert_nets_equal(on, off)


# =========================== masked run_group (PR 9 carried-forward)
def test_masked_run_group_matches_sequential_steps():
    """run_group(k) with label masks stacked alongside features must
    evolve params / updater state / rng exactly like k sequential
    run() calls on the same masked batches — the pin that lets masked
    nets leave the k=1 path."""
    import jax.numpy as jnp

    seq = _net()
    prog_seq = StepProgram(seq)
    for s in range(4):
        x, y, _, lm = _masked_batch(s)
        prog_seq.run(jnp.asarray(x), jnp.asarray(y),
                     lm=jnp.asarray(lm))

    grp = _net()
    prog_grp = StepProgram(grp)
    xs = jnp.asarray(np.stack([_masked_batch(s)[0] for s in range(4)]))
    ys = jnp.asarray(np.stack([_masked_batch(s)[1] for s in range(4)]))
    lms = jnp.asarray(np.stack([_masked_batch(s)[3] for s in range(4)]))
    prog_grp.run_group(xs, ys, lms=lms)

    assert grp.iteration == seq.iteration == 4
    _assert_nets_equal(grp, seq)
    np.testing.assert_array_equal(np.asarray(grp._rng),
                                  np.asarray(seq._rng))
    losses = np.asarray(prog_grp.last_step_losses)
    assert losses.shape == (4,) and np.isfinite(losses).all()


def test_wrapper_steps_per_dispatch_masked_matches_k1():
    """ParallelWrapper(steps_per_dispatch=k) on MASKED batches is a
    pure perf knob: byte-identical to the per-step wrapper fit."""
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    data = [_masked_batch(s) for s in range(6)]
    k1, k3 = _net(), _net()
    ParallelWrapper(k1, mesh=make_mesh(dp=1), pipeline=False).fit(data)
    ParallelWrapper(k3, mesh=make_mesh(dp=1), pipeline=False,
                    steps_per_dispatch=3).fit(data)
    _assert_nets_equal(k3, k1)


def test_wrapper_steps_per_dispatch_excludes_local_sgd():
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    with pytest.raises(ValueError, match="mutually exclusive"):
        ParallelWrapper(_net(), steps_per_dispatch=4,
                        averaging_frequency=2)


# ================================= chaos drills via the prefetched path
@pytest.mark.chaos
def test_pipeline_data_retry_parity():
    """A transient data.next fault is retried on the PRODUCER thread;
    the run matches an un-faulted oracle and loses no step."""
    net = _net()
    retry = Retry(max_attempts=3, initial_backoff_s=0.01,
                  retryable=lambda e: isinstance(e, FaultInjectedError))
    tm = TrainingMaster(net, data_retry=retry, pipeline=True)
    injector().inject("data.next", at_hit=2)   # step 1, first attempt
    tm.fit(lambda s: _batch(s), 4)
    assert net.iteration == 4
    assert injector().hits("data.next") == 5   # 4 fetches + 1 retry
    oracle = _net()
    TrainingMaster(oracle, pipeline=False).fit(lambda s: _batch(s), 4)
    _assert_nets_equal(net, oracle)


@pytest.mark.chaos
def test_pipeline_skip_bad_batches_parity():
    """A persistently failing batch is consumed by skip_bad_batches on
    the producer side — the right step is skipped and the run equals
    one that never saw it."""
    net = _net()
    retry = Retry(max_attempts=2, initial_backoff_s=0.01,
                  retryable=lambda e: isinstance(e, FaultInjectedError))
    tm = TrainingMaster(net, data_retry=retry, skip_bad_batches=True,
                        pipeline=True)
    injector().inject("data.next", at_hit=2, times=3)  # kills step 1
    tm.fit(lambda s: _batch(s), 4)
    assert tm._resil_counters["data_skipped_steps"] == 1
    assert net.iteration == 3
    order = [0, 2, 3]
    oracle = _net()
    TrainingMaster(oracle, pipeline=False).fit(
        lambda s: _batch(order[s]), len(order))
    _assert_nets_equal(net, oracle)


@pytest.mark.chaos
def test_pipeline_rollback_condemns_right_step(tmp_path):
    """A poisoned batch through the prefetched path condemns the RIGHT
    step: rollback restores the checkpoint, the producer reseeks (a
    dl4j_pipeline_reseeks_total event) and never refetches the
    condemned step, and the replay matches an oracle that never saw
    the poison."""
    from deeplearning4j_tpu.observability.metrics import get_registry

    base = get_registry().counter_value("dl4j_pipeline_reseeks_total")
    net = _net()
    tm = TrainingMaster(
        net, checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=4,
        guard=NonFiniteGuard(policy="rollback", check_every=1),
        pipeline=True)
    # poison step 6: the rollback target (checkpoint step 4) is BEHIND
    # the producer, so the replay must reseek, not just roll forward
    injector().inject("train.grad_nonfinite", at_hit=7)
    tm.fit(lambda s: _batch(s), 8)
    assert tm.guard.counters["rollbacks"] == 1
    poisoned = sorted(tm._poisoned_steps)
    assert poisoned == [6]
    assert get_registry().counter_value(
        "dl4j_pipeline_reseeks_total") >= (base or 0) + 1
    order = [s for s in range(8) if s not in tm._poisoned_steps]
    oracle = _net()
    TrainingMaster(oracle, pipeline=False).fit(
        lambda s, order=order: _batch(order[s]), len(order))
    _assert_nets_equal(net, oracle)


@pytest.mark.chaos
def test_pipeline_supervised_chaos_completes_and_matches(tmp_path):
    """The all-fault-points drill through the PREFETCHED path: crash +
    NaN batch + preemption under a Supervisor. Unlike the synchronous
    drill (test_selfhealing), a prefetching producer legitimately
    fetches ahead of a crash, so the pin here is outcome-shaped: the
    job completes, exactly the condemned steps are excluded, and final
    state matches an oracle over the surviving stream."""
    from deeplearning4j_tpu.resilience import Supervisor

    net = _net()
    g = NonFiniteGuard(policy="rollback", check_every=1)
    sup = Supervisor(max_restarts=4, initial_backoff_s=0.05)
    tm = TrainingMaster(net, checkpoint_dir=str(tmp_path),
                        checkpoint_every=1, guard=g, preemption=True,
                        supervisor=sup, pipeline=True)
    injector().load_spec_string(
        "train.step:raise@2,"            # worker-loss crash
        "train.grad_nonfinite:raise@5,"  # NaN batch (rolled back)
        "train.preempt:raise@7")         # simulated TPU preemption
    sup.run(tm.fit, lambda s: _batch(s), 8)
    assert len(sup.restart_ledger) >= 2
    assert g.counters["rollbacks"] == 1
    assert len(tm._poisoned_steps) == 1
    order = [s for s in range(8) if s not in tm._poisoned_steps]
    oracle = _net()
    TrainingMaster(oracle, pipeline=False).fit(
        lambda s, order=order: _batch(order[s]), len(order))
    _assert_nets_equal(net, oracle)


# =============================== phase attribution under the pipeline
STALL_S = 0.015     # what the slow iterator sleeps for every batch


def _heavy_net(seed=7):
    """A step heavy enough (25 ms and more on the CPUs this has run
    on) that a 15 ms ETL stall fits entirely under it — overlap can
    only hide ETL up to the step's own time."""
    from deeplearning4j_tpu import (
        MultiLayerNetwork,
        NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.conf import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer

    conf = (NeuralNetConfiguration.Builder().seed(seed).updater("adam")
            .learning_rate(1e-3).activation("tanh")
            .weight_init("xavier").list()
            .layer(DenseLayer(n_out=512))
            .layer(DenseLayer(n_out=512))
            .layer(OutputLayer(n_out=10, loss="mcxent"))
            .set_input_type(InputType.feed_forward(256)).build())
    return MultiLayerNetwork(conf).init()


def _heavy_batch(step):
    rng = np.random.default_rng(step)
    x = rng.normal(size=(1024, 256)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 1024)]
    return x, y


def test_phase_attribution_data_wait_collapses():
    """With a deliberately slow iterator whose ETL stall fits under
    the step's compute, pipeline ON collapses the data_wait phase vs
    OFF — the StepPhaseProfiler proof the tentpole claims (on CPU the
    honest claim is ETL/copy overlap; the flagship re-measure needs
    hardware). Read in seconds of data_wait a step against the stall,
    not in shares of the step's wall time: a loaded machine stretches
    the step (five other xdist workers made it 165 ms for 35 and the
    stall's share 10% for 50%), and cannot shorten a sleep nor take
    away what the producer thread slept through meanwhile."""
    batches = [_heavy_batch(s) for s in range(10)]

    def slow_batch(s):
        time.sleep(STALL_S)
        return batches[s]

    def run(pipeline):
        from deeplearning4j_tpu.observability.perf import (
            StepPhaseProfiler,
        )

        tm = TrainingMaster(_heavy_net(), pipeline=pipeline)
        tm.fit(slow_batch, 2)   # compile warm-up outside the profile
        # a sync a step keeps the consumer from running ahead of the
        # device, so every step takes its batch one step's time after
        # the last
        tm.phase_profiler = StepPhaseProfiler(sync_every=1)
        tm.fit(slow_batch, 10, start_step=2)    # steps 2..9
        rep = tm.training_stats()["phases"]
        return rep["phases"]["data_wait"]["seconds"] / rep["steps"]

    wait_off = run(False)
    wait_on = run(True)
    assert wait_off >= STALL_S     # the ETL stall is paid in full, sync
    # the pipeline hides more than half of it (all but the first
    # batch's, which nothing runs beside)
    assert wait_off - wait_on > STALL_S / 2


def test_pipeline_metrics_and_stats_block():
    """dl4j_pipeline_* emission: batches through, consumer wait, and
    the depth gauge land in the registry; training_stats() carries the
    `pipeline` facts block with the live-world derivation."""
    from deeplearning4j_tpu.observability.metrics import get_registry

    r = get_registry()
    base = r.counter_value("dl4j_pipeline_batches_total") or 0
    tm = TrainingMaster(_net(), pipeline=True, pipeline_depth=3)
    tm.fit(lambda s: _batch(s), 4)
    assert r.counter_value("dl4j_pipeline_batches_total") == base + 4
    snap = r.snapshot()
    assert snap["histograms"]["dl4j_pipeline_wait_seconds"]["count"] \
        >= 4
    assert snap["gauges"]["dl4j_pipeline_depth"][""] == 3.0
    pipe = tm.training_stats()["pipeline"]
    assert pipe["enabled"] and pipe["kind"] == "step"
    assert pipe["depth"] == 3 and pipe["batches"] == 4
    assert pipe["sharding"] == "dp"
    assert pipe["world"]["processes"] == 1
    off = TrainingMaster(_net(), pipeline=False)
    off.fit(lambda s: _batch(s), 2)
    assert off.training_stats()["pipeline"] is None


# ==================================== close / teardown / donation safety
def test_device_prefetch_close_propagates_to_async_base():
    """Satellite: DevicePrefetchIterator.close() reaches the wrapped
    AsyncDataSetIterator's producer thread (previously hidden from
    StepHarness.attach_data's hasattr check)."""
    from deeplearning4j_tpu.datasets.iterators import (
        AsyncDataSetIterator,
        DevicePrefetchIterator,
    )

    base = AsyncDataSetIterator([_batch(s) for s in range(4)],
                                queue_size=2)
    it = DevicePrefetchIterator(base, buffer_size=2)
    first = next(iter(it))
    assert base._thread is not None   # producer started (may be done)
    it.close()
    assert base._thread is None   # joined through the propagation
    assert first is not None
    with DevicePrefetchIterator(
            AsyncDataSetIterator([_batch(0)])) as cm:
        assert len(list(cm)) == 1
    assert cm.base._thread is None


def test_harness_session_joins_wrapped_producer():
    """Satellite: a harness-owned pipeline wrapping an async producer
    is JOINED on session teardown even when the fit body raises."""
    import threading

    from deeplearning4j_tpu.engine import StepHarness

    before = {t.name for t in threading.enumerate()}
    harness = StepHarness(_net())
    pipe = harness.build_iterator_pipeline(
        [_batch(s) for s in range(4)], depth=2)
    with pytest.raises(RuntimeError):
        with harness.session():
            next(iter(pipe))      # producer thread is now live
            raise RuntimeError("fit crashed")
    assert pipe._async._thread is None
    leaked = [t for t in threading.enumerate()
              if t.name.startswith("AsyncDataSetIterator")
              and t.name not in before and t.is_alive()]
    assert not leaked, "prefetch thread leaked past session teardown"


def test_staged_batches_survive_donation():
    """Donation safety: every yield is freshly staged even when the
    base hands out the SAME host batch repeatedly — donating a
    consumed staged array never invalidates a later yield."""
    import jax

    from deeplearning4j_tpu.datasets.iterators import (
        BenchmarkDataSetIterator,
        DevicePrefetchIterator,
    )

    base = BenchmarkDataSetIterator((8, N_IN), N_OUT, num_batches=4)
    it = iter(DevicePrefetchIterator(base, buffer_size=2))
    eat = jax.jit(lambda x: x * 2.0, donate_argnums=0)
    seen = []
    first = None
    for x, y, _, _ in it:
        # a fresh device buffer every yield, never a re-yield
        assert all(b is not x for b in seen), "re-yielded staged buffer"
        val = np.asarray(x).copy()   # read BEFORE donating
        if first is None:
            first = val
        np.testing.assert_array_equal(val, first)
        seen.append(x)
        eat(x)   # donates (invalidates) the consumed staged buffer
    assert len(seen) == 4


def test_step_prefetcher_seek_and_skip_predicate():
    """StepPrefetcher contract: stale entries are discarded, a
    backward get() reseeks (discarding staged lookahead — donation
    safety), and the live skip predicate suppresses refetching
    condemned steps."""
    calls = []
    condemned = set()

    def fetch(s):
        calls.append(s)
        return ("batch", s)

    with StepPrefetcher(fetch, start=0, stop=8, depth=2,
                        skip=lambda s: s in condemned) as pf:
        assert pf.get(0) == ("batch", 0)
        assert pf.get(1) == ("batch", 1)
        condemned.add(3)
        assert pf.get(2) == ("batch", 2)
        # rollback: rewind to 1 — triggers a reseek
        assert pf.get(1) == ("batch", 1)
        assert pf.counters["reseeks"] >= 1
        assert pf.get(2) == ("batch", 2)
        assert pf.get(4) == ("batch", 4)   # 3 skipped by predicate
    assert 3 not in calls[calls.index(4):]  # condemned never refetched


def test_step_prefetcher_carries_fetch_error_to_the_right_step():
    def fetch(s):
        if s == 2:
            raise ValueError("bad shard")
        return s

    with StepPrefetcher(fetch, start=0, stop=6, depth=2) as pf:
        assert pf.get(0) == 0
        assert pf.get(1) == 1
        with pytest.raises(ValueError, match="bad shard"):
            pf.get(2)
        assert pf.get(3) == 3   # producer restarts past the error
