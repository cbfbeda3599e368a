"""Training-engine tests (PR 9 tentpole): the shared StepProgram /
StepHarness contract.

Parity pins: byte-identical final params AND updater state for all
three fit entry points (TrainingMaster, ParallelWrapper,
EarlyStoppingTrainer) running on the shared harness vs a pre-refactor
oracle (a hand-rolled loop over the net's own `_train_step` — the
exact step math the entry points ran before the extraction). Drills:
rollback-after-NaN through the harness's verdict dispatch, the k-step
`lax.scan` group condemning ONE poisoned inner step, k-group state
evolution matching k sequential steps, harness teardown closing an
AsyncDataSetIterator, and dispatch-count proof that k-grouping
amortizes dispatches."""

import numpy as np
import pytest

from deeplearning4j_tpu.engine import StepHarness, StepProgram
from deeplearning4j_tpu.parallel.training_master import TrainingMaster
from deeplearning4j_tpu.resilience import (
    NonFiniteGuard,
    NonFiniteLossError,
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


def _leaves(tree):
    import jax

    return [np.asarray(TrainingMaster._host_leaf(l))
            for l in jax.tree_util.tree_leaves(tree)]


def _assert_trees_equal(tree_a, tree_b):
    la, lb = _leaves(tree_a), _leaves(tree_b)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(a, b)


def _oracle(n_steps, seed=7):
    """Pre-refactor oracle: the net's own cached donated train step,
    driven by a bare loop — exactly what every entry point executed
    per step before the engine extraction."""
    net = _net(seed)
    for s in range(n_steps):
        x, y = _batch(s)
        net._train_step(x, y)
    return net


def _tm_oracle(n_steps, seed=7):
    """TrainingMaster-shaped oracle: the pre-refactor per-step path
    verbatim — net staged onto the mesh as replicated global arrays,
    batches staged with _global_batch, then the net's own train step
    (what _fit_one_step dispatched before the engine extraction).
    Separate from _oracle because device placement participates in
    compilation: the staged program must be compared against a staged
    oracle for a byte-identity claim."""
    net = _net(seed)
    tm = TrainingMaster(net)    # staging helpers only; no harness loop
    tm._stage_net()
    with tm.mesh:
        for s in range(n_steps):
            x, y = tm._global_batch(*_batch(s))
            net._train_step(x, y)
    return net


# ===================================== parity: the three entry points
def test_training_master_matches_oracle():
    net = _net()
    TrainingMaster(net).fit(lambda s: _batch(s), 6)
    oracle = _tm_oracle(6)
    _assert_trees_equal(net.params, oracle.params)
    _assert_trees_equal(net.updater_states, oracle.updater_states)


def test_parallel_wrapper_matches_oracle():
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    net = _net()
    mesh = make_mesh(dp=1)
    pw = ParallelWrapper(net, mesh=mesh)
    pw.fit([_batch(s) for s in range(6)])
    oracle = _oracle(6)
    _assert_trees_equal(net.params, oracle.params)
    _assert_trees_equal(net.updater_states, oracle.updater_states)


def test_early_stopping_trainer_matches_oracle():
    from deeplearning4j_tpu.earlystopping import (
        EarlyStoppingConfiguration,
        EarlyStoppingTrainer,
        InMemoryModelSaver,
        MaxEpochsTerminationCondition,
    )

    net = _net()
    cfg = EarlyStoppingConfiguration(
        epoch_termination_conditions=[
            MaxEpochsTerminationCondition(1)],
        model_saver=InMemoryModelSaver(),
        evaluate_every_n_epochs=1)
    trainer = EarlyStoppingTrainer(
        cfg, net, [_batch(s) for s in range(6)])
    trainer.fit()
    oracle = _oracle(6)
    _assert_trees_equal(net.params, oracle.params)
    _assert_trees_equal(net.updater_states, oracle.updater_states)


def test_all_entry_points_share_the_harness():
    """The refactor's structural pin: every entry point owns an
    engine.StepHarness whose program wraps the SAME net."""
    from deeplearning4j_tpu.earlystopping import (
        EarlyStoppingConfiguration,
        EarlyStoppingTrainer,
        InMemoryModelSaver,
        MaxEpochsTerminationCondition,
    )
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    net = _net()
    tm = TrainingMaster(net)
    pw = ParallelWrapper(net, mesh=make_mesh(dp=1))
    es = EarlyStoppingTrainer(
        EarlyStoppingConfiguration(
            epoch_termination_conditions=[
                MaxEpochsTerminationCondition(1)],
            model_saver=InMemoryModelSaver(),
            evaluate_every_n_epochs=1),
        net, [])
    for owner in (tm, pw, es):
        harness = owner._harness
        assert isinstance(harness, StepHarness)
        assert isinstance(harness.program, StepProgram)
        assert harness.program.net is net


# ============================================= k-step lax.scan groups
def test_k_group_matches_sequential_steps():
    """run_group(k) must evolve params / updater state / rng exactly
    like k sequential run() calls (same split chain, same per-step lr
    schedule) — the contract that makes k a pure dispatch knob."""
    import jax.numpy as jnp

    net_seq = _net()
    prog_seq = StepProgram(net_seq)
    for s in range(6):
        x, y = _batch(s)
        prog_seq.run(jnp.asarray(x), jnp.asarray(y))

    net_grp = _net()
    prog_grp = StepProgram(net_grp)
    xs = np.stack([_batch(s)[0] for s in range(6)])
    ys = np.stack([_batch(s)[1] for s in range(6)])
    prog_grp.run_group(jnp.asarray(xs), jnp.asarray(ys))

    assert net_grp.iteration == net_seq.iteration == 6
    _assert_trees_equal(net_grp.params, net_seq.params)
    _assert_trees_equal(net_grp.updater_states, net_seq.updater_states)
    np.testing.assert_array_equal(np.asarray(net_grp._rng),
                                  np.asarray(net_seq._rng))
    # per-inner-step losses surface for the guard
    losses = np.asarray(prog_grp.last_step_losses)
    assert losses.shape == (6,)
    assert np.isfinite(losses).all()


def test_k_group_amortizes_dispatches():
    """One compiled-program call per k steps: the trace counter proves
    the group compiles ONCE and the per-call shim sees iters/k calls."""
    import jax.numpy as jnp

    net = _net()
    prog = StepProgram(net)
    xs = jnp.asarray(np.stack([_batch(s)[0] for s in range(4)]))
    ys = jnp.asarray(np.stack([_batch(s)[1] for s in range(4)]))
    for _ in range(5):
        prog.run_group(xs, ys)
    counts = net._jit_cache.trace_counts()
    group_keys = [k for k in counts if "engine_group" in k]
    assert group_keys, counts
    # one trace (= one compile) total despite 5 group dispatches
    assert sum(counts[k] for k in group_keys) == 1
    assert net.iteration == 20


def test_training_master_steps_per_dispatch_matches_k1():
    """steps_per_dispatch is a pure perf knob: k=4 grouped fit ends
    byte-identical to the default per-step fit."""
    net_k1 = _net()
    TrainingMaster(net_k1).fit(lambda s: _batch(s), 8)
    net_k4 = _net()
    TrainingMaster(net_k4, steps_per_dispatch=4).fit(
        lambda s: _batch(s), 8)
    _assert_trees_equal(net_k4.params, net_k1.params)
    _assert_trees_equal(net_k4.updater_states, net_k1.updater_states)


def test_steps_per_dispatch_excludes_local_sgd():
    with pytest.raises(ValueError, match="mutually exclusive"):
        TrainingMaster(_net(), steps_per_dispatch=4,
                       averaging_frequency=2)


# ====================================================== guard drills
@pytest.mark.chaos
def test_rollback_after_nan_through_harness(tmp_path):
    """Rollback-after-NaN drill on the shared harness: a poisoned step
    under policy='rollback' restores the newest checkpoint, marks the
    step poisoned, and the replay matches an oracle that never saw
    the poison."""
    ckpt = str(tmp_path / "ck")
    net = _net()
    tm = TrainingMaster(
        net, checkpoint_dir=ckpt, checkpoint_every=2,
        guard=NonFiniteGuard(policy="rollback", check_every=1))
    injector().inject("train.grad_nonfinite", at_hit=5)  # poison step 4
    tm.fit(lambda s: _batch(s), 8)
    assert tm.guard.counters["rollbacks"] == 1
    poisoned = sorted(tm._poisoned_steps)
    assert len(poisoned) == 1
    # oracle: same data stream minus the poisoned step — but the
    # replayed fit re-trains the un-poisoned steps after the rollback
    # point, so final state equals a run that simply skipped it
    order = [s for s in range(8) if s not in poisoned]
    oracle = _net()
    TrainingMaster(oracle).fit(
        lambda s, order=order: _batch(order[s]), len(order))
    _assert_trees_equal(net.params, oracle.params)
    _assert_trees_equal(net.updater_states, oracle.updater_states)


@pytest.mark.chaos
def test_k_group_condemns_single_poisoned_inner_step(tmp_path):
    """k-step-group poisoned-inner-step drill: one NaN batch inside a
    k=4 window condemns THAT inner step only — the window replays
    without it and the final state matches an oracle that never saw
    the poison (the granularity the per-inner-step losses exist
    for)."""
    ckpt = str(tmp_path / "ck")
    net = _net()
    tm = TrainingMaster(
        net, checkpoint_dir=ckpt, checkpoint_every=4,
        steps_per_dispatch=4,
        guard=NonFiniteGuard(policy="rollback", check_every=1))
    injector().inject("train.grad_nonfinite", at_hit=3)  # poison step 2
    tm.fit(lambda s: _batch(s), 8)
    poisoned = sorted(tm._poisoned_steps)
    assert len(poisoned) == 1, poisoned
    assert tm.guard.counters["nonfinite"] >= 1
    order = [s for s in range(8) if s not in poisoned]
    oracle = _net()
    TrainingMaster(oracle).fit(
        lambda s, order=order: _batch(order[s]), len(order))
    _assert_trees_equal(net.params, oracle.params)
    _assert_trees_equal(net.updater_states, oracle.updater_states)


@pytest.mark.chaos
def test_k_group_skip_step_policy(tmp_path):
    """skip_step under k-grouping: the pre-group snapshot restores and
    the window replays minus the poisoned inner step — no checkpoint
    directory required."""
    net = _net()
    tm = TrainingMaster(
        net, steps_per_dispatch=4,
        guard=NonFiniteGuard(policy="skip_step", check_every=1))
    injector().inject("train.grad_nonfinite", at_hit=4)  # poison step 3
    tm.fit(lambda s: _batch(s), 8)
    poisoned = sorted(tm._poisoned_steps)
    assert len(poisoned) == 1
    order = [s for s in range(8) if s not in poisoned]
    oracle = _net()
    TrainingMaster(oracle).fit(
        lambda s, order=order: _batch(order[s]), len(order))
    _assert_trees_equal(net.params, oracle.params)
    _assert_trees_equal(net.updater_states, oracle.updater_states)


def test_dispatch_verdict_abort_raises():
    net = _net()
    harness = StepHarness(net, guard=NonFiniteGuard(policy="abort"))
    with pytest.raises(NonFiniteLossError, match="policy=abort"):
        harness.dispatch_verdict("nonfinite", context="at step 0")


def test_dispatch_verdict_bounds_rollbacks():
    net = _net()
    guard = NonFiniteGuard(policy="rollback", max_rollbacks=1)
    harness = StepHarness(net, guard=guard)
    assert harness.dispatch_verdict(
        "nonfinite", restore_rollback=lambda: None) == "rollback"
    with pytest.raises(NonFiniteLossError, match="max_rollbacks"):
        harness.dispatch_verdict("nonfinite",
                                 restore_rollback=lambda: None)


# ============================================== harness session drills
def test_session_closes_attached_async_iterator():
    """Harness teardown joins the AsyncDataSetIterator prefetch thread
    (the analyzer-baseline debt this PR burns down) even when the fit
    body raises."""
    import threading

    from deeplearning4j_tpu.datasets.iterators import (
        AsyncDataSetIterator,
    )

    before = {t.name for t in threading.enumerate()}
    it = AsyncDataSetIterator([_batch(s) for s in range(4)],
                              queue_size=2)
    harness = StepHarness(_net())
    harness.attach_data(it)
    with pytest.raises(RuntimeError):
        with harness.session():
            next(iter(it))        # producer thread is now live
            raise RuntimeError("fit crashed")
    after = [t for t in threading.enumerate()
             if t.name.startswith("AsyncDataSetIterator")
             and t.name not in before and t.is_alive()]
    assert not after, "prefetch thread leaked past session teardown"
    assert it._thread is None


def test_async_iterator_close_is_reusable():
    from deeplearning4j_tpu.datasets.iterators import (
        AsyncDataSetIterator,
    )

    data = [_batch(s) for s in range(3)]
    it = AsyncDataSetIterator(data, queue_size=2)
    first = next(iter(it))
    it.close()
    with pytest.raises(StopIteration):
        next(it)                  # closed: exhausted until restarted
    again = list(it)              # __iter__ restarts a fresh pass
    assert len(again) == 3
    np.testing.assert_array_equal(np.asarray(first[0]),
                                  np.asarray(again[0][0]))
    it.close()                    # idempotent


def test_async_iterator_context_manager():
    from deeplearning4j_tpu.datasets.iterators import (
        AsyncDataSetIterator,
    )

    with AsyncDataSetIterator([_batch(s) for s in range(3)]) as it:
        assert len(list(it)) == 3
    assert it._thread is None


def test_parallel_wrapper_session_closes_iterator():
    from deeplearning4j_tpu.datasets.iterators import (
        AsyncDataSetIterator,
    )
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    net = _net()
    it = AsyncDataSetIterator([_batch(s) for s in range(4)])
    ParallelWrapper(net, mesh=make_mesh(dp=1)).fit(it)
    assert it._thread is None     # joined by the harness teardown


def test_require_sgd_rejects_solvers():
    from deeplearning4j_tpu import (
        MultiLayerNetwork,
        NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.conf import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer

    conf = (NeuralNetConfiguration.Builder().seed(1).updater("sgd")
            .learning_rate(0.1).optimization_algo("lbfgs").list()
            .layer(DenseLayer(n_out=8))
            .layer(OutputLayer(n_out=2, loss="mcxent"))
            .set_input_type(InputType.feed_forward(4)).build())
    net = MultiLayerNetwork(conf).init()
    with pytest.raises(NotImplementedError, match="line-search"):
        StepProgram(net).require_sgd("TrainingMaster")
