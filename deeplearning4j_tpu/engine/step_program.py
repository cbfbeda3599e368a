"""StepProgram: the ONE compiled train step every fit loop runs on.

The compiled half of the engine (see package docstring). A StepProgram
wraps a container net (MultiLayerNetwork or ComputationGraph) and owns:

  - the shared loss/update closures (`make_loss_and_apply`) that the
    single step, the k-step group, the local-SGD rendezvous trainer,
    and the stale-gradient trainer all compile from — one source of
    step math;
  - `run(x, y)`: one training step in the canonical (x, y, fm, lm)
    batch shape, with the graph-input and truncated-BPTT adaptation
    that TrainingMaster and ParallelWrapper previously each hand-rolled
    (the compiled program is the net's own cached, donated train step —
    byte-identical state evolution by construction);
  - `run_group(xs, ys)`: the `lax.scan` k-step group — ONE dispatch
    advances k steps on stacked [k, ...] data, splitting the rng chain
    exactly as k sequential steps would, donating params / updater
    state / BN states end-to-end, and returning the [k] per-inner-step
    losses (`last_step_losses`) so a NonFiniteGuard can condemn a
    single poisoned inner step instead of the whole window. This
    generalizes the local-SGD grouping (which adds a dp rendezvous on
    top);
  - forensics: the group program lands in the net's JitCache (key
    `("engine_group", ...)`, `record_trace` inside the traced body) so
    recompile forensics cover it.
"""

from __future__ import annotations

import numpy as np


def make_loss_and_apply(net, fused: bool = True):
    """(loss_for_grad, apply_updates) closures over a net — the shared
    step math. Every compiled step variant (StepProgram single/group,
    the ZeRO-1 mesh-sharded step, LocalStepTrainer's dp rendezvous,
    StaleGradientTrainer) builds from these two closures, so a change
    to the step lands once.

    `loss_for_grad(params, states, x, y, rng, fm, lm)` returns
    (loss, new_states) with the net's mixed-precision policy applied
    (bf16 compute params/inputs, f32 master params and loss).
    `apply_updates(params, upd_states, grads, lr, step)` runs the
    per-layer updater chain with per-layer lr factors and frozen flags
    baked in (callers must key compiled-program caches on the frozen
    signature). `fused=True` (default) runs the cross-layer fused
    flat-buffer chain; `fused=False` runs the per-layer unfused path —
    bitwise-identical math (pinned in test_mesh.py), required by the
    ZeRO-1 sharded update whose per-leaf shardings the fused concat
    would force XLA to all-gather."""
    import jax

    conf = net.conf
    cd = net.compute_dtype
    is_graph = hasattr(conf, "network_inputs")

    def loss_for_grad(params, states, x, y, rng, fm, lm):
        if cd is not None:
            from deeplearning4j_tpu.nn.dtype import cast_floating
            params = cast_floating(params, cd)
            x = cast_floating(x, cd)
        loss, (new_states, _) = net._loss_fn(
            params, states, x, y, rng, fm, lm, rnn_carries=None)
        if cd is not None:
            loss = loss.astype(net.dtype)
        return loss, new_states

    def _apply(items, lr, step):
        from deeplearning4j_tpu.nn.updater import fused_apply
        with jax.named_scope("updater"):
            if fused:
                return fused_apply(items, lr, step)
            return _unfused_apply(items, lr, step)

    if is_graph:
        layer_names = [n.name for n in net.topo if n.kind == "layer"]
        frozen = {n.name for n in net.topo
                  if n.kind == "layer" and n.obj.frozen}
        lr_factors = {
            n.name: ((n.obj.learning_rate / conf.learning_rate)
                     if getattr(n.obj, "learning_rate", None) is not None
                     and conf.learning_rate != 0 else 1.0)
            for n in net.topo if n.kind == "layer"}

        def apply_updates(params, upd_states, grads, lr, step):
            np_list, nu_list = _apply(
                [(net._updaters[name], lr_factors[name], name in frozen,
                  params[name], grads[name], upd_states[name])
                 for name in layer_names], lr, step)
            return (dict(zip(layer_names, np_list)),
                    dict(zip(layer_names, nu_list)))
    else:
        lr_factors = [
            (l.learning_rate / conf.learning_rate)
            if l.learning_rate is not None and conf.learning_rate != 0
            else 1.0 for l in conf.layers]

        def apply_updates(params, upd_states, grads, lr, step):
            return _apply(
                [(net._updaters[i], lr_factors[i], conf.layers[i].frozen,
                  params[i], grads[i], upd_states[i])
                 for i in range(len(params))], lr, step)

    return loss_for_grad, apply_updates


def _unfused_apply(items, lr, step):
    """Per-layer updater application — the pre-fusion formulation
    fused_apply documents as bitwise-identical. The ZeRO-1 step uses
    it so per-leaf GSPMD shardings survive the update (the fused
    flat-buffer concat would all-gather the sharded state)."""
    import jax

    new_p, new_s = [], []
    for upd, lf, frozen, p, g, s in items:
        if frozen or not jax.tree_util.tree_leaves(p):
            new_p.append(p)
            new_s.append(s)
            continue
        deltas, ns = upd.update(g, s, p, lr * lf, step)
        new_p.append(jax.tree_util.tree_map(
            lambda a, d: a + d, p, deltas))
        new_s.append(ns)
    return new_p, new_s


class StepProgram:
    """One net's compiled training step, in every grouping.

    `run` / `run_batch` execute exactly one optimizer step (the net's
    own cached donated program — the k=1 program); `run_group` executes
    a k-step `lax.scan` group in one dispatch. All three mutate the net
    the way a train step always has (params / updater state / BN states
    rebound, rng split, iteration advanced, `_score` set) so guards,
    snapshots, and checkpoints see an identical contract."""

    def __init__(self, net):
        self.net = net
        self.is_graph = hasattr(net.conf, "network_inputs")
        self.is_tbptt = getattr(net.conf, "backprop_type", None) \
            == "truncated_bptt"
        # the DECLARED compute-precision policy of every program this
        # StepProgram compiles ('bf16'/'f16' mixed precision, 'f32'
        # default) — an explicit registration fact the program lint
        # checks the lowered programs against, never a guess
        from deeplearning4j_tpu.nn.jit_cache import policy_name

        self.precision_policy = policy_name(
            getattr(net, "compute_dtype", None))
        # [k] dp-visible per-inner-step losses of the newest run_group
        # dispatch (device array; fetched by the guard only on checked
        # groups so the hot loop never syncs)
        self.last_step_losses = None
        # engine/mesh.py MeshManager when the ZeRO-1 sharded path is
        # attached: run/run_group/run_batch then route through the
        # mesh-sharded compiled step (engine/sharding.py) instead of
        # the net's replicated one
        self.mesh_manager = None

    # ------------------------------------------------------------ mesh
    def attach_mesh(self, manager) -> "StepProgram":
        """Route this program through the ZeRO-1 mesh-sharded step
        (engine/sharding.py) over `manager`'s mesh: optimizer state
        lives SHARDED between steps (1/n per replica), the update is
        reduce-scatter → shard-local → all-gather inside the one
        donated program, equal to the unsharded step within a few ulp
        (another reduction order; engine/sharding.py). Every
        harness entry point inherits the sharded compilation through
        run/run_group/run_batch unchanged."""
        if self.is_tbptt:
            raise NotImplementedError(
                "ZeRO-1 mesh sharding does not support truncated BPTT "
                "(per-chunk host carries); train unsharded")
        self.mesh_manager = manager
        return self

    def _zero1_key(self, kind: str, *extra):
        return (kind,) + tuple(extra) + (
            self._frozen_sig(), self.mesh_manager.cache_token())

    def _zero1_program(self):
        from deeplearning4j_tpu.engine.sharding import build_zero1_step

        key = self._zero1_key("engine_zero1")
        cache = self.net._jit_cache
        if key not in cache:
            cache[key] = build_zero1_step(
                self.net, self.mesh_manager, str(key))
            cache.register_policy(key, self.precision_policy)
        return cache[key]

    def _run_zero1(self, x, y, fm=None, lm=None):
        """One ZeRO-1 training step — the net-state contract of
        `_train_step` (params/upd/states rebound, rng split on host,
        iteration advanced, `_score` set) on the mesh-sharded
        program."""
        import jax
        import jax.numpy as jnp

        net = self.net
        if self.is_graph:
            x, y, fm, lm = self._graph_args(x, y, fm, lm)
        fn = self._zero1_program()
        net._rng, sub = jax.random.split(net._rng)
        (net.params, net.updater_states, net.states, loss) = fn(
            net.params, net.updater_states, net.states,
            jnp.asarray(net.iteration, jnp.int32), x, y, fm, lm, sub,
            jnp.asarray(net._lr_score_factor, jnp.float32))
        net.iteration += 1
        net._score = loss
        net._apply_score_decay(loss)
        return loss

    # -------------------------------------- engine-owned trainer programs
    def trainer_program(self, kind: str, build, *key_extra):
        """Engine-owned compilation for the shard_map trainer programs
        (LocalStepTrainer's dp rendezvous, StaleGradientTrainer's
        delayed-gradient step): the compiled callable lives in the
        net's JitCache under an ``(kind, *key_extra, frozen_sig)`` key
        with the program's precision policy registered — so recompile
        forensics, the program lint's policy checks, and the mesh arc
        all see ONE compilation owner instead of per-trainer private
        caches. `build(trace_key)` compiles the program; the trace key
        is the cache key's string form (forensics names the entry the
        same way run_group's groups are named)."""
        cache = self.net._jit_cache
        key = (kind,) + tuple(key_extra) + (self._frozen_sig(),)
        if key not in cache:
            cache[key] = build(str(key))
            cache.register_policy(key, self.precision_policy)
        return cache[key]

    # ------------------------------------------------------ validation
    def require_sgd(self, entry: str) -> None:
        """Line-search solvers drive multiple loss evaluations per
        iteration from the host — there is no single compiled step to
        supervise. Every harness entry point calls this once."""
        if getattr(self.net.conf, "optimization_algo",
                   "stochastic_gradient_descent") not in (
                "stochastic_gradient_descent", "sgd"):
            raise NotImplementedError(
                f"line-search solvers are not supported under {entry}; "
                "use stochastic_gradient_descent")

    # ------------------------------------------------------- single step
    def _graph_args(self, x, y, fm, lm):
        name = self.net.conf.network_inputs[0]
        return ({name: x}, [y],
                None if fm is None else {name: fm},
                None if lm is None else [lm])

    def run(self, x, y, fm=None, lm=None):
        """One training step on a canonical (x, y[, fm, lm]) batch:
        the graph-input and TBPTT-chunking dispatch the fit loops used
        to duplicate, routed into the net's cached donated step
        program. Returns the device loss scalar."""
        net = self.net
        if self.mesh_manager is not None:
            return self._run_zero1(x, y, fm, lm)
        chunked = self.is_tbptt and getattr(x, "ndim", 0) == 3
        if self.is_graph:
            ins, labs, fms, lms = self._graph_args(x, y, fm, lm)
            if chunked:
                return net._fit_tbptt(ins, labs, fms, lms)
            loss, _ = net._train_step(ins, labs, fms, lms)
            return loss
        if chunked:
            return net._fit_tbptt(x, y, fm, lm)
        loss, _ = net._train_step(x, y, fm, lm)
        return loss

    def run_batch(self, batch):
        """One step on a batch in any container shape ((x, y), DataSet,
        (x, y, fm, lm), ...) with full fit_batch semantics (listener
        fire, solver fallback) — the EarlyStoppingTrainer entry. With
        a mesh attached the batch routes through the ZeRO-1 sharded
        step (listener fire preserved; solvers already rejected by
        require_sgd at the harness entry)."""
        if self.mesh_manager is None:
            return self.net.fit_batch(batch)
        import jax.numpy as jnp

        from deeplearning4j_tpu.nn.multilayer import (
            _as_batch as _as_b,
        )

        import jax

        net = self.net
        mgr = self.mesh_manager
        x, y, fm, lm = _as_b(batch)
        # dp-shard the batch when divisible (the same staging
        # TrainingMaster / ParallelWrapper feed run() with — and the
        # layout the byte-parity oracle stages); an indivisible batch
        # replicates, trading partitioned compute for correctness
        b = int(np.asarray(x).shape[0])
        sh = (mgr.batch_sharding() if b % mgr.dp == 0
              else mgr.replicated())
        put = lambda a: jax.device_put(jnp.asarray(a, net.dtype), sh)
        x = put(x)
        y = put(y)
        net._last_batch_size = b
        fm = None if fm is None else put(fm)
        lm = None if lm is None else put(lm)
        loss = self._run_zero1(x, y, fm, lm)
        for listener in net.listeners:
            listener.iteration_done(net, net.iteration)
        return loss

    # ------------------------------------------------------ k-step group
    def _frozen_sig(self):
        net = self.net
        if self.is_graph:
            return tuple(sorted(n.name for n in net.topo
                                if n.kind == "layer" and n.obj.frozen))
        return tuple(i for i, l in enumerate(net.conf.layers)
                     if l.frozen)

    def _build_group(self, k: int, with_fm: bool, with_lm: bool,
                     trace_key: str):
        """Compile the k-step scan group. The scan carry splits the rng
        chain per inner step exactly like k sequential `_train_step`
        calls (`rng, sub = split(rng)`), so the group's state evolution
        matches the sequential oracle; per-inner-step losses come back
        stacked [k] for the guard's granularity."""
        import jax

        from deeplearning4j_tpu.nn.updater import schedule_lr

        net = self.net
        conf = net.conf
        loss_for_grad, apply_updates = make_loss_and_apply(net)

        def group_step_fn(params, upd_states, states, rng, step0,
                          xs, ys, fms, lms, lr_scale):
            net._jit_cache.record_trace(trace_key)

            def one(carry, sl):
                params, upd_states, states, rng, step = carry
                x, y, fm, lm = sl
                rng, sub = jax.random.split(rng)
                (loss, new_states), grads = jax.value_and_grad(
                    loss_for_grad, has_aux=True)(
                        params, states, x, y, sub, fm, lm)
                grads = net._clip_grads(grads)
                lr = schedule_lr(conf, step) * lr_scale
                params, upd_states = apply_updates(
                    params, upd_states, grads, lr, step)
                return ((params, upd_states, new_states, rng, step + 1),
                        loss)

            (params, upd_states, states, rng, _), losses = jax.lax.scan(
                one, (params, upd_states, states, rng, step0),
                (xs, ys, fms, lms))
            return params, upd_states, states, rng, losses

        return jax.jit(group_step_fn, donate_argnums=(0, 1, 2, 3))

    def group_key(self, k: int, with_fm: bool, with_lm: bool):
        """JitCache key of the k-step group program (public so perf
        registration and forensics reads name the same entry)."""
        return ("engine_group", k, with_fm, with_lm, self._frozen_sig())

    def run_group(self, xs, ys, fms=None, lms=None):
        """One dispatch, k steps. `xs`/`ys` (and optional masks) carry a
        leading [k, ...] step dim; state advances exactly as k
        sequential `run` calls would (same rng split chain, same
        per-step lr schedule). Sets `last_step_losses` to the [k]
        device losses and `_score` to the final one. TBPTT nets and
        lr_policy='score' have per-step host state and fall back to
        k=1 dispatch upstream."""
        import jax
        import jax.numpy as jnp

        net = self.net
        if self.is_tbptt:
            raise NotImplementedError(
                "k-step grouping does not support truncated BPTT (the "
                "scan carries no RNN state); use steps_per_dispatch=1")
        if getattr(net.conf, "lr_policy", None) == "score":
            raise NotImplementedError(
                "k-step grouping does not support lr_policy='score' "
                "(the decay factor is host state updated per step); "
                "use steps_per_dispatch=1")
        k = int(np.asarray(xs).shape[0])
        if self.is_graph:
            xs, ys, fms, lms = self._graph_args(xs, ys, fms, lms)
        if self.mesh_manager is not None:
            from deeplearning4j_tpu.engine.sharding import (
                build_zero1_group,
            )

            key = self._zero1_key("engine_zero1_group", k,
                                  fms is not None, lms is not None)
            cache = net._jit_cache
            if key not in cache:
                cache[key] = build_zero1_group(
                    net, self.mesh_manager, k, str(key))
                cache.register_policy(key, self.precision_policy)
        else:
            key = self.group_key(k, fms is not None, lms is not None)
            cache = net._jit_cache
            if key not in cache:
                cache[key] = self._build_group(
                    k, fms is not None, lms is not None, str(key))
                cache.register_policy(key, self.precision_policy)
        (net.params, net.updater_states, net.states, net._rng,
         losses) = cache[key](
            net.params, net.updater_states, net.states, net._rng,
            jnp.asarray(net.iteration, jnp.int32), xs, ys, fms, lms,
            jnp.asarray(net._lr_score_factor, jnp.float32))
        net.iteration += k
        self.last_step_losses = losses
        net._score = losses[-1]
        return losses[-1]

    # ------------------------------------------------------------- lint
    def lint_records(self, x, y, fm=None, lm=None, k=None, name=None):
        """ProgramRecords for this net's compiled step programs — the
        k=1 single step (graph/TBPTT adaptation included) and, when
        `k` is given, the k-step scan group — for
        `analysis/program_lint`. Programs are built and
        policy-registered through the same cache paths `run`/`run_group`
        use, but only traced/lowered by the lint, never executed, so
        the net's live (donated) buffers stay valid."""
        import jax.numpy as jnp

        from deeplearning4j_tpu.analysis.program_lint import (
            ProgramRecord,
        )

        net = self.net
        base = name or ("engine_graph" if self.is_graph
                        else "engine_single")
        carries = None
        if self.is_tbptt:
            batch = int(np.asarray(x).shape[0])
            carries = net._initial_carries(batch)
            base = name or "engine_tbptt"
        if self.is_graph:
            ins, labs, fms, lms = self._graph_args(x, y, fm, lm)
            fn, args = net.lint_program(ins, labs, fms, lms,
                                        carries=carries)
        else:
            fn, args = net.lint_program(x, y, fm, lm, carries=carries)
        source = "deeplearning4j_tpu/engine/step_program.py"
        # every output of the step contract is consumed by the fit
        # loops (params/upd/states/carries, loss) — declaring that
        # arms prog-dead-output against a future output nobody binds
        records = [ProgramRecord(
            name=base, fn=fn, example_args=args,
            precision_policy=self.precision_policy, source=source,
            consumed_outputs=tuple(range(5)))]
        if k:
            xs = jnp.broadcast_to(jnp.asarray(x), (k,) + np.shape(x))
            ys = jnp.broadcast_to(jnp.asarray(y), (k,) + np.shape(y))
            if self.is_graph:
                xs, ys, _, _ = self._graph_args(xs, ys, None, None)
            key = self.group_key(k, False, False)
            cache = net._jit_cache
            if key not in cache:
                cache[key] = self._build_group(k, False, False, str(key))
                cache.register_policy(key, self.precision_policy)
            gfn = cache[key]
            gargs = (net.params, net.updater_states, net.states,
                     net._rng, jnp.asarray(net.iteration, jnp.int32),
                     xs, ys, None, None,
                     jnp.asarray(net._lr_score_factor, jnp.float32))
            records.append(ProgramRecord(
                name=f"{base}_group_k{k}",
                fn=getattr(gfn, "__wrapped__", gfn),
                example_args=gargs,
                precision_policy=self.precision_policy, source=source,
                consumed_outputs=tuple(range(5))))
        return records

    def lint_record_zero1(self, x, y, name=None):
        """ProgramRecord of the ZeRO-1 mesh-sharded step for
        `analysis/program_lint` (requires an attached mesh). The
        example args are staged exactly as the live path stages them —
        params replicated, optimizer state SHARDED, batch dp-sharded —
        so the lowering bakes the real sharding annotations the
        `prog-unsharded-optimizer-state` rule verifies, and
        `sharded_argnums` declares which argument's leaves must carry
        them (argnum 1 = the optimizer state)."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.analysis.program_lint import (
            ProgramRecord,
        )

        if self.mesh_manager is None:
            raise ValueError("lint_record_zero1 requires attach_mesh")
        net = self.net
        mgr = self.mesh_manager
        if net.params is None:
            net.init()
        fn = self._zero1_program()
        params = mgr.replicate_tree(jax.tree_util.tree_map(
            np.asarray, net.params))
        upd = mgr.shard_tree(jax.tree_util.tree_map(
            np.asarray, net.updater_states))
        states = mgr.replicate_tree(jax.tree_util.tree_map(
            np.asarray, net.states))
        xb = jax.device_put(jnp.asarray(x, net.dtype),
                            mgr.batch_sharding())
        yb = jax.device_put(jnp.asarray(y, net.dtype),
                            mgr.batch_sharding())
        if self.is_graph:
            xb, yb, _, _ = self._graph_args(xb, yb, None, None)
        _, sub = jax.random.split(net._rng)
        args = (params, upd, states,
                jnp.asarray(net.iteration, jnp.int32), xb, yb, None,
                None, sub,
                jnp.asarray(net._lr_score_factor, jnp.float32))
        return ProgramRecord(
            name=name or "engine_zero1",
            fn=getattr(fn, "__wrapped__", fn), example_args=args,
            precision_policy=self.precision_policy,
            source="deeplearning4j_tpu/engine/sharding.py",
            consumed_outputs=tuple(range(4)),
            sharded_argnums=(1,))
