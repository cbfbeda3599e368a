"""ComputationGraph: the DAG network container.

Parity: nn/graph/ComputationGraph.java (3,159 LoC) — topo-sorted vertex
execution (topologicalOrder :144, init :364), fit(DataSetIterator) :787,
fit(MultiDataSetIterator) :907, computeGradientAndScore :1213,
rnnTimeStep :2269. Vertex impls: nn/graph/vertex/impl/.

TPU-native design mirrors MultiLayerNetwork: params are a dict
name -> pytree, the whole forward+backward+update is one jit-compiled XLA
program, gradients via jax.grad over the summed multi-output loss.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf.graph_conf import (
    ComputationGraphConfiguration,
    GraphNode,
)
from deeplearning4j_tpu.nn.conf.graph_vertices import (
    ElementWiseVertex,
    LastTimeStepVertex,
)
from deeplearning4j_tpu.nn.jit_cache import JitCache, policy_name
from deeplearning4j_tpu.nn.layers.conv import (
    Convolution1DLayer,
    ConvolutionLayer,
    Subsampling1DLayer,
    SubsamplingLayer,
)
from deeplearning4j_tpu.nn.layers.core import (
    ActivationLayer,
    BaseOutputLayer,
    DenseLayer,
    GlobalPoolingLayer,
)
from deeplearning4j_tpu.nn.layers.norm import BatchNormalization
from deeplearning4j_tpu.nn.layers.recurrent import (
    LSTM,
    GravesBidirectionalLSTM,
)
from deeplearning4j_tpu.nn.updater import (fused_apply, get_updater,
                                            schedule_lr)


def _as_multi(data) -> Tuple[List, List, Optional[List], Optional[List]]:
    """Normalize to (inputs, labels, input_masks, label_masks) lists.
    Accepts MultiDataSet-like objects, (x, y) with arrays or lists."""
    if hasattr(data, "features"):
        f, l = data.features, data.labels
        fm = getattr(data, "features_mask", None)
        lm = getattr(data, "labels_mask", None)
        as_list = lambda v: (list(v) if isinstance(v, (list, tuple)) else
                             [v]) if v is not None else None
        return as_list(f), as_list(l), as_list(fm), as_list(lm)
    if isinstance(data, (tuple, list)):
        x = data[0]
        y = data[1] if len(data) > 1 else None
        fm = data[2] if len(data) > 2 else None
        lm = data[3] if len(data) > 3 else None
        as_list = lambda v: (list(v) if isinstance(v, (list, tuple))
                             else [v]) if v is not None else None
        return as_list(x), as_list(y), as_list(fm), as_list(lm)
    return [data], None, None, None


_SCOPE_KINDS = (
    ("conv", (ConvolutionLayer, Convolution1DLayer)),
    ("dense", (DenseLayer, BaseOutputLayer)),
    ("bn", (BatchNormalization,)),
    ("act", (ActivationLayer,)),
    ("pool", (SubsamplingLayer, Subsampling1DLayer, GlobalPoolingLayer)),
)


def node_scope(node: GraphNode) -> str:
    """`<kind>/<vertex name>`: the `jax.named_scope` a vertex's
    operations run under, forward and (through `transpose(jvp(...))`)
    backward. The kind says what the work is (conv, dense, bn, act,
    add, pool, other), so that a device trace sums by kind whatever
    fusions the compiler makes (benchmark/timeline.py)."""
    if isinstance(node.obj, ElementWiseVertex) and node.obj.op == "add":
        return f"add/{node.name}"
    for kind, classes in _SCOPE_KINDS:
        if isinstance(node.obj, classes):
            return f"{kind}/{node.name}"
    return f"other/{node.name}"


class ComputationGraph:
    def __init__(self, conf: ComputationGraphConfiguration,
                 dtype=jnp.float32, compute_dtype=None):
        """`dtype` = parameter/optimizer dtype; `compute_dtype` (e.g.
        jnp.bfloat16) runs forward+backward in that dtype with fp32 master
        params — the TPU mixed-precision policy (see nn/dtype.py)."""
        if not conf.nodes:
            raise ValueError("Configuration has no nodes")
        from deeplearning4j_tpu.nn.dtype import canonical_dtype
        self.conf = conf
        self.dtype = dtype
        self.compute_dtype = canonical_dtype(compute_dtype)
        self.topo: List[GraphNode] = conf.topological_order()
        self.node_types = None
        self._layer_in_types = None
        if conf.input_types:
            self.node_types, self._layer_in_types = conf.resolve_shapes(
                return_layer_inputs=True)
        self._params: Optional[Dict[str, Any]] = None
        self.states: Optional[Dict[str, Any]] = None
        self._upd_states: Optional[Dict[str, Any]] = None
        self._flat_train = None       # (flat params, flat updater state)
        self._flat_chain = "uninit"   # grad-over-flat carrier (updater/)
        self.rnn_states: Optional[Dict[str, Any]] = None
        self.iteration = 0
        self.epoch = 0
        self._score = None
        self.listeners: List = []
        self._rng = None
        self._jit_cache: JitCache = JitCache()
        self._updaters: Optional[Dict[str, Any]] = None
        self._lr_score_factor = 1.0   # lr_policy="score" decay state
        self._best_score = None
        self._fusion_plan = "uninit"   # helper tier (nn/helpers/)

    # -------------------------------------------------- params (flat carry)
    # The train step carries ONE flat parameter/updater-state vector when
    # the configuration allows (updater/flat_chain.py — the UpdaterBlock
    # flattened-view role); `params`/`updater_states` materialize the
    # usual per-layer trees on demand. Any external access drops the flat
    # carry, since the caller may mutate the returned tree.
    def _materialize_flat(self):
        if self._flat_train is not None:
            chain = self._flat_chain
            flat, uflat = self._flat_train
            self._params = chain.unravel(flat)
            self._upd_states = chain.unravel_upd(uflat, self._upd_states)
            self._flat_train = None

    @property
    def params(self):
        self._materialize_flat()
        return self._params

    @params.setter
    def params(self, value):
        self._flat_train = None
        self._params = value

    @property
    def updater_states(self):
        self._materialize_flat()
        return self._upd_states

    @updater_states.setter
    def updater_states(self, value):
        self._flat_train = None
        self._upd_states = value

    def _flat_chain_obj(self):
        if self._flat_chain == "uninit":
            from deeplearning4j_tpu.nn.updater.flat_chain import (
                FlatTrainChain,
            )
            self._flat_chain = FlatTrainChain.build(self)
        return self._flat_chain

    # ------------------------------------------------------------------ init
    def init(self, seed: Optional[int] = None) -> "ComputationGraph":
        if self.node_types is None:
            raise ValueError("set input types on the configuration "
                             "before init()")
        seed = self.conf.seed if seed is None else seed
        key = jax.random.PRNGKey(seed)
        self._rng = jax.random.fold_in(key, 0xBEEF)
        self.params = {}
        self.states = {}
        layer_nodes = [n for n in self.topo if n.kind == "layer"]
        keys = jax.random.split(key, max(len(layer_nodes), 1))
        for node, k in zip(layer_nodes, keys):
            t = self._layer_in_types[node.name]
            self.params[node.name] = node.obj.init_params(k, t, self.dtype)
            self.states[node.name] = node.obj.init_state(t, self.dtype)
        self._init_updaters()
        self.clear_rnn_state()
        return self

    def _init_updaters(self):
        self._updaters = {}
        self.updater_states = {}
        for node in self.topo:
            if node.kind != "layer":
                continue
            upd = get_updater(node.obj.updater or self.conf.updater,
                              self.conf)
            self._updaters[node.name] = upd
            self.updater_states[node.name] = upd.init(self.params[node.name])

    def num_params(self) -> int:
        return sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(self.params))

    # --------------------------------------------------------------- forward
    def _helper_plan(self):
        """Lazily build the fusion plan when the helper tier is enabled
        (conf `.helpers("fused")` or env DL4J_TPU_HELPERS — the ambient
        default the reference gets from the CUDA backend's presence)."""
        if self._fusion_plan == "uninit":
            import os

            from deeplearning4j_tpu.nn.helpers import validate_helper_mode

            mode = validate_helper_mode(
                getattr(self.conf, "helper_mode", ""))
            if not mode:
                # env is the ambient default for UNSET nets only; an
                # explicit .helpers("none") stays "none"
                mode = validate_helper_mode(
                    os.environ.get("DL4J_TPU_HELPERS", "")) or "none"
            if mode in ("fused", "pallas"):
                from deeplearning4j_tpu.nn.helpers.fused_graph import (
                    build_plan,
                )
                self._fusion_plan = build_plan(
                    self.topo, self.conf.network_outputs,
                    impl="pallas" if mode == "pallas" else "xla")
            else:
                self._fusion_plan = None
        return self._fusion_plan

    def _forward(self, params, states, inputs: Dict[str, Any], *, train,
                 rng, input_masks: Optional[Dict[str, Any]] = None,
                 rnn_carries: Optional[Dict[str, Any]] = None,
                 materialize_all: bool = False):
        """Pure forward over the DAG. Returns (activations dict,
        new_states, new_carries)."""
        if self._helper_plan() is not None:
            from deeplearning4j_tpu.nn.helpers.fused_graph import (
                fused_forward,
            )
            return fused_forward(
                self, params, states, inputs, train=train, rng=rng,
                input_masks=input_masks, rnn_carries=rnn_carries,
                materialize_all=materialize_all)
        acts: Dict[str, Any] = dict(inputs)
        masks: Dict[str, Any] = dict(input_masks or {})
        new_states: Dict[str, Any] = {}
        new_carries: Dict[str, Any] = {}
        if rng is not None:
            rngs = jax.random.split(rng, max(len(self.topo), 1))
        else:
            rngs = [None] * len(self.topo)
        for i, node in enumerate(self.topo):
            xs = [acts[s] for s in node.inputs]
            in_masks = [masks.get(s) for s in node.inputs]
            self._exec_node(node, xs, in_masks, rngs[i], params, states,
                            train, rnn_carries, acts, masks, new_states,
                            new_carries)
        return acts, new_states, new_carries

    def _exec_node(self, node, xs, in_masks, rng_i, params, states, train,
                   rnn_carries, acts, masks, new_states, new_carries):
        """Execute ONE node with resolved inputs, writing its activation,
        mask, state, and carry. Shared by the default loop above and the
        fused executor's fallback branch (nn/helpers/fused_graph.py)."""
        with jax.named_scope(node_scope(node)):
            if node.kind == "layer":
                x = xs[0]
                m = in_masks[0]
                if node.preprocessor is not None:
                    x = node.preprocessor.preprocess(x)
                    m = node.preprocessor.feed_forward_mask(m, None)
                layer = node.obj
                if isinstance(layer, (LSTM, GravesBidirectionalLSTM)):
                    carry = (None if rnn_carries is None
                             else rnn_carries.get(node.name))
                    out, nc = layer.apply(
                        params[node.name], x, train=train, rng=rng_i,
                        state=carry, mask=m)
                    new_carries[node.name] = nc
                    new_states[node.name] = states[node.name]
                else:
                    st = states[node.name] if states[node.name] else None
                    out, ns = layer.apply(
                        params[node.name], x, train=train, rng=rng_i,
                        state=st, mask=m)
                    new_states[node.name] = (ns if ns is not None
                                             else states[node.name])
                acts[node.name] = out
                masks[node.name] = layer.feed_forward_mask(m, None)
            else:
                v = node.obj
                if isinstance(v, LastTimeStepVertex):
                    m = (masks.get(v.mask_input)
                         if v.mask_input else in_masks[0])
                    acts[node.name] = v.apply(xs, mask=m)
                else:
                    acts[node.name] = v.apply(xs)
                masks[node.name] = v.feed_forward_mask(in_masks, None)

    # ------------------------------------------------------------------ loss
    def _output_layer_nodes(self) -> List[GraphNode]:
        return [self.conf.node(n) for n in self.conf.network_outputs]

    def _loss_fn(self, params, states, inputs, labels, rng,
                 input_masks=None, label_masks=None, rnn_carries=None,
                 train=True):
        """Sum of output-layer losses + regularization
        (ref: ComputationGraph.computeGradientAndScore :1213)."""
        conf = self.conf
        # run DAG up to each output's pre-activation: we re-run full DAG and
        # recompute output layer pre_output from its input activation
        out_nodes = self._output_layer_nodes()
        for n in out_nodes:
            if n.kind != "layer" or not isinstance(n.obj, BaseOutputLayer):
                raise ValueError(
                    f"network output '{n.name}' must be an output layer "
                    f"to train; got {type(n.obj).__name__}")
        acts, new_states, new_carries = self._forward(
            params, states, inputs, train=train, rng=rng,
            input_masks=input_masks, rnn_carries=rnn_carries)
        with jax.named_scope("loss"):
            total = 0.0
            for oi, node in enumerate(out_nodes):
                # recompute the output layer's per-example loss from its
                # input
                src = node.inputs[0]
                x = acts[src]
                if node.preprocessor is not None:
                    x = node.preprocessor.preprocess(x)
                layer = node.obj
                if rng is not None:
                    x = layer._maybe_dropout_input(
                        x, train, jax.random.fold_in(rng, 0x0D0 + oi))
                y = labels[oi]
                lm = None if label_masks is None else label_masks[oi]
                per_ex = layer.per_example_loss_from_input(
                    params[node.name], x, y, mask=lm)
                if lm is not None:
                    active = (lm if lm.ndim == 1
                              else jnp.any(lm > 0, axis=1))
                    s = jnp.sum(per_ex)
                    total = total + (s / jnp.maximum(jnp.sum(active), 1.0)
                                     if conf.minibatch else s)
                elif conf.minibatch:
                    total = total + jnp.mean(per_ex)
                else:
                    total = total + jnp.sum(per_ex)
            reg = 0.0
            for node in self.topo:
                if node.kind == "layer":
                    reg = reg + node.obj.regularization_loss(
                        params[node.name])
            total = total + reg
        return total, (new_states, new_carries)

    # ------------------------------------------------------------ train step
    def _clip_grads(self, grads):
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        return MultiLayerNetwork._clip_grads(self, grads)  # same logic

    def _build_train_step(self, with_carries: bool):
        conf = self.conf
        updaters = self._updaters
        layer_names = [n.name for n in self.topo if n.kind == "layer"]
        lr_factors = {
            n.name: ((n.obj.learning_rate / conf.learning_rate)
                     if getattr(n.obj, "learning_rate", None) is not None
                     and conf.learning_rate != 0 else 1.0)
            for n in self.topo if n.kind == "layer"
        }

        cd = self.compute_dtype

        def loss_for_grad(params, states, inputs, labels, rng, fmasks,
                          lmasks, carries):
            if cd is not None:
                from deeplearning4j_tpu.nn.dtype import cast_floating
                params = cast_floating(params, cd)
                inputs = cast_floating(inputs, cd)
                carries = cast_floating(carries, cd)
            loss, (new_states, new_carries) = self._loss_fn(
                params, states, inputs, labels, rng, fmasks, lmasks,
                rnn_carries=carries)
            if cd is not None:
                from deeplearning4j_tpu.nn.dtype import cast_floating
                new_carries = cast_floating(new_carries, self.dtype)
                loss = loss.astype(self.dtype)
            return loss, (new_states, new_carries)

        def step_fn(params, upd_states, states, step, inputs, labels,
                    fmasks, lmasks, rng, carries, lr_scale):
            self._jit_cache.record_trace(
                "train_c" if with_carries else "train")
            (loss, (new_states, new_carries)), grads = jax.value_and_grad(
                loss_for_grad, has_aux=True)(
                    params, states, inputs, labels, rng, fmasks, lmasks,
                    carries if with_carries else None)
            frozen = {n.name for n in self.topo
                      if n.kind == "layer" and n.obj.frozen}
            with jax.named_scope("updater"):
                grads = self._clip_grads(grads)
                lr = schedule_lr(conf, step) * lr_scale
                np_list, nu_list = fused_apply(
                    [(updaters[name], lr_factors[name], name in frozen,
                      params[name], grads[name], upd_states[name])
                     for name in layer_names], lr, step)
            new_params = dict(zip(layer_names, np_list))
            new_upd = dict(zip(layer_names, nu_list))
            return new_params, new_upd, new_states, new_carries, loss

        # with_carries also donates the RNN carries (arg 9): the TBPTT
        # loop rebinds them every chunk, so new_carries aliases the old
        # buffers (verified by the program lint's alias-map check)
        return jax.jit(step_fn, donate_argnums=(
            (0, 1, 2, 9) if with_carries else (0, 1, 2)))

    def _build_flat_train_step(self, with_carries: bool, chain):
        """Grad-over-flat variant of the train step: differentiates
        through chain.unravel so gradients arrive as ONE flat vector and
        the update rule is a single elementwise chain — no per-step
        concats/slices (updater/flat_chain.py)."""
        conf = self.conf
        cd = self.compute_dtype

        def loss_for_grad(flat, states, inputs, labels, rng, fmasks,
                          lmasks, carries):
            params = chain.unravel(flat)
            if cd is not None:
                from deeplearning4j_tpu.nn.dtype import cast_floating
                params = cast_floating(params, cd)
                inputs = cast_floating(inputs, cd)
                carries = cast_floating(carries, cd)
            loss, (new_states, new_carries) = self._loss_fn(
                params, states, inputs, labels, rng, fmasks, lmasks,
                rnn_carries=carries)
            if cd is not None:
                from deeplearning4j_tpu.nn.dtype import cast_floating
                new_carries = cast_floating(new_carries, self.dtype)
                loss = loss.astype(self.dtype)
            return loss, (new_states, new_carries)

        def step_fn(flat, uflat, states, step, inputs, labels,
                    fmasks, lmasks, rng, carries, lr_scale):
            self._jit_cache.record_trace(
                "train_flat_c" if with_carries else "train_flat")
            (loss, (new_states, new_carries)), g = jax.value_and_grad(
                loss_for_grad, has_aux=True)(
                    flat, states, inputs, labels, rng, fmasks, lmasks,
                    carries if with_carries else None)
            with jax.named_scope("updater"):
                g = self._clip_grads(g)
                lr = schedule_lr(conf, step) * lr_scale
                deltas, new_u = chain.updater.update(g, uflat, flat, lr,
                                                     step)
                new_flat = flat + deltas
            return new_flat, new_u, new_states, new_carries, loss

        return jax.jit(step_fn, donate_argnums=(
            (0, 1, 2, 9) if with_carries else (0, 1, 2)))

    def _train_step(self, inputs, labels, fmasks=None, lmasks=None,
                    carries=None):
        # cache key includes frozen flags: they're baked into the trace
        frozen_sig = tuple(sorted(n.name for n in self.topo
                                  if n.kind == "layer" and n.obj.frozen))
        chain = self._flat_chain_obj() if not frozen_sig else None
        self._rng, sub = jax.random.split(self._rng)
        if chain is not None:
            key = ("train_flat_c" if carries is not None else "train_flat",)
            if key not in self._jit_cache:
                self._jit_cache[key] = self._build_flat_train_step(
                    carries is not None, chain)
                self._jit_cache.register_policy(
                    key, policy_name(self.compute_dtype))
            if self._flat_train is None:
                self._flat_train = (chain.ravel(self._params),
                                    chain.ravel_upd(self._upd_states))
                # keep only a structure skeleton: the live state is the
                # flat carry; the original buffers are freed
                self._upd_states = chain.upd_skeleton(self._upd_states)
            flat, uflat = self._flat_train
            new_flat, new_u, self.states, new_carries, loss = \
                self._jit_cache[key](
                    flat, uflat, self.states,
                    jnp.asarray(self.iteration, jnp.int32), inputs,
                    labels, fmasks, lmasks, sub, carries,
                    jnp.asarray(self._lr_score_factor, jnp.float32))
            self._flat_train = (new_flat, new_u)
            self._params = None
        else:
            key = ("train_c" if carries is not None else "train",
                   frozen_sig)
            if key not in self._jit_cache:
                self._jit_cache[key] = self._build_train_step(
                    carries is not None)
                self._jit_cache.register_policy(
                    key, policy_name(self.compute_dtype))
            (self.params, self.updater_states, self.states, new_carries,
             loss) = self._jit_cache[key](
                self.params, self.updater_states, self.states,
                jnp.asarray(self.iteration, jnp.int32), inputs, labels,
                fmasks, lmasks, sub, carries,
                jnp.asarray(self._lr_score_factor, jnp.float32))
        self.iteration += 1
        self._score = loss
        self._apply_score_decay(loss)
        return loss, new_carries

    def _apply_score_decay(self, loss):
        from deeplearning4j_tpu.nn.updater import apply_score_decay
        apply_score_decay(self, loss)

    def lint_program(self, inputs, labels, fmasks=None, lmasks=None,
                     carries=None):
        """(jitted_fn, example_args) of the cached donated train step
        on the SAME path `_train_step` would take (flat-chain when
        eligible) — the program-lint view; traced/lowered, never
        executed."""
        with_carries = carries is not None
        frozen_sig = tuple(sorted(n.name for n in self.topo
                                  if n.kind == "layer" and n.obj.frozen))
        chain = self._flat_chain_obj() if not frozen_sig else None
        _, sub = jax.random.split(self._rng)
        tail = (jnp.asarray(self.iteration, jnp.int32), inputs, labels,
                fmasks, lmasks, sub, carries,
                jnp.asarray(self._lr_score_factor, jnp.float32))
        if chain is not None:
            key = ("train_flat_c" if with_carries else "train_flat",)
            if key not in self._jit_cache:
                self._jit_cache[key] = self._build_flat_train_step(
                    with_carries, chain)
                self._jit_cache.register_policy(
                    key, policy_name(self.compute_dtype))
            if self._flat_train is not None:
                flat, uflat = self._flat_train
            else:
                flat = chain.ravel(self.params)
                uflat = chain.ravel_upd(self.updater_states)
            args = (flat, uflat, self.states) + tail
        else:
            key = ("train_c" if with_carries else "train", frozen_sig)
            if key not in self._jit_cache:
                self._jit_cache[key] = self._build_train_step(
                    with_carries)
                self._jit_cache.register_policy(
                    key, policy_name(self.compute_dtype))
            args = (self.params, self.updater_states,
                    self.states) + tail
        fn = self._jit_cache[key]
        return getattr(fn, "__wrapped__", fn), args

    # ------------------------------------------------------------------- fit
    def fit(self, data, labels=None, epochs: int = 1):
        """Train on a MultiDataSet iterator / list of batches / single batch
        (ref: ComputationGraph.fit :787/:907)."""
        if self.params is None:
            self.init()
        if labels is not None:
            batches: Sequence = [(data, labels)]
        elif isinstance(data, tuple):
            batches = [data]
        elif hasattr(data, "__iter__") and not hasattr(data, "features"):
            batches = data
            if epochs > 1 and iter(batches) is batches and not hasattr(
                    batches, "reset"):
                raise ValueError(
                    "fit() got a one-shot iterator with epochs > 1; pass a "
                    "list or an iterator with reset()")
        else:
            batches = [data]

        for _ in range(epochs):
            for listener in self.listeners:
                if hasattr(listener, "on_epoch_start"):
                    listener.on_epoch_start(self)
            if hasattr(batches, "reset"):
                batches.reset()
            _it = iter(batches)
            while True:
                # ETL bookkeeping (ref: MLN.fit lastEtlTime :1108-1113)
                _t0 = time.perf_counter()
                try:
                    batch = next(_it)
                except StopIteration:
                    break
                self._last_etl_ms = (time.perf_counter() - _t0) * 1e3
                self.fit_batch(batch)
            self.epoch += 1
            for listener in self.listeners:
                if hasattr(listener, "on_epoch_end"):
                    listener.on_epoch_end(self)
        return self

    def fit_batch(self, batch):
        """Train on ONE batch without fit()'s epoch bookkeeping."""
        if self.params is None:
            self.init()
        ins, labs, fms, lms = _as_multi(batch)
        self._fit_one(ins, labs, fms, lms)
        for listener in self.listeners:
            listener.iteration_done(self, self.iteration)
        return self._score

    def _fit_one(self, ins, labs, fms, lms):
        from deeplearning4j_tpu.nn.conf.network import BackpropType

        conf = self.conf
        if labs is None:
            raise ValueError("fit needs labels")
        inputs = {name: jnp.asarray(x, self.dtype)
                  for name, x in zip(conf.network_inputs, ins)}
        labels = [jnp.asarray(y, self.dtype) for y in labs]
        self._last_batch_size = int(next(iter(inputs.values())).shape[0])
        fmasks = None
        if fms is not None:
            fmasks = {name: (None if m is None else jnp.asarray(m, self.dtype))
                      for name, m in zip(conf.network_inputs, fms)}
        lmasks = None
        if lms is not None:
            lmasks = [None if m is None else jnp.asarray(m, self.dtype)
                      for m in lms]
        if (conf.backprop_type == BackpropType.TRUNCATED_BPTT
                and all(x.ndim == 3 for x in inputs.values())):
            self._fit_tbptt(inputs, labels, fmasks, lmasks)
        elif getattr(conf, "optimization_algo",
                     "stochastic_gradient_descent") not in (
                "stochastic_gradient_descent", "sgd"):
            from deeplearning4j_tpu.optimize.solvers import make_solver

            if getattr(self, "_solver", None) is None:
                self._solver = make_solver(conf.optimization_algo, self)
            loss = self._solver.step(inputs, labels, fmasks, lmasks)
            self.iteration += 1
            self._score = loss
        else:
            self._train_step(inputs, labels, fmasks, lmasks)

    def _fit_tbptt(self, inputs, labels, fmasks, lmasks):
        """Truncated BPTT over the DAG: slice every 3-D input/label on the
        time axis into fwd-length chunks, carry RNN state across chunks
        (ref: ComputationGraph's TBPTT path mirrors MLN
        truncatedBPTTGradient :1395)."""
        T = next(iter(inputs.values())).shape[1]
        L = self.conf.tbptt_fwd_length
        batch = next(iter(inputs.values())).shape[0]
        carries = self._initial_carries(batch)
        for start in range(0, T, L):
            end = min(start + L, T)
            sl = lambda a: a[:, start:end] if a is not None and a.ndim >= 2 \
                and a.shape[1] == T else a
            ins = {k: sl(v) for k, v in inputs.items()}
            labs = [y[:, start:end] if y.ndim == 3 else y for y in labels]
            fms = (None if fmasks is None
                   else {k: sl(v) for k, v in fmasks.items()})
            lms = (None if lmasks is None else [sl(m) for m in lmasks])
            _, carries = self._train_step(ins, labs, fms, lms,
                                          carries=carries)
            carries = jax.lax.stop_gradient(carries)

    # ------------------------------------------------------------- inference
    def output(self, *xs, train: bool = False):
        """Forward pass; returns the output-node activations (single array
        if one output)."""
        conf = self.conf
        if len(xs) == 1 and isinstance(xs[0], (list, tuple)):
            xs = tuple(xs[0])
        inputs = {name: jnp.asarray(x, self.dtype)
                  for name, x in zip(conf.network_inputs, xs)}
        if "predict" not in self._jit_cache:
            cd = self.compute_dtype

            def predict_fn(params, states, inputs):
                self._jit_cache.record_trace("predict")
                if cd is not None:
                    from deeplearning4j_tpu.nn.dtype import cast_floating
                    params = cast_floating(params, cd)
                    inputs = cast_floating(inputs, cd)
                acts, _, _ = self._forward(params, states, inputs,
                                           train=False, rng=None)
                return [acts[n].astype(self.dtype) if cd is not None
                        else acts[n] for n in self.conf.network_outputs]
            self._jit_cache["predict"] = jax.jit(predict_fn)
            self._jit_cache.register_policy(
                "predict", policy_name(self.compute_dtype))
        outs = self._jit_cache["predict"](self.params, self.states, inputs)
        return outs[0] if len(outs) == 1 else outs

    def feed_forward(self, *xs, train: bool = False):
        """All activations dict name -> array."""
        inputs = {name: jnp.asarray(x, self.dtype)
                  for name, x in zip(self.conf.network_inputs, xs)}
        acts, _, _ = self._forward(self.params, self.states, inputs,
                                   train=train, rng=None,
                                   materialize_all=True)
        return acts

    def evaluate(self, iterator, evaluation=None, output_index: int = 0):
        """Evaluate the output at `output_index` over a (Multi)DataSet
        iterator (ref: ComputationGraph.evaluate(DataSetIterator))."""
        from deeplearning4j_tpu.eval import Evaluation

        ev = evaluation if evaluation is not None else Evaluation()
        for batch in iterator:
            ins, labs, fms, lms = _as_multi(batch)
            out = self.output(*ins)
            outs = out if isinstance(out, (list, tuple)) else [out]
            lm = None if lms is None else lms[output_index]
            ev.eval(np.asarray(labs[output_index]),
                    np.asarray(outs[output_index]), mask=lm)
        return ev

    def summary(self) -> str:
        """Node table with shapes and parameter counts
        (ref: ComputationGraph.summary())."""
        rows = [("name", "kind", "type", "inputs", "out", "params")]
        total = 0
        for node in self.topo:
            if node.kind == "layer" and self.params is not None:
                n = sum(int(np.prod(l.shape)) for l in
                        jax.tree_util.tree_leaves(self.params[node.name]))
            else:
                n = 0
            total += n
            out_t = (str(self.node_types.get(node.name))
                     if self.node_types else "?")
            rows.append((node.name, node.kind, type(node.obj).__name__,
                         ",".join(node.inputs), out_t, f"{n:,}"))
        widths = [max(len(r[c]) for r in rows) for c in range(6)]
        lines = ["  ".join(v.ljust(w) for v, w in zip(r, widths))
                 for r in rows]
        lines.insert(1, "-" * len(lines[0]))
        lines.append(f"Total parameters: {total:,}")
        return "\n".join(lines)

    def raw_score(self):
        """Last training loss WITHOUT the device->host sync `score()`
        pays (see MultiLayerNetwork.raw_score)."""
        return self._score

    def score(self, data=None):
        if data is None:
            return None if self._score is None else float(self._score)
        ins, labs, fms, lms = _as_multi(data)
        inputs = {name: jnp.asarray(x, self.dtype)
                  for name, x in zip(self.conf.network_inputs, ins)}
        labels = [jnp.asarray(y, self.dtype) for y in labs]
        fmasks = None
        if fms is not None:
            fmasks = {name: (None if m is None else jnp.asarray(m))
                      for name, m in zip(self.conf.network_inputs, fms)}
        lmasks = (None if lms is None else
                  [None if m is None else jnp.asarray(m) for m in lms])
        loss, _ = self._loss_fn(self.params, self.states, inputs, labels,
                                None, fmasks, lmasks, train=False)
        return float(loss)

    # --------------------------------------------------------- streaming RNN
    def rnn_time_step(self, *xs):
        """Stateful decoding (ref: ComputationGraph.rnnTimeStep :2269)."""
        for node in self.topo:
            if isinstance(node.obj, GravesBidirectionalLSTM):
                raise ValueError(
                    "rnn_time_step is not supported for bidirectional "
                    "RNN layers; use output() on the full sequence")
        inputs = {}
        single = False
        for name, x in zip(self.conf.network_inputs, xs):
            x = jnp.asarray(x, self.dtype)
            if x.ndim == 2:
                single = True
                x = x[:, None, :]
            inputs[name] = x
        if self.rnn_states is None or self.rnn_states == "uninit":
            batch = next(iter(inputs.values())).shape[0]
            self.rnn_states = self._initial_carries(batch)
        acts, _, new_carries = self._forward(
            self.params, self.states, inputs, train=False, rng=None,
            rnn_carries=self.rnn_states)
        for k, v in new_carries.items():
            if v is not None:
                self.rnn_states[k] = v
        outs = [acts[n] for n in self.conf.network_outputs]
        if single:
            outs = [o[:, -1, :] if o.ndim == 3 else o for o in outs]
        return outs[0] if len(outs) == 1 else outs

    def _initial_carries(self, batch_size):
        carries = {}
        for node in self.topo:
            if isinstance(node.obj, GravesBidirectionalLSTM):
                sub = node.obj._directional()
                c = sub.initial_carry(batch_size, self.dtype)
                carries[node.name] = (c, c)
            elif isinstance(node.obj, LSTM):
                carries[node.name] = node.obj.initial_carry(
                    batch_size, self.dtype)
        return carries

    def clear_rnn_state(self):
        self.rnn_states = "uninit"

    # -------------------------------------------------------------- plumbing
    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def get_layer(self, name: str):
        return self.conf.node(name).obj

    def n_layers(self) -> int:
        return sum(1 for n in self.topo if n.kind == "layer")
