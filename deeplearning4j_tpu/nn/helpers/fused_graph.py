"""Fusion planner + executor for ComputationGraph (the helper-tier hook).

Parity role: ConvolutionLayer.java:74-84 — the reference consults an
optional accelerated helper per layer and falls back to the built-in
path. Here the "helper" is a graph-level rewrite: a static planning pass
over the topo order recognizes conv→BN(→relu)(→add) chains (the
`_conv_bn` pattern every ResNet/Inception zoo model is built from) and
executes them through `fused_ops.fused_conv`, carrying activations
between fused convolutions as (raw conv output, per-channel affine)
pairs so BN-stats / BN-apply / relu / residual-add never cost separate
HBM passes. Unrecognized nodes run exactly like the default executor —
the plan degrades to per-node fallback, never changes semantics.

Enable with `.helpers("fused")` on the graph builder (serialized in the
configuration), or env `DL4J_TPU_HELPERS=fused` as the
ConvolutionLayer.java-style ambient default. Equivalence vs the default
executor is tested in tests/test_helpers.py (the CuDNNGradientChecks
pattern: same net, both executors, matching loss/grads/running stats).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.helpers.fused_ops import (
    bn_affine,
    bn_affine_inference,
    fused_conv,
)


# ------------------------------------------------------------------ plan


@dataclass
class ConvSpec:
    stride: Tuple[int, int]
    padding: object           # lax padding spec
    bn_name: Optional[str]    # BN node consuming this conv (stats sink)


@dataclass
class Plan:
    """Static fusion plan: node-name -> role."""
    impl: str = "xla"         # "xla" | "pallas" (kernel tier for bwd)
    conv: Dict[str, ConvSpec] = field(default_factory=dict)
    bn: Dict[str, str] = field(default_factory=dict)      # bn -> conv src
    vact: Dict[str, str] = field(default_factory=dict)    # act -> src node
    vadd: Dict[str, List[str]] = field(default_factory=dict)

    def covers(self) -> int:
        return (len(self.conv) + len(self.bn) + len(self.vact)
                + len(self.vadd))


def _consumers(topo) -> Dict[str, List[str]]:
    out: Dict[str, List[str]] = {n.name: [] for n in topo}
    for n in topo:
        for s in n.inputs:
            if s in out:
                out[s].append(n.name)
    return out


def build_plan(topo, network_outputs, impl: str = "xla") -> Optional[Plan]:
    """Pattern-match fusable chains over the topo order. Conservative:
    a conv is fused only when its sole consumer is a vanilla
    BatchNormalization; BN/act/add nodes become virtual only when the
    expression stays within the supported prologue shapes."""
    from deeplearning4j_tpu.nn.conf.graph_vertices import ElementWiseVertex
    from deeplearning4j_tpu.nn.layers.conv import ConvolutionLayer
    from deeplearning4j_tpu.nn.layers.core import ActivationLayer
    from deeplearning4j_tpu.nn.layers.norm import BatchNormalization

    by_name = {n.name: n for n in topo}
    cons = _consumers(topo)
    outputs = set(network_outputs)
    plan = Plan(impl=impl)

    def conv_eligible(n) -> bool:
        l = n.obj
        return (n.kind == "layer" and isinstance(l, ConvolutionLayer)
                and (l.activation in (None, "identity"))
                and not l.dropout and tuple(l.dilation) == (1, 1)
                and n.preprocessor is None and n.name not in outputs)

    def bn_eligible(n) -> bool:
        l = n.obj
        return (n.kind == "layer" and isinstance(l, BatchNormalization)
                and not l.lock_gamma_beta and not l.dropout
                and n.preprocessor is None and n.name not in outputs)

    for n in topo:
        if conv_eligible(n):
            cs = cons[n.name]
            bn_name = None
            if len(cs) == 1 and bn_eligible(by_name[cs[0]]):
                bn_name = cs[0]
            if bn_name is None:
                continue
            l = n.obj
            sh, sw = ((l.stride, l.stride)
                      if isinstance(l.stride, int) else tuple(l.stride))
            if l.convolution_mode == "same":
                padding = "SAME"
            else:
                ph, pw = ((l.padding, l.padding)
                          if isinstance(l.padding, int)
                          else tuple(l.padding))
                padding = ((ph, ph), (pw, pw))
            plan.conv[n.name] = ConvSpec((int(sh), int(sw)), padding,
                                         bn_name)
            plan.bn[bn_name] = n.name

    # virtualize act/add nodes whose inputs stay in the representation
    virtual = set(plan.bn)
    for n in topo:
        if n.name in outputs or n.preprocessor is not None:
            continue
        if (n.kind == "layer" and isinstance(n.obj, ActivationLayer)
                and n.obj.activation == "relu" and not n.obj.dropout
                and len(n.inputs) == 1 and n.inputs[0] in virtual):
            plan.vact[n.name] = n.inputs[0]
            virtual.add(n.name)
        elif (n.kind == "vertex" and isinstance(n.obj, ElementWiseVertex)
              and n.obj.op == "add" and len(n.inputs) == 2
              and any(s in plan.bn for s in n.inputs)):
            plan.vadd[n.name] = list(n.inputs)
            virtual.add(n.name)
    if not plan.conv:
        return None
    return plan


# -------------------------------------------------------------- executor


class _Expr:
    """Deferred value: relu?(sum of affine/plain terms)."""

    __slots__ = ("terms", "relu")

    def __init__(self, terms, relu=False):
        self.terms = terms            # [(tensor, scale|None, shift|None)]
        self.relu = relu


def _materialize(expr: _Expr):
    out = None
    for x, s, t in expr.terms:
        v = x if s is None else x * s.astype(x.dtype) + t.astype(x.dtype)
        out = v if out is None else out + v
    if expr.relu:
        out = jnp.maximum(out, 0)
    return out


def fused_forward(net, params, states, inputs, *, train, rng,
                  input_masks=None, rnn_carries=None,
                  materialize_all=False):
    """Drop-in replacement for ComputationGraph._forward when a fusion
    plan is active. Non-planned nodes execute through the SAME node
    executor as the default path (ComputationGraph._exec_node) —
    including masks, preprocessors, and RNN carries."""
    from deeplearning4j_tpu.nn.graph import node_scope

    plan: Plan = net._fusion_plan
    topo = net.topo
    by_name = {n.name: n for n in topo}
    acts: Dict[str, object] = dict(inputs)
    virts: Dict[str, _Expr] = {}
    raws: Dict[str, object] = {}
    stats: Dict[str, Tuple] = {}
    masks: Dict[str, object] = dict(input_masks or {})
    new_states: Dict[str, object] = {}
    new_carries: Dict[str, object] = {}
    rngs = (jax.random.split(rng, max(len(topo), 1)) if rng is not None
            else [None] * len(topo))

    def resolve(name):
        """Materialized tensor for a node (cached)."""
        if name not in acts:
            with jax.named_scope(node_scope(by_name[name])):
                acts[name] = _materialize(virts[name])
        return acts[name]

    def expr_of(name) -> _Expr:
        if name in acts:
            return _Expr([(acts[name], None, None)])
        return virts[name]

    for i, node in enumerate(topo):
        name = node.name
        # fused nodes pass an incoming feature mask through unchanged —
        # the same default-pass-through their layer/vertex types apply
        in_mask = masks.get(node.inputs[0]) if node.inputs else None
        if name in plan.conv:
            spec = plan.conv[name]
            src = node.inputs[0]
            e = expr_of(src)
            if len(e.terms) > 2:
                e = _Expr([(resolve(src), None, None)])
            (x, s1, t1) = e.terms[0]
            (x2, s2, t2) = e.terms[1] if len(e.terms) > 1 else (None,) * 3
            p = params[name]
            # with_stats carries the BN consumer's stat_sample
            # (1 = exact full-batch statistics, k>1 = ghost/sampled;
            # clamped so stat_sample<=0 means exact, matching norm.py)
            bn_layer = by_name[spec.bn_name].obj
            stats_k = (max(1, int(getattr(bn_layer, "stat_sample", 1)))
                       if train else 0)
            # the producers' deferred batch-norm apply, relu and add
            # run as this convolution's prologue and the consumer's
            # statistics as its epilogue: inside this scope, under
            # `bn/apply` and `bn/stats` (fused_ops.py)
            with jax.named_scope(node_scope(node)):
                y, ssum, ssq, u = fused_conv(
                    x, p["W"], p["b"], s1, t1, x2, s2, t2,
                    spec.stride, spec.padding, e.relu, stats_k,
                    plan.impl)
            raws[name] = y
            stats[name] = (ssum, ssq)
            if src not in acts and (e.relu or len(e.terms) > 1
                                    or e.terms[0][1] is not None):
                acts[src] = u   # byproduct: src is now materialized
            new_states[name] = states[name]
            masks[name] = in_mask
            continue
        if name in plan.bn:
            conv_src = plan.bn[name]
            layer = node.obj
            gamma = params[name]["gamma"]
            beta = params[name]["beta"]
            st = states[name]
            with jax.named_scope(node_scope(node)):
                if train:
                    ssum, ssq = stats[conv_src]
                    raw = raws[conv_src]
                    k = int(getattr(layer, "stat_sample", 1))
                    # sampled rows
                    nb = (raw.shape[0] - 1) // max(k, 1) + 1
                    count = nb * raw.shape[1] * raw.shape[2]
                    scale, shift, mean, var = bn_affine(
                        gamma, beta, ssum, ssq, count, layer.eps)
                    if st is not None:
                        d = layer.decay
                        sd = st["mean"].dtype
                        new_states[name] = {
                            "mean": d * st["mean"] + (1.0 - d)
                            * jax.lax.stop_gradient(mean).astype(sd),
                            "var": d * st["var"] + (1.0 - d)
                            * jax.lax.stop_gradient(var).astype(sd),
                        }
                    else:
                        new_states[name] = st
                else:
                    scale, shift = bn_affine_inference(
                        gamma, beta, st["mean"], st["var"], layer.eps)
                    new_states[name] = st
            virts[name] = _Expr([(raws[conv_src], scale, shift)])
            masks[name] = in_mask
            continue
        if name in plan.vact:
            e = expr_of(plan.vact[name])
            virts[name] = _Expr(list(e.terms), relu=True)
            new_states[name] = states.get(name)
            masks[name] = in_mask
            continue
        if name in plan.vadd:
            terms = []
            for s in plan.vadd[name]:
                e = expr_of(s)
                if e.relu or len(e.terms) > 1:
                    terms.append((resolve(s), None, None))
                else:
                    terms.append(e.terms[0])
            virts[name] = _Expr(terms)
            masks[name] = node.obj.feed_forward_mask(
                [masks.get(s) for s in node.inputs], None)
            continue

        # -------- default node semantics via the shared executor
        xs = [resolve(s) for s in node.inputs]
        in_masks = [masks.get(s) for s in node.inputs]
        net._exec_node(node, xs, in_masks, rngs[i], params, states, train,
                       rnn_carries, acts, masks, new_states, new_carries)

    if materialize_all:
        for name, y in raws.items():
            acts.setdefault(name, y)   # raw conv outputs ARE the conv acts
        for name in virts:
            resolve(name)
    return acts, new_states, new_carries
