"""Fused conv+BN+activation pipeline op (the pass-count eliminator).

The helper tier's core primitive (parity role: CudnnConvolutionHelper /
CudnnBatchNormalizationHelper fused algorithms, hooked at
ConvolutionLayer.java:74-84). Profiling (PERF.md) showed the flagship's
MFU ceiling is NOT kernel quality — XLA fuses `relu(scale*x+shift)` into
a conv's operand and channel-statistics into its output in ONE
roofline-bound pass — but the *materialization structure* of autodiff:
the per-layer conv→BN→relu composition saves both the conv output and
the normalized activation as residuals and splits stats/apply into
separate HBM passes.

This module restructures the chain so activations cross layer
boundaries as (raw conv output, per-channel affine) pairs:

    u     = relu(scale*x + shift [+ scale2*x2 + shift2])  # BN-apply(+add)
    y_raw = conv(u, W) + b                                # the only pass
    ssum, ssq = channel sums of y_raw                     # stats epilogue
    scale', shift' = f(gamma, beta, ssum, ssq)            # [C] algebra

`fused_conv` is a custom-VJP op: u is NEVER saved — the backward
recomputes it from the raw inputs (an elementwise chain XLA fuses into
the wgrad/dgrad convolutions' operands). Residuals are only tensors
that already exist (the raw inputs and the output). The BN backward
needs no hand-derivation: cotangents for scale/shift arrive from the
NEXT conv's backward via the chain rule, and the statistics cotangents
(dssum, dssq) flow into THIS op's backward — the classic fused-BN
backward emerges from composition (verified exact against the naive
layer composition in tests/test_helpers.py).

The convolution itself is `lax.conv_general_dilated` (MXU-tiled by XLA,
97.6% MFU in isolation — PERF.md) for any kernel/stride; grad convs are
derived with `jax.vjp` so stride/padding transposition is always right.
An opt-in Pallas kernel path exists in pallas_conv.py for the shapes
where hand tiling wins.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_DIMS_NHWC = ("NHWC", "HWIO", "NHWC")


def _conv(u, w, stride, padding):
    return lax.conv_general_dilated(
        u, w, window_strides=stride, padding=padding,
        dimension_numbers=_DIMS_NHWC)


# Inside a fused convolution's `conv/<vertex>` scope (fused_graph.py)
# the batch-norm work it carries has scopes of its own, so that a device
# trace can tell it from the convolution: `bn/apply` for the producers'
# deferred scale-and-shift, add and relu (forward and backward),
# `bn/stats` for the consumer's channel statistics, and
# `other/bias_grad` for the bias's gradient, a reduction over the whole
# output cotangent that XLA fuses with the statistics' backward. The
# innermost scope is the one a reader counts (benchmark/timeline.py).
_BN_APPLY, _BN_STATS, _BIAS_GRAD = "bn/apply", "bn/stats", "other/bias_grad"


def _prologue(x, scale, shift, x2, scale2, shift2, relu):
    with jax.named_scope(_BN_APPLY):
        u = x
        if scale is not None:
            u = u * scale.astype(x.dtype) + shift.astype(x.dtype)
        if x2 is not None:
            if scale2 is not None:
                u = u + (x2 * scale2.astype(x.dtype)
                         + shift2.astype(x.dtype))
            else:
                u = u + x2
        if relu:
            u = jnp.maximum(u, 0)
    return u


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11, 12))
def fused_conv(x, w, b, scale, shift, x2, scale2, shift2,
               stride, padding, relu, with_stats, impl="xla"):
    """y_raw = conv(act(scale*x+shift [+ scale2*x2+shift2]), w) + b,
    plus channel sum/sumsq of y_raw and the materialized activation u.

    x/x2: [B,H,W,C] raw (pre-BN) inputs; scale*/shift*: [C] f32 affines
    (None = plain tensor); stride: (sh, sw); padding: lax padding
    ('SAME'/'VALID'/explicit); relu: bool; with_stats: 0/False = no
    channel statistics (eval), 1/True = statistics of the full y
    (train-mode BN), k>1 = statistics of the leading ceil(B/k) batch
    rows of y (ghost/sampled statistics —
    BatchNormalization.stat_sample; the stats pass then reads 1/k of
    the activation).

    Returns (y_raw [B,H,W,N], ssum [N] f32, ssq [N] f32, u). `u` is the
    post-activation tensor — callers that don't use it get it DCE'd by
    XLA; residual branches use it as the materialized skip tensor.

    impl: "xla" composes lax ops (XLA fuses them); "pallas" additionally
    routes the backward of 1x1 stride-1 convs through the hand-written
    dgrad/wgrad kernels in pallas_conv.py (single-chip TPU path).
    """
    return _fwd_impl(x, w, b, scale, shift, x2, scale2, shift2,
                     stride, padding, relu, with_stats)


def _fwd_impl(x, w, b, scale, shift, x2, scale2, shift2,
              stride, padding, relu, with_stats):
    u = _prologue(x, scale, shift, x2, scale2, shift2, relu)
    y = _conv(u, w, stride, padding)
    if b is not None:
        y = y + b.astype(y.dtype)
    if with_stats:
        with jax.named_scope(_BN_STATS):
            ys = _stat_rows(y, int(with_stats))
            yf = ys.astype(jnp.float32)
            ssum = jnp.sum(yf, axis=(0, 1, 2))
            ssq = jnp.sum(yf * yf, axis=(0, 1, 2))
    else:
        n = y.shape[-1]
        ssum = jnp.zeros((n,), jnp.float32)
        ssq = jnp.zeros((n,), jnp.float32)
    return y, ssum, ssq, u


def _stat_rows(y, k):
    """Leading ceil(B/k) batch rows of y (k=1: y itself) — contiguous
    so the slice stays inside XLA's conv-epilogue fusion (a strided
    slice materializes a gather and loses ~40 ms/step on the
    flagship)."""
    if k <= 1:
        return y
    nb = (y.shape[0] - 1) // k + 1
    return lax.slice(y, (0,) * y.ndim, (nb,) + tuple(y.shape[1:]))


def _fused_conv_fwd(x, w, b, scale, shift, x2, scale2, shift2,
                    stride, padding, relu, with_stats, impl="xla"):
    out = _fwd_impl(x, w, b, scale, shift, x2, scale2, shift2,
                    stride, padding, relu, with_stats)
    y = out[0]
    # residuals: x, x2 and y are buffers that exist anyway (y is the
    # next layer's x; x2 is an earlier op's output); the rest is [C]
    return out, (x, w, b, scale, shift, x2, scale2, shift2, y)


def _fused_conv_bwd(stride, padding, relu, with_stats, impl, res, cts):
    x, w, b, scale, shift, x2, scale2, shift2, y = res
    dy, dssum, dssq, du_out = cts
    dtype = x.dtype

    if (impl == "pallas" and w.ndim == 4 and w.shape[:2] == (1, 1)
            and tuple(stride) == (1, 1) and int(with_stats) <= 1):
        return _bwd_pallas_1x1(x, w, b, scale, shift, x2, scale2, shift2,
                               y, dy, dssum, dssq, du_out, relu,
                               with_stats)

    # effective output cotangent: dy + statistics contributions (fused
    # by XLA into the grad convolutions' operand reads). With sampled
    # statistics (k>1) only the leading ghost-batch rows carry a
    # statistics contribution; a tail zero-pad extends the 1/k-sized
    # correction without re-reading the full y.
    ybar = dy
    if with_stats:
        with jax.named_scope(_BN_STATS):
            k = int(with_stats)
            if k <= 1:
                ybar = (ybar.astype(jnp.float32) + dssum
                        + 2.0 * y.astype(jnp.float32) * dssq).astype(dtype)
            else:
                ys = _stat_rows(y, k)
                corr = (dssum + 2.0 * ys.astype(jnp.float32) * dssq
                        ).astype(dtype)
                hi = y.shape[0] - ys.shape[0]
                pad_cfg = [(0, hi, 0)] + [(0, 0, 0)] * (y.ndim - 1)
                ybar = ybar + lax.pad(corr, jnp.zeros((), dtype), pad_cfg)

    # recompute u (never materialized in fwd residuals)
    u = _prologue(x, scale, shift, x2, scale2, shift2, relu)
    with jax.named_scope(_BIAS_GRAD):
        db = (jnp.sum(ybar.astype(jnp.float32), axis=(0, 1, 2))
              if b is not None else None)

    du = jax.vjp(lambda uu: _conv(uu, w, stride, padding), u)[1](ybar)[0]
    dw = jax.vjp(lambda ww: _conv(u, ww, stride, padding), w)[1](ybar)[0]

    with jax.named_scope(_BN_APPLY):
        if du_out is not None:
            du = du + du_out.astype(du.dtype)
        if relu:
            du = du * (u > 0).astype(dtype)

        def branch_grads(xb, sb):
            if sb is None:
                return du, None, None
            ds = jnp.sum(xb.astype(jnp.float32) * du.astype(jnp.float32),
                         axis=(0, 1, 2))
            dt = jnp.sum(du.astype(jnp.float32), axis=(0, 1, 2))
            return du * sb.astype(dtype), ds, dt

        dx, dscale, dshift = branch_grads(x, scale)
        if x2 is not None:
            dx2, dscale2, dshift2 = branch_grads(x2, scale2)
        else:
            dx2 = dscale2 = dshift2 = None
    return dx, dw, db, dscale, dshift, dx2, dscale2, dshift2


def _bwd_pallas_1x1(x, w, b, scale, shift, x2, scale2, shift2, y, dy,
                    dssum, dssq, du_out, relu, with_stats):
    """Backward via the fused Pallas dgrad/wgrad kernels: each big
    tensor is read once per kernel; ybar and du never round-trip HBM
    (see pallas_conv.py)."""
    from deeplearning4j_tpu.nn.helpers.pallas_conv import (
        dgrad_conv1x1,
        wgrad_conv1x1,
    )

    bsz, h, wd, k = x.shape
    m = bsz * h * wd
    n = w.shape[-1]
    w2 = w.reshape(k, n)
    dy2 = dy.reshape(m, n)
    y2 = y.reshape(m, n)
    st = (dssum, dssq) if with_stats else (None, None)
    duo = None if du_out is None else du_out.reshape(m, k)
    dx1, dx2, ds1, dt1, ds2, dt2, db = dgrad_conv1x1(
        dy2, y2, w2, x.reshape(m, k),
        None if x2 is None else x2.reshape(m, k), duo,
        scale, shift, scale2, shift2, st[0], st[1], relu)
    dw = wgrad_conv1x1(
        dy2, y2, x.reshape(m, k),
        None if x2 is None else x2.reshape(m, k),
        scale, shift, scale2, shift2, st[0], st[1], relu)
    return (dx1.reshape(x.shape), dw.reshape(w.shape).astype(w.dtype),
            db.astype(jnp.float32) if b is not None else None,
            ds1, dt1,
            None if x2 is None else dx2.reshape(x2.shape), ds2, dt2)


fused_conv.defvjp(_fused_conv_fwd, _fused_conv_bwd)


# ---------------------------------------------------------------- helpers


def bn_affine(gamma, beta, ssum, ssq, count, eps):
    """Fold BN statistics into the next conv's prologue affine.
    Returns (scale [C] f32, shift [C] f32, mean, var) — all
    differentiable, so BN's backward-through-statistics emerges from the
    chain rule through these [C]-vector ops.

    Numerical note: the variance is necessarily the one-pass
    E[x^2]-E[x]^2 form (the fused epilogue can only accumulate sums),
    which cancels in f32 when |mean| >> std. Inside a BN'd network the
    conv outputs this normalizes are standardized-scale by construction,
    so the regime does not arise past the first layer; nets fed raw
    ~1e4-scale inputs should standardize them (NormalizerStandardize) or
    keep the default executor, whose two-pass f32 path (norm.py
    _bn_stats) is immune."""
    mean = ssum / count
    var = jnp.maximum(ssq / count - mean * mean, 0.0)
    scale = gamma.astype(jnp.float32) * lax.rsqrt(var + eps)
    shift = beta.astype(jnp.float32) - mean * scale
    return scale, shift, mean, var


def bn_affine_inference(gamma, beta, mean, var, eps):
    scale = gamma.astype(jnp.float32) * lax.rsqrt(
        var.astype(jnp.float32) + eps)
    shift = beta.astype(jnp.float32) - mean.astype(jnp.float32) * scale
    return scale, shift
