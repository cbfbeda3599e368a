"""Weights from `--seed`: one jitted call on the device, float32.

The program and the plain reference both get their weights from here,
from the shapes the reference derives from the configuration file, so
neither takes anything the other has made. Rules go by a leaf's last
key: matrices and kernels are normals scaled by `w_scale/sqrt(fan_in)`
(or the fixed `w_std` of the configuration), gains are 1 + 0.1 n, every
other vector is 0.05 n — no leaf is constant, so no gradient is zero
for want of a value. `gain_by_suffix` scales the gains of the layers
whose name ends so (the last batch-norm of a residual branch starts
small, as in Goyal et al. 2017, arXiv:1706.02677, section 5.1).
"""

from __future__ import annotations

import math

GAIN_KEYS = ("gamma", "ln1_g", "ln2_g", "lnf_g")


def _paths(shapes, prefix=()):
    out = []
    for k in sorted(shapes):
        v = shapes[k]
        if isinstance(v, dict):
            out += _paths(v, prefix + (k,))
        elif isinstance(v, (list, tuple)) and v and isinstance(v[0], dict):
            for i, d in enumerate(v):
                out += _paths(d, prefix + (k, i))
        else:
            out.append((prefix + (k,), tuple(v)))
    return out


def _put(tree, path, value):
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def make_weights(shapes: dict, seed: int, init: dict):
    """`shapes`: nested dict of leaf shapes (a list of dicts becomes a
    dict keyed by index). Returns the same nesting of float32 device
    arrays, made in one jitted call from the seed."""
    import jax
    import jax.numpy as jnp

    leaves = _paths(shapes)
    w_std = init.get("w_std")
    w_scale = float(init.get("w_scale", 1.0))
    gains = {k: float(v) for k, v in init.get("gain_by_suffix", {}).items()}

    def gain(path):
        return next((g for suffix, g in gains.items() if len(path) > 1
                     and str(path[-2]).endswith(suffix)), 1.0)

    def build(lo, hi):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(0), lo), hi)
        out = {}
        for i, (path, shape) in enumerate(leaves):
            n = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            name = path[-1]
            if len(shape) >= 2:
                fan_in = math.prod(shape[:-1])
                v = n * (w_std if w_std is not None
                         else w_scale / math.sqrt(fan_in))
            elif name in GAIN_KEYS:
                v = gain(path) * (1.0 + 0.1 * n)
            else:
                v = 0.05 * n
            _put(out, path, v)
        return out

    seed = int(seed)
    return jax.jit(build)(jnp.uint32(seed & 0xFFFFFFFF),
                          jnp.uint32(seed >> 32))
