"""The comparison that decides `correct`: every number beside its limit."""

from __future__ import annotations

import json
import sys

import numpy as np


def leaf_gaps(prog, ref, keep=None) -> np.ndarray:
    """By leaf, |‖prog‖ - ‖ref‖| over max(‖ref‖ of that leaf, the
    median leaf's ‖ref‖): the gap between the two norms, not the norm
    of the difference. `keep` masks the leaves that count (the median
    is taken over them); the others read -1. A gap that is not finite
    reads infinite."""
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    if keep is None:
        keep = np.ones(ref.shape, bool)
    floor = float(np.median(ref[keep]))
    gaps = np.abs(prog - ref) / np.maximum(ref, floor)
    return np.where(keep, np.where(np.isfinite(gaps), gaps, np.inf), -1.0)


def verdict(numbers: dict, limits: dict):
    """`numbers`: name -> value; `limits`: name -> limit (value must be
    <= limit). Returns (correct, compared) where compared is
    name -> [value, limit]; a number with no limit, a limit with no
    number, or a value that is not finite is not correct."""
    compared = {}
    ok = bool(limits)
    for name, limit in limits.items():
        v = numbers.get(name)
        good = v is not None and np.isfinite(v) and v <= limit
        ok = ok and bool(good)
        compared[name] = [None if v is None else float(v), float(limit)]
    return ok, compared


def print_compared(compared: dict, correct: bool) -> None:
    print("compared " + json.dumps({"correct": correct, **compared}),
          file=sys.stderr, flush=True)


def _sorted_leaves(tree):
    import jax

    return sorted(((jax.tree_util.keystr(path), leaf) for path, leaf in
                   jax.tree_util.tree_flatten_with_path(tree)[0]),
                  key=lambda kv: kv[0])


def leaf_names(tree):
    """The leaves' paths, in the order `host_leaves` lists them."""
    return [name for name, _ in _sorted_leaves(tree)]


def host_leaves(tree):
    """Every leaf as a float32 numpy array, in sorted-path order."""
    import jax

    return [np.asarray(a, np.float32) for a in
            jax.device_get([leaf for _, leaf in _sorted_leaves(tree)])]


def norms(leaves) -> np.ndarray:
    return np.asarray([np.sqrt(np.sum(np.square(a, dtype=np.float64)))
                       for a in leaves])


def leaf_diffs(prog, ref, keep=None) -> np.ndarray:
    """By leaf, the norm of the difference over max(‖ref‖ of that leaf,
    the median leaf's ‖ref‖): what rounding noise shows in, where the
    gap of two norms hides it (noise at right angles to a leaf moves
    its norm by half its square). Masked and floored as `leaf_gaps`."""
    ref_n = norms(ref)
    if keep is None:
        keep = np.ones(ref_n.shape, bool)
    floor = float(np.median(ref_n[keep]))
    d = norms([p.astype(np.float64) - r for p, r in zip(prog, ref)]) \
        / np.maximum(ref_n, floor)
    return np.where(keep, np.where(np.isfinite(d), d, np.inf), -1.0)
