"""Device time by named scope for the readers of the scopes that
neither `timeline.py`'s table nor `scope_times.py`'s knows: `swa/proj`,
`swa/ring_write`, `swa/ring_read`, `swa/mix`, `swa/out` of the
sliding-window attention layers over their per-slot rings
(zoo/window_moe.py). No part is a token either table knows, so
`scope_times.scope_of`, which keeps the innermost known token, files
them all under `(unscoped)` and `swa/mix` never under `attn`. Reads the
same trace through `timeline.read_trace` as `scope_times.py` does, once
a run (kept in `facts`), and logs its table beside the others'. Every
other scope is `scope_times.scope_of`'s.
"""

from __future__ import annotations

import re

import numpy as np

from benchmark import scope_times, timeline, xplane

GROUP = "swa"       # `swa/<part>`: two tokens name the scope
PARTS = ("proj", "ring_write", "ring_read", "mix", "out")


def scope_of(tf_op: str) -> str:
    """`jit(decode_fn)/swa/mix/dot_general` -> `swa/mix`; the innermost
    `swa/<part>` wins, and an operation under none is
    `scope_times.scope_of`'s."""
    tokens = [t for t in re.split(r"[/()]", tf_op) if t]
    for i in range(len(tokens) - 2, -1, -1):
        if tokens[i] == GROUP:
            return f"{GROUP}/{tokens[i + 1]}"
    return scope_times.scope_of(tf_op)


def by_scope(facts) -> dict:
    """{program: {"n", "scopes": {scope: seconds}}} of the traced
    slice; an operation belongs to the execution it starts in."""
    if "swa_scope_times" in facts:
        return facts["swa_scope_times"]
    from benchmark.run import TRACE_DIR, log

    trace = timeline.read_trace(xplane.find_trace(TRACE_DIR))
    modules = trace["modules"]
    starts = [m[0] for m in modules]
    out = {}
    for _, _, prog in modules:
        out.setdefault(prog, {"n": 0, "scopes": {}})["n"] += 1
    for start, end, tf_op, _ in trace["ops"]:
        i = int(np.searchsorted(starts, start, side="right")) - 1
        if i < 0 or start > modules[i][1]:
            continue
        scopes = out[modules[i][2]]["scopes"]
        scope = scope_of(tf_op)
        scopes[scope] = scopes.get(scope, 0.0) + (end - start) * 1e-9
    facts["swa_scope_times"] = out
    for prog in facts["config"]["programs"].values():
        p = out.get(prog)
        if p and p["n"]:
            log(f"device time by scope with swa/*, {prog}, ms an "
                "execution: " + ", ".join(
                    f"{k} {v / p['n'] * 1e3:.4f}" for k, v in sorted(
                        p["scopes"].items(), key=lambda kv: -kv[1])))
    return out


def swa_ms(facts, program_key: str) -> float | None:
    """Device ms under `swa/*` (all window layers) per execution of the
    configuration's program `program_key`; None where the trace has no
    execution of it or none of its operations carries such a scope (a
    program without these layers, the parent's included)."""
    prog = by_scope(facts).get(facts["config"]["programs"][program_key])
    if not prog or not prog["n"]:
        return None
    picked = [v for k, v in prog["scopes"].items()
              if k.startswith(GROUP + "/")]
    if not picked:
        return None
    return sum(picked) / prog["n"] * 1e3
