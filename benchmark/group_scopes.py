"""Device time by named scope for the readers of a GROUP of scopes that
neither `timeline.py`'s table nor `scope_times.py`'s knows, named
`<group>/<part>` (two tokens name the scope): `ssd/in_proj`,
`ssd/conv`, `ssd/scan`, `ssd/out` of the Mamba-2 layers
(nn/mamba2.py). The group is the readers' argument, so a further group
needs no module of its own (`kda_scopes.py` and `swa_scopes.py` are
earlier copies of this for their one group each). Reads the same trace
through `timeline.read_trace` as `scope_times.py` does, once a run and
group (kept in `facts`), and logs its table beside the others'. Every
other scope is `scope_times.scope_of`'s.
"""

from __future__ import annotations

import re

import numpy as np

from benchmark import scope_times, timeline, xplane


def scope_of(tf_op: str, group: str) -> str:
    """`jit(decode_fn)/ssd/scan/mul` -> `ssd/scan` for the group `ssd`;
    the innermost `<group>/<part>` wins, and an operation under none is
    `scope_times.scope_of`'s."""
    tokens = [t for t in re.split(r"[/()]", tf_op) if t]
    for i in range(len(tokens) - 2, -1, -1):
        if tokens[i] == group:
            return f"{group}/{tokens[i + 1]}"
    return scope_times.scope_of(tf_op)


def by_scope(facts, group: str) -> dict:
    """{program: {"n", "scopes": {scope: seconds}}} of the traced
    slice with the group's parts; an operation belongs to the execution
    it starts in."""
    key = f"{group}_scope_times"
    if key in facts:
        return facts[key]
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
        scope = scope_of(tf_op, group)
        scopes[scope] = scopes.get(scope, 0.0) + (end - start) * 1e-9
    facts[key] = out
    for prog in facts["config"]["programs"].values():
        p = out.get(prog)
        if p and p["n"]:
            log(f"device time by scope with {group}/*, {prog}, ms an "
                "execution: " + ", ".join(
                    f"{k} {v / p['n'] * 1e3:.4f}" for k, v in sorted(
                        p["scopes"].items(), key=lambda kv: -kv[1])))
    return out


def group_ms(facts, group: str, program_key: str) -> float | None:
    """Device ms under `<group>/*` (all such layers) per execution of
    the configuration's program `program_key`; None where the trace has
    no execution of it or none of its operations carries such a scope
    (a program without these layers, the parent's included)."""
    prog = by_scope(facts, group).get(
        facts["config"]["programs"][program_key])
    if not prog or not prog["n"]:
        return None
    picked = [v for k, v in prog["scopes"].items()
              if k.startswith(group + "/")]
    if not picked:
        return None
    return sum(picked) / prog["n"] * 1e3
