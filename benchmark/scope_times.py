"""Device time by named scope for the readers of the scopes that
`timeline.py`'s own table does not know (`timeline.SERVE_SCOPES` is
GPT-2's list, and a scope outside it falls to its parent there):
`q_proj`, `kv_proj`, `attn_out` and `moe/router`, `moe/experts`,
`moe/shared` of the latent-attention, sparse-expert decoder. Reads the
same trace through `timeline.read_trace`, once a run (kept in `facts`),
and logs its table beside the harness's.
"""

from __future__ import annotations

import re

import numpy as np

from benchmark import timeline, xplane

SCOPES = timeline.SERVE_SCOPES + ("q_proj", "kv_proj", "attn_out")
GROUPS = ("moe",)       # `moe/<part>`: two tokens name the scope


def scope_of(tf_op: str) -> str:
    """`jit(decode_fn)/moe/experts/dot_general` -> `moe/experts`; the
    innermost known scope wins; none -> `(unscoped)`."""
    tokens = [t for t in re.split(r"[/()]", tf_op) if t]
    scope = timeline.UNSCOPED
    for i, tok in enumerate(tokens):
        if tok in SCOPES:
            scope = tok
        elif tok in GROUPS and i + 1 < len(tokens):
            scope = f"{tok}/{tokens[i + 1]}"
    return scope


def by_scope(facts) -> dict:
    """{program: {"n", "scopes": {scope: seconds}}} of the traced
    slice; an operation belongs to the execution it starts in."""
    if "scope_times" in facts:
        return facts["scope_times"]
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
    facts["scope_times"] = out
    for prog in facts["config"]["programs"].values():
        p = out.get(prog)
        if p and p["n"]:
            log(f"device time by scope, {prog}, ms an execution: "
                + ", ".join(f"{k} {v / p['n'] * 1e3:.4f}" for k, v in sorted(
                    p["scopes"].items(), key=lambda kv: -kv[1])))
    return out


def scope_ms(facts, program_key: str, keep) -> float | None:
    """Device ms an execution of the configuration's program
    `program_key` under the scopes `keep(scope)` picks; None where the
    trace has no execution of it or none of its operations carries one
    of them (a program without these scopes)."""
    prog = by_scope(facts).get(facts["config"]["programs"][program_key])
    if not prog or not prog["n"]:
        return None
    picked = [v for k, v in prog["scopes"].items() if keep(k)]
    if not picked:
        return None
    return sum(picked) / prog["n"] * 1e3


def moe_ms(facts) -> float | None:
    """Device ms under `moe/*` (router, held experts, shared expert, all
    expert layers) per execution of the decode-step program."""
    return scope_ms(facts, "decode_step",
                    lambda scope: scope.startswith("moe/"))
