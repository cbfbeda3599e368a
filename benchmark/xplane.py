"""From the profiler's `.xplane.pb` to busy time, per-program device
time, the operations that took most time and the longest idle gaps.

Reads the trace with `jax.profiler.ProfileData` and nothing else. A
device plane is one named `/device:TPU:<n>`; its `XLA Ops` line holds
one event per executed operation and its `XLA Modules` line one event
per executed program. An idle gap is named by the programs on its two
sides (`jit_step_fn>jit_step_fn`: the host's turn between two steps):
the host's own plane is not traced (see `run.tracing`), and the device's
clock sits a millisecond off the host's in any case.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import statistics

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_trace(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def program_name(event_name: str) -> str:
    """`jit_step_fn(8273645)` -> `jit_step_fn`."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(event_name: str) -> str:
    """`%fusion.7 = bf16[256,56,56,64]{...} fusion(...), kind=...` ->
    `%fusion.7 bf16[256,56,56,64]`: the operation and what it makes,
    without the layouts and operands that fill the trace's names."""
    name, _, rest = event_name.partition(" = ")
    made = re.sub(r"\{[^{}]*\}", "", rest.split(" ", 1)[0])
    return f"{name} {made}".strip()[:96]


def _union(intervals):
    """Merged, sorted [start, end] intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce_profile(profile, top: int = 10, programs=()) -> dict:
    """`profile`: a ProfileData. Times in seconds. `window_s` spans the
    first start to the last end of the executions of `programs` (the
    cell's own compiled programs; every program where none is named),
    so that the profiler's own start does not count as idle; `busy_s`
    is the union of the operation intervals inside it, averaged over
    the device planes."""
    busy, progs, ops = [], {}, {}
    lo, hi = float("inf"), float("-inf")
    first_merged, first_runs = None, []
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        intervals, w_lo, w_hi = [], float("inf"), float("-inf")
        runs = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    s, d = ev.start_ns, ev.duration_ns
                    intervals.append((s, s + d))
                    name = op_name(ev.name)
                    ops[name] = ops.get(name, 0.0) + d * 1e-9
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    pname = program_name(ev.name)
                    p = progs.setdefault(pname, {"n": 0, "seconds": 0.0,
                                                 "each": []})
                    p["n"] += 1
                    p["seconds"] += ev.duration_ns * 1e-9
                    p["each"].append(ev.duration_ns * 1e-9)
                    runs.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                 pname))
                    if not programs or pname in programs:
                        w_lo = min(w_lo, ev.start_ns)
                        w_hi = max(w_hi, ev.start_ns + ev.duration_ns)
        merged = [[max(s, w_lo), min(e, w_hi)]
                  for s, e in _union(intervals) if e > w_lo and s < w_hi]
        if not merged:
            continue
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        lo, hi = min(lo, w_lo), max(hi, w_hi)
        if first_merged is None:
            first_merged, first_runs = merged, sorted(runs)
    if not busy:
        return {"busy_s": 0.0, "window_s": 0.0, "chips": 0,
                "programs": {}, "device_ops": [], "idle_gaps": []}
    n = len(busy)
    for p in progs.values():        # every chip runs every program
        p["n"] //= n
        p["seconds"] /= n
        # what one execution takes: the median, since the slice's first
        # execution is cut short by the profiler's start
        p["median_s"] = statistics.median(p.pop("each"))
    gaps = {}
    starts = [r[0] for r in first_runs]
    edges = [(lo, lo)] + [tuple(m) for m in first_merged] + [(hi, hi)]
    for (_, e0), (s1, _) in zip(edges, edges[1:]):
        if s1 <= e0:
            continue
        mid = (e0 + s1) / 2
        i = bisect.bisect_right(starts, mid)
        before = first_runs[i - 1] if i else None
        if before and before[1] > mid:
            label = f"inside:{before[2]}"
        else:
            after = first_runs[i][2] if i < len(first_runs) else "end"
            label = f"{before[2] if before else 'start'}>{after}"
        gaps.setdefault(label, []).append((s1 - e0) * 1e-9)
    idle = sorted(((f"{label}[n={len(v)},max={max(v):.6f}]", sum(v))
                   for label, v in gaps.items()), key=lambda kv: -kv[1])
    return {
        "busy_s": sum(busy) / n,
        "window_s": (hi - lo) * 1e-9,
        "chips": n,
        "programs": progs,
        "device_ops": [[k, v / n] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v] for k, v in idle[:top]],
    }


def reduce_trace(trace_dir: str, top: int = 10, programs=()) -> dict:
    import jax

    return reduce_profile(
        jax.profiler.ProfileData.from_file(find_trace(trace_dir)), top,
        programs)
