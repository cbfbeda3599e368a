"""One timeline of a traced run: the program's own step and request
records (`deeplearning4j_tpu.observability.perf.get_timeline()`) laid
over the device trace under `benchmark.run.TRACE_DIR`.

The records are picked by step number: the serving window is the
`facts["delta"]["steps"]` engine steps after `mix["warmup_steps"]`, the
traced slice the `trace_steps` that end `trace_settle_steps` before it
(`drivers/serve.py`); the training window is the last `facts["steps"]`
steps of the profiler the driver attaches, the traced slice the steps
just before it. The device's side is read from the `.xplane.pb` itself:
the scope a `jax.named_scope` gave an operation sits in the `tf_op` stat
of the operation's *metadata* on the `XLA Ops` line, which
`jax.profiler.ProfileData` does not hand out (it gives an event's own
stats only), so `read_trace` walks the file's wire format.

`analysis(facts)` is what the readers under `layer_metrics/` share: it
runs once a traced run (kept in `facts`), logs two tables to standard
error (idle gaps by host phase, device time by scope) and returns the
numbers. On a program that has no timeline or no scopes (the parent of
the PR that brought them) it returns what it can and the readers
return None.
"""

from __future__ import annotations

import re
import struct

import numpy as np

from benchmark import xplane

STEP_OWNERS = {"serve": "decode/", "train": "train"}
ANCHOR = {"serve": "fetch", "train": "host_sync"}
JOIN_SPREAD_NS = 0.5e6   # wider, and host and device are not one clock
UNSCOPED = "(unscoped)"
# the scopes of the decode programs (engine/decode_program.py,
# nn/attention.py) and the kinds of the train step's (nn/graph.py)
SERVE_SCOPES = ("embed", "qkv", "kv_write", "kv_read", "attn", "mlp",
                "head", "kv_copy")
TRAIN_KINDS = ("conv", "dense", "bn", "act", "add", "pool", "other")
TRAIN_SCOPES = ("loss", "updater")
MATMUL_KINDS = ("conv", "dense")


# ------------------------------------------------- the trace's wire format
def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: ints for varints,
    memoryviews for length-delimited and fixed fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield field, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, names):
    """(name, value) of one XStat; `ref_value` resolved through the
    plane's stat names (the profiler interns repeated strings)."""
    name, val = None, None
    for f, v in _fields(buf):
        if f == 1:
            name = names.get(v)
        elif f == 2:
            val = struct.unpack("<d", v)[0]
        elif f == 3:
            val = v
        elif f == 4:
            val = _signed(v)
        elif f in (5, 6):
            val = bytes(v).decode("utf-8", "replace")
        elif f == 7:
            val = names.get(v, "")
    return name, val


def _map_entries(plane, field):
    for f, v in plane:
        if f == field:
            entry = dict(_fields(v))
            yield entry.get(1, 0), entry.get(2, b"")


def read_trace(path: str) -> dict:
    """The first device plane of an `.xplane.pb`, in nanoseconds of the
    trace's own clock (from `profile_start_ns`, Unix time, where the
    file gives it):

        modules  [(start, end, program)]            `XLA Modules`, in order
        ops      [(start, end, tf_op or "", name)]  `XLA Ops`, in order
    """
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {"profile_start_ns": None, "modules": [], "ops": []}
    found = False
    for f, raw in _fields(space):
        if f != 1:
            continue
        plane = list(_fields(raw))
        name = next((bytes(v).decode() for pf, v in plane if pf == 2), "")
        stat_names = {}
        for key, val in _map_entries(plane, 5):
            stat_names[key] = next(
                (bytes(v).decode() for sf, v in _fields(val) if sf == 2),
                "")
        if name == "Task Environment":
            for pf, v in plane:
                if pf == 6:
                    key, val = _stat(v, stat_names)
                    if key == "profile_start_time":
                        out["profile_start_ns"] = int(val)
        if found or not xplane.DEVICE_PLANE.match(name):
            continue
        found = True
        meta = {}           # metadata id -> (name, tf_op)
        for key, val in _map_entries(plane, 4):
            ev_name, tf_op = "", ""
            for mf, v in _fields(val):
                if mf == 2:
                    ev_name = bytes(v).decode("utf-8", "replace")
                elif mf == 5:
                    sk, sv = _stat(v, stat_names)
                    if sk == "tf_op":
                        tf_op = sv or ""
            meta[key] = (ev_name, tf_op)
        for pf, raw_line in plane:
            if pf != 3:
                continue
            line = list(_fields(raw_line))
            lname = next((bytes(v).decode() for lf, v in line if lf == 2),
                         "")
            if lname not in (xplane.OPS_LINE, xplane.MODULES_LINE):
                continue
            t_line = next((_signed(v) for lf, v in line if lf == 3), 0)
            for lf, raw_ev in line:
                if lf != 4:
                    continue
                ev = dict(_fields(raw_ev))
                start = t_line + _signed(ev.get(2, 0)) / 1e3
                end = start + _signed(ev.get(3, 0)) / 1e3
                ev_name, tf_op = meta.get(ev.get(1, 0), ("", ""))
                if lname == xplane.MODULES_LINE:
                    out["modules"].append(
                        (start, end, xplane.program_name(ev_name)))
                else:
                    out["ops"].append((start, end, tf_op, ev_name))
    out["modules"].sort()
    out["ops"].sort()
    return out


# ------------------------------------------------------- the device's side
def scope_of(tf_op: str) -> str:
    """`jit(step_fn)/transpose(jvp(conv/s2b0_a_conv))/mul` ->
    `conv/s2b0_a_conv`; `jit(decode_fn)/kv_read/gather` -> `kv_read`;
    an operation under none of the program's scopes -> `(unscoped)`."""
    tokens = [t for t in re.split(r"[/()]", tf_op) if t]
    scope = UNSCOPED
    for i, tok in enumerate(tokens):        # the innermost scope wins
        if tok in SERVE_SCOPES or tok in TRAIN_SCOPES:
            scope = tok
        elif tok in TRAIN_KINDS and i + 1 < len(tokens):
            scope = f"{tok}/{tokens[i + 1]}"
    return scope


def device_by_scope(trace: dict) -> dict:
    """{program: {"n", "seconds", "scopes": {scope: seconds}}}: the
    operations of each execution summed by scope. An operation belongs
    to the execution it starts in."""
    modules = trace["modules"]
    starts = [m[0] for m in modules]
    out = {}
    for start, end, prog in modules:
        p = out.setdefault(prog, {"n": 0, "seconds": 0.0, "scopes": {}})
        p["n"] += 1
        p["seconds"] += (end - start) * 1e-9
    for start, end, tf_op, _ in trace["ops"]:
        i = int(np.searchsorted(starts, start, side="right")) - 1
        if i < 0 or start > modules[i][1]:
            continue
        scopes = out[modules[i][2]]["scopes"]
        scope = scope_of(tf_op)
        scopes[scope] = scopes.get(scope, 0.0) + (end - start) * 1e-9
    return out


def idle_gaps(trace: dict, programs) -> list:
    """[(start, end)] of the device's idle time between the first start
    and the last end of the executions of `programs`: the complement of
    the union of the operations' intervals, as `xplane.reduce_profile`
    counts busy time."""
    mine = [m for m in trace["modules"] if m[2] in programs]
    if not mine:
        return []
    lo, hi = mine[0][0], max(m[1] for m in mine)
    merged = [(max(s, lo), min(e, hi)) for s, e in xplane._union(
        (o[0], o[1]) for o in trace["ops"]) if e > lo and s < hi]
    edges = [(lo, lo)] + merged + [(hi, hi)]
    return [(e0, s1) for (_, e0), (s1, _) in zip(edges, edges[1:])
            if s1 > e0]


# -------------------------------------------------------- the host's side
def phase_spans(record):
    """[(phase, start, end)] of one step record, seconds."""
    from deeplearning4j_tpu.observability.perf import phase_spans as spans

    return spans(record[3], record[4])


def phase_seconds(record) -> dict:
    out = {}
    for name, t0, t1 in phase_spans(record):
        out[name] = out.get(name, 0.0) + (t1 - t0)
    return out


def split_records(plane: str):
    """(step records, request records) of the plane's owner, or
    (None, None) where the program keeps no timeline."""
    from deeplearning4j_tpu.observability import perf

    if not hasattr(perf, "get_timeline"):
        return None, None
    mine = [r for r in list(perf.get_timeline())
            if str(r[0]).startswith(STEP_OWNERS[plane])]
    return ([r for r in mine if r[1] != "request"],
            [r for r in mine if r[1] == "request"])


def pick_steps(steps, first: int, last: int):
    """The records of steps first..last, the newest where a number
    repeats (a second engine or fit in the same process)."""
    by_n = {}
    for r in steps:
        if isinstance(r[1], int) and first <= r[1] <= last:
            by_n[r[1]] = r
    return [by_n[n] for n in sorted(by_n)]


def log_phases(secs, log) -> None:
    """The table of host phases over a window's steps."""
    log(f"host phases over the window's {len(secs)} steps, ms a step "
        "(mean, most):")
    names = {k for s in secs for k in s}
    for k in sorted(names, key=lambda k: -sum(s.get(k, 0.0) for s in secs)):
        v = [s.get(k, 0.0) * 1e3 for s in secs]
        log(f"    {k:14s} {np.mean(v):10.4f} {max(v):10.4f}")


def serve_numbers(facts, steps, requests, log) -> dict:
    """What the serving readers return, from the window's records."""
    warm = int(facts["mix"]["warmup_steps"])
    n = int(facts["delta"]["steps"])
    window = pick_steps(steps, warm + 1, warm + n)
    out = {"window": window}
    if len(window) < 2:
        return out
    secs = [phase_seconds(r) for r in window]
    # inside `step_once` and not blocked on the device
    inside = [sum(v for k, v in s.items()
                  if k not in ("between_steps", "fetch")) for s in secs]
    out["engine_host_ms"] = float(np.mean(inside)) * 1e3
    # the caller's turn before the window's first step began before the
    # window opened (in a traced run it holds the wait for the profiler
    # to write its trace out): the turns that began in the window count
    out["between_steps_ms"] = float(np.mean(
        [s.get("between_steps", 0.0) for s in secs[1:]])) * 1e3
    log_phases([{k: v for k, v in secs[0].items()
                 if k != "between_steps"}] + secs[1:], log)
    chunks = int(facts["delta"].get("prefill_chunks", 0))
    if chunks:
        # the window's chunks are dispatched inside `admit`, one a step
        log(f"    a chunk's dispatch is at most `admit`: "
            f"{sum(s.get('admit', 0.0) for s in secs) / chunks * 1e3:.3f} "
            f"ms a chunk over {chunks} chunks")

    # harvest to harvest, both in the window: what a streaming client
    # sees between two tokens
    at = {r[1]: t0 for r in window
          for name, t0, _ in phase_spans(r) if name == "harvest"}
    gaps = [(at[k] - at[k - 1], k) for k in sorted(at) if k - 1 in at]
    if len(gaps) >= 2:
        out["step_interval_p99_ms"] = float(np.percentile(
            [g for g, _ in gaps], 99)) * 1e3
        worst, k = max(gaps)
        held = _held_by(steps, k, at[k - 1], at[k], secs)
        log(f"step interval: p99 {out['step_interval_p99_ms']:.2f} ms, "
            f"median {np.median([g for g, _ in gaps]) * 1e3:.2f}, most "
            f"{worst * 1e3:.2f} ending in step {k}, held by {held}")

    mine = [r for r in requests if warm <= r[2] < warm + n]
    if mine:
        wait = [(r[4] - r[3]) * 1e3 for r in mine]
        fill = [(r[5] - r[4]) * 1e3 for r in mine]
        out["queue_wait_p95_ms"] = float(np.percentile(wait, 95))
        log(f"{len(mine)} requests submitted in the window: wait for "
            f"placement p95 {out['queue_wait_p95_ms']:.1f} ms (median "
            f"{np.median(wait):.1f}), placement to first token p95 "
            f"{np.percentile(fill, 95):.1f} ms (median "
            f"{np.median(fill):.1f})")
    return out


def _held_by(steps, k: int, t_from: float, t_to: float, secs) -> str:
    """The phase that ran longest over its window median in the
    interval that ends at step k's harvest."""
    median = {}
    for name in {n for s in secs for n in s}:
        median[name] = float(np.median([s.get(name, 0.0) for s in secs]))
    over = {}
    for r in pick_steps(steps, k - 1, k):
        for name, t0, t1 in phase_spans(r):
            d = min(t1, t_to) - max(t0, t_from)
            if d > 0:
                over[name] = over.get(name, 0.0) + d
    if not over:
        return "no record"
    name = max(over, key=lambda n: over[n] - median.get(n, 0.0))
    return (f"{name} ({over[name] * 1e3:.2f} ms against a median of "
            f"{median.get(name, 0.0) * 1e3:.2f})")


# ------------------------------------------------------------- the join
def join(facts, plane: str, trace: dict, steps, log) -> dict:
    """Align the traced slice's records with the trace and put its idle
    gaps down to host phases. Returns the alignment (`clock`) and, where
    it holds, `gap_phases`."""
    from deeplearning4j_tpu.observability import tracing

    programs = set(facts["config"]["programs"].values())
    anchor_prog = facts["config"]["programs"][
        "decode_step" if plane == "serve" else "train_step"]
    runs = [(m[0], m[1]) for m in trace["modules"] if m[2] == anchor_prog]
    if plane == "serve":
        stop = int(facts["mix"]["warmup_steps"]) - int(
            facts["cell"].get("trace_settle_steps", 0))
        sliced = pick_steps(steps, stop - len(runs) + 1, stop)
    else:
        last = max(r[1] for r in steps) - int(facts["steps"])
        sliced = pick_steps(steps, last - len(runs) + 1, last)
    clock = tracing.clock_offset(sliced, runs, ANCHOR[plane])
    out = {"clock": clock, "slice": sliced}
    if not clock["n"]:
        log(f"timeline: {len(runs)} executions of {anchor_prog} in the "
            f"trace, {len(sliced)} records of their steps: no join")
        return out
    from deeplearning4j_tpu.observability.perf import perf_to_unix_ns

    stamp = ""
    if trace["profile_start_ns"] is not None:
        # what the two stamps alone would have said, beside the measure
        by_stamp = trace["profile_start_ns"] - perf_to_unix_ns(0.0)
        stamp = (f"; by the records' Unix stamp and the trace's "
                 f"`profile_start_time` alone {by_stamp * 1e-6:.3f} ms, "
                 f"{(clock['offset_ns'] - by_stamp) * 1e-6:+.3f} ms off")
    log(f"clock: `{ANCHOR[plane]}` end less `{anchor_prog}` end over "
        f"{clock['n']} steps: median {clock['offset_ns'] * 1e-6:.3f} ms, "
        f"spread {clock['spread_ns'] * 1e-6:.4f} ms" + stamp)
    if clock["spread_ns"] > JOIN_SPREAD_NS:
        log(f"timeline: spread over {JOIN_SPREAD_NS * 1e-6} ms: the host "
            f"phase `{ANCHOR[plane]}` does not end with the device's "
            "program here, so idle gaps are not put down to phases")
        return out
    gaps = idle_gaps(trace, programs)
    # every record the slice could overlap: a gap past the last traced
    # step's end lies in the next step's `between_steps`
    around = pick_steps(steps, sliced[0][1] - 1, sliced[-1][1] + 1)
    by_phase = tracing.phases_over(around, gaps, clock["offset_ns"])
    total = sum(e - s for s, e in gaps) * 1e-9
    named = sum(v for k, v in by_phase.items() if k != "(no record)")
    out.update(gap_phases=by_phase, idle_s=total,
               named_share=named / total if total else 1.0)
    log(f"idle gaps of the traced slice by host phase ({len(gaps)} gaps, "
        f"{total * 1e3:.3f} ms, {100 * out['named_share']:.1f}% under "
        "named phases), ms:")
    for k, v in sorted(by_phase.items(), key=lambda kv: -kv[1]):
        log(f"    {k:14s} {v * 1e3:10.4f}")
    after = list(_gaps_after(trace, anchor_prog))
    if after:
        out["gap_after_ms"] = float(np.mean(after)) * 1e-6
    return out


def _gaps_after(trace: dict, program: str):
    """Idle nanoseconds from each execution of `program` to the next
    execution of any program."""
    mods = trace["modules"]
    for (_, e0, p0), (s1, _, _) in zip(mods, mods[1:]):
        if p0 == program:
            yield max(0.0, s1 - e0)


def analysis(facts) -> dict:
    """The traced run's timeline, computed and logged once a run."""
    if "timeline" in facts:
        return facts["timeline"]
    from benchmark.run import TRACE_DIR, log

    plane = facts["config"]["driver"]
    out = facts["timeline"] = {"plane": plane}
    trace = read_trace(xplane.find_trace(TRACE_DIR))
    by_prog = out["device"] = device_by_scope(trace)
    log("device time by scope, ms an execution (share of the program's "
        "device time):")
    for prog in sorted(by_prog, key=lambda p: -by_prog[p]["seconds"]):
        p = by_prog[prog]
        if prog not in facts["config"]["programs"].values():
            continue
        kinds = {}
        for scope, s in p["scopes"].items():
            kind = scope.split("/")[0]
            kinds[kind] = kinds.get(kind, 0.0) + s
        log(f"  {prog}: {p['n']} executions, "
            f"{p['seconds'] / p['n'] * 1e3:.3f} ms each, operations "
            f"{sum(kinds.values()) / p['n'] * 1e3:.3f}")
        for kind, s in sorted(kinds.items(), key=lambda kv: -kv[1]):
            log(f"    {kind:12s} {s / p['n'] * 1e3:10.4f} "
                f"({100 * s / p['seconds']:.1f}%)")
        top = sorted(((s, k) for k, s in p["scopes"].items() if "/" in k),
                     reverse=True)[:8]
        if top:
            log("    longest vertices: " + ", ".join(
                f"{k} {s / p['n'] * 1e3:.3f}" for s, k in top))
    steps, requests = split_records(plane)
    if not steps:
        log("timeline: the program keeps no step records; the readers "
            "that need them return nothing")
        return out
    if plane == "serve":
        out.update(serve_numbers(facts, steps, requests, log))
    else:
        last = max(r[1] for r in steps)
        log_phases([phase_seconds(r) for r in pick_steps(
            steps, last - int(facts["steps"]) + 1, last)], log)
    out.update(join(facts, plane, trace, steps, log))
    if plane == "serve" and "gap_after_ms" in out \
            and "engine_host_ms" in out:
        host = out["engine_host_ms"] + out["between_steps_ms"]
        log(f"host outside `fetch`, a step: {host:.3f} ms over the "
            f"window; the device's idle time after a decode step in the "
            f"traced slice: {out['gap_after_ms']:.3f} ms")
    return out


def scope_ms(facts, program_key: str, keep) -> float | None:
    """Device ms an execution of the configuration's program
    `program_key` under the scopes that `keep(scope)` picks; None where
    no operation of the program carries a scope (a program from before
    the scopes, or an executable cached from then)."""
    prog = analysis(facts)["device"].get(
        facts["config"]["programs"][program_key])
    if not prog or not prog["n"]:
        return None
    if not any(s != UNSCOPED for s in prog["scopes"]):
        return None
    return sum(v for k, v in prog["scopes"].items() if keep(k)) \
        / prog["n"] * 1e3
