"""Performance introspection: step phase attribution, the step
timeline, cross-rank metric aggregation.

Three instruments, all host-side bookkeeping:

  StepPhaseProfiler    the one step-phase recorder of both planes:
                       decomposes every step into named phases from
                       perf_counter marks. A fit loop's phases
                       (data_wait / h2d / dispatch / device_compute /
                       host_sync / checkpoint / telemetry) land as
                       `dl4j_train_phase_seconds{phase=...}` through
                       the loop's StepAccumulator; `DecodeEngine`
                       always owns one for `step_once` (no registry
                       write, totals in `stats()["phases"]`). Every
                       step also leaves one record in the process's
                       step timeline (`get_timeline()`), which
                       `observability.tracing.clock_offset` lays over
                       a device trace.
  recompile forensics  lives in nn/jit_cache.py (signature + duration
                       ring per new trace, `dl4j_jit_compiles_total`).
  aggregate_snapshots  rank-0 pull path: merge per-rank
                       MetricsRegistry snapshot dumps (written by
                       `dump_snapshot`, e.g. from distributed_worker
                       at exit) into ONE fleet-level snapshot —
                       counters summed, histogram buckets merged,
                       gauges re-keyed per rank — rendered through the
                       same `render_prometheus` as a single /metrics
                       body.

Operation counts, device peaks and utilization are the benchmark's
(`benchmark/roofline.py`, `benchmark/layer_metrics/`), not the
package's.

No jax import at module scope, so the aggregation path stays usable in
no-jax drills (cluster supervisor, tier-1 tests).
"""

from __future__ import annotations

import json
import os
import time
from collections import defaultdict, deque
from typing import Dict, List, Optional, Tuple

from deeplearning4j_tpu.observability import metrics as _obs
from deeplearning4j_tpu.observability.metrics import render_prometheus

# ------------------------------------------------ step phase profiler
PHASES = ("data_wait", "h2d", "dispatch", "device_compute",
          "host_sync", "checkpoint", "telemetry")
# pre-resolved accumulator keys: the per-step emission fast path pays
# a dict lookup per phase, not a label-dict build + sort per phase
_PHASE_KEYS = {p: ("dl4j_train_phase_seconds", (("phase", p),))
               for p in PHASES}

# The process's step timeline: the last steps of every profiler, and
# the engine's request records, in the order they ended. Held at module
# level (as `metrics.get_registry()` holds the registry) so that a
# reader reaches it after the engine or the fit loop that wrote it is
# gone. Two kinds of record, both plain tuples of perf_counter seconds:
#   (owner, step, t_begin, marks, t_end, work)
#       marks: ((phase, t_start), ..); work: what the step's call
#       dispatched to the device (`WORK_FIELDS`), None for a fit step
#       and for any caller of `end_step` that passes none
#   (owner, "request", step at submit, t_submit, t_placed, t_first_token,
#    prompt tokens, pages the trie mapped at its placements, chunks
#    dispatched for it)     the last three None where a caller of
#       `record_request` passes none
# The ring holds a run: a serving window of 51 s at a cycle of 3 ms is
# 17,000 step records and some 4,000 request records, with the warm-up
# and the traced slice before them. A step record with the engine's ten
# marks and its `work` is 1.2 KB, so the full ring is 40 MB of host
# memory (PERF.md, Findings of PR 38). A record is tuples of numbers and
# strings all the way down, so the collector lets go of it at its first
# pass: with the marks in a list a full collection walked every record
# of the ring (8 ms at 30,000 records, a stalled step). What the ring
# does push out it counts: `timeline_dropped()`.
TIMELINE_CAPACITY = 32768
_TIMELINE: deque = deque(maxlen=TIMELINE_CAPACITY)
_DROPPED = [0]      # records the ring has pushed out, this process
# The `work` of an engine step record, `DecodeEngine.step_once`'s: ints
# and two tuples, ready-made when it reaches `end_step`.
#   ahead       1 where a decode step was in flight when this call
#               dispatched its own: only then does the device run them
#               back to back
#   width       window width in pages of the decode step dispatched,
#               0 where the call dispatched none (a drain)
#   rows        rows decoding in it
#   live_pages  pages it gathers that hold a live cell
#   copies      copy-on-write page copies dispatched
#   chunks      one (width of the prior pages' window, pages filled,
#               tokens run) a prefill chunk dispatched, in order
#   earlier     the same seven fields (their own `earlier` empty) of
#               each call since the last record that dispatched
#               something and left no record, having harvested nothing:
#               chunks before any row decodes, the first dispatch after
#               a drain
# A call's record carries the step it HARVESTED (n) and the work it
# DISPATCHED (chunks, copies, step n+1). On the device that work runs
# between the end of step n and the end of step n+1: from this record's
# `harvest` mark to the next record's. What `earlier` holds ran before
# this record's `harvest` mark, after the record before's.
WORK_FIELDS = ("ahead", "width", "rows", "live_pages", "copies", "chunks",
               "earlier")
# both host clocks read back to back, once: what converts a record's
# perf_counter seconds to the Unix time a device trace's
# `profile_start_time` is in
CLOCK_ANCHOR = (time.perf_counter_ns(), time.time_ns())


def get_timeline() -> deque:
    """The bounded ring of step and request records (newest last)."""
    return _TIMELINE


def timeline_dropped() -> int:
    """Records the ring has pushed out since the process began: a
    reader that wants a whole run checks that this reads 0."""
    return _DROPPED[0]


def _push(record: tuple) -> None:
    # a full `deque(maxlen=)` drops its oldest in silence: count first
    if len(_TIMELINE) == _TIMELINE.maxlen:
        _DROPPED[0] += 1
    _TIMELINE.append(record)


def perf_to_unix_ns(t_perf_s: float) -> int:
    """A record's perf_counter seconds as Unix nanoseconds."""
    return int(t_perf_s * 1e9) - CLOCK_ANCHOR[0] + CLOCK_ANCHOR[1]


def phase_spans(marks, t_end: float):
    """[(phase, start, end)] of one step's marks: a phase runs from its
    mark to the next mark, the last to the step's end."""
    return [(ph, t, marks[i + 1][1] if i + 1 < len(marks) else t_end)
            for i, (ph, t) in enumerate(marks)]


def record_request(owner: str, step_at_submit: int, t_submit: float,
                   t_placed: float, t_first_token: float,
                   prompt_tokens: Optional[int] = None,
                   pages_mapped: Optional[int] = None,
                   chunks: Optional[int] = None) -> None:
    """One record per request, written at its first token: its three
    clocks, and what the time to that token was made of."""
    _push((owner, "request", step_at_submit, t_submit, t_placed,
           t_first_token, prompt_tokens, pages_mapped, chunks))


class StepPhaseProfiler:
    """Attribute every step's wall time to named phases.

    The owning loop calls `begin_step()` once per step, `mark(p)` at
    each phase boundary (phase p runs from its mark to the next mark),
    optionally `sync(device_value)` right after dispatch — when this
    step samples a device sync (`sync_every` > 0), the blocked
    `block_until_ready` interval becomes the device_compute phase —
    and `end_step()` in its finally. Cumulative totals stay on the
    instance for `report()`, every step leaves one record in the
    process's timeline (`get_timeline()`), and with a tracer attached
    each phase records a `phase:<name>` span on the shared timeline.
    With `emit_metrics` (a fit loop's profiler) durations also land as
    `dl4j_train_phase_seconds{phase=...}` through the loop's
    StepAccumulator (container appends per step, one guarded registry
    write per flush — the PR 5 <2% discipline); the decode engine's
    profiler writes nothing to the registry.

    NOT thread-safe — one owner loop per instance, like the
    accumulator it feeds."""

    def __init__(self, accumulator=None, tracer=None,
                 sync_every: int = 0, owner: str = "train",
                 emit_metrics: bool = True):
        self.accumulator = accumulator
        self.tracer = tracer
        # sync_every=N blocks on the device value every Nth step:
        # device_compute becomes visible at 1/N the host-sync cost;
        # un-synced steps leave device time inside dispatch. 0 (the
        # default) never syncs: a sync a step serializes host and
        # device (125 ms a ResNet50 step for 107.5, PERF.md)
        self.sync_every = max(0, int(sync_every))
        self.owner = owner
        self.emit_metrics = emit_metrics
        self.totals: Dict[str, float] = defaultdict(
            float, {p: 0.0 for p in PHASES} if emit_metrics else {})
        self.wall_s = 0.0
        self.steps = 0
        self._marks: List[Tuple[str, float]] = []
        self._t_begin: Optional[float] = None
        self._t_last_end: Optional[float] = None
        self._step = None

    def begin_step(self, step=None, since_last: str = "") -> None:
        """A step starts now. With `since_last`, it starts where this
        profiler's last step ended, and the time since is the phase of
        that name (the engine's `between_steps`: the caller's turn)."""
        if since_last and self._t_last_end is not None:
            self._t_begin = self._t_last_end
            self._marks = [(since_last, self._t_last_end)]
        else:
            self._t_begin = time.perf_counter()
            self._marks = []
        self._step = step

    def mark(self, phase: str) -> None:
        """Phase `phase` starts now (and the previous phase ends)."""
        self._marks.append((phase, time.perf_counter()))

    def should_sync(self, step=None) -> bool:
        if self.sync_every <= 0:
            return False
        s = self.steps if step is None else int(step)
        return s % self.sync_every == 0

    def sync(self, value, step=None) -> None:
        """Sampled device sync: on sampling steps, block until `value`
        is ready and attribute the blocked interval to device_compute.
        Swallows everything — profiling must never fail a step."""
        if value is None or not self.should_sync(step):
            return
        self.mark("device_compute")
        try:
            import jax

            jax.block_until_ready(value)
        except Exception:   # noqa: BLE001 - profiling is best-effort
            pass

    def end_step(self, step=None, work=None) -> None:
        """The step ends now. `step` names it where `begin_step` could
        not yet (the engine counts a step only once it has run);
        `work` is the record's last field as the caller made it
        (`WORK_FIELDS`)."""
        if self._t_begin is None:
            return
        t_end = time.perf_counter()
        marks = self._marks
        if step is None:
            step = self._step
        totals = self.totals
        if marks:
            each = iter(marks)
            prev, t_prev = next(each)
            for ph, t in each:
                totals[prev] += t - t_prev
                prev, t_prev = ph, t
            totals[prev] += t_end - t_prev
        _push((self.owner, step, self._t_begin, tuple(marks), t_end, work))
        if self.emit_metrics:
            self._emit(marks, t_end)
        tr = self.tracer
        if tr is not None:
            for ph, t, t_next in phase_spans(marks, t_end):
                tr.record(f"phase:{ph}", t, t_next, cat="phase",
                          args={"step": step})
        # the profiler's own emission cost is telemetry time too —
        # attribute it so coverage stays honest, not flattering
        t_done = time.perf_counter()
        totals["telemetry"] += t_done - t_end
        self.wall_s += t_done - self._t_begin
        self.steps += 1
        self._t_begin = None
        self._t_last_end = t_done
        self._marks = []

    def _emit(self, marks, t_end: float) -> None:
        """One observation per phase of the step (a phase marked twice
        counts once, with both intervals)."""
        durs: Dict[str, float] = {}
        for ph, t, t_next in phase_spans(marks, t_end):
            durs[ph] = durs.get(ph, 0.0) + max(0.0, t_next - t)
        acc = self.accumulator
        for ph, d in durs.items():
            key = _PHASE_KEYS.get(ph)
            if acc is not None and key is not None:
                acc.observe_keyed(key, d)
            else:
                _obs.observe("dl4j_train_phase_seconds", d,
                             labels={"phase": ph})

    def report(self) -> dict:
        """Cumulative per-phase seconds + shares and the coverage
        fraction (sum of attributed phase time / wall time of the
        profiled steps) — the ≥95% acceptance observable."""
        attributed = sum(self.totals.values())
        phases = {
            p: {"seconds": round(s, 6),
                "share": (s / attributed) if attributed else 0.0}
            for p, s in self.totals.items() if s > 0.0}
        return {
            "steps": self.steps,
            "wall_s": round(self.wall_s, 6),
            "attributed_s": round(attributed, 6),
            "coverage": (attributed / self.wall_s) if self.wall_s
            else 0.0,
            "phases": phases,
        }


# --------------------------------------------- cross-rank aggregation
def dump_snapshot(path: str, registry=None, rank: Optional[int] = None,
                  extra: Optional[dict] = None) -> str:
    """Write this process's MetricsRegistry snapshot to `path` (tmp +
    os.replace so a reader never sees a torn file) — the per-rank half
    of the rank-0 pull path. `distributed_worker` calls this at exit;
    `aggregate_snapshots` merges the files."""
    snap = (registry or _obs.get_registry()).snapshot()
    doc = {"rank": rank, "wall_time": time.time(), "snapshot": snap}
    if extra:
        doc.update(extra)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return path


def _load_snapshot(source, fallback_rank: int) -> Tuple[dict, int]:
    if isinstance(source, str):
        with open(source) as f:
            source = json.load(f)
    rank = fallback_rank
    snap = source
    if isinstance(source, dict) and "snapshot" in source:
        if source.get("rank") is not None:
            rank = int(source["rank"])
        snap = source["snapshot"]
    return snap, rank


def _with_rank(label_str: str, rank: int) -> str:
    inner = f'rank="{rank}"'
    if not label_str:
        return "{" + inner + "}"
    return label_str[:-1] + "," + inner + "}"


def aggregate_snapshots(sources) -> dict:
    """Merge per-rank snapshot dumps (paths, dump_snapshot docs, or raw
    snapshot dicts) into ONE fleet-level snapshot: counters summed per
    (name, label set), histogram buckets/counts/sums merged (ring
    quantiles cannot merge exactly and are dropped), gauges re-keyed
    with a rank label so per-rank values stay distinguishable. The
    result renders through `render_prometheus` — the fleet /metrics
    body the cluster supervisor reports instead of rank-local
    numbers."""
    merged: dict = {"counters": {}, "gauges": {}, "histograms": {},
                    "ranks": 0, "uptime_s": 0.0}
    for i, source in enumerate(sources):
        snap, rank = _load_snapshot(source, i)
        for name, series in snap.get("counters", {}).items():
            tgt = merged["counters"].setdefault(name, {})
            for lab, v in series.items():
                tgt[lab] = tgt.get(lab, 0.0) + float(v)
        for name, series in snap.get("gauges", {}).items():
            tgt = merged["gauges"].setdefault(name, {})
            for lab, v in series.items():
                tgt[_with_rank(lab, rank)] = float(v)
        for name, h in snap.get("histograms", {}).items():
            tgt = merged["histograms"].setdefault(
                name, {"count": 0, "sum": 0.0, "buckets": {},
                       "p50": None, "p90": None, "p99": None})
            tgt["count"] += int(h.get("count", 0))
            tgt["sum"] = round(tgt["sum"] + float(h.get("sum", 0.0)), 9)
            for le, c in h.get("buckets", {}).items():
                tgt["buckets"][le] = tgt["buckets"].get(le, 0) + int(c)
        merged["ranks"] += 1
        merged["uptime_s"] = max(merged["uptime_s"],
                                 float(snap.get("uptime_s", 0.0)))
    return merged


def aggregate_prometheus_text(sources) -> str:
    """One fleet-level Prometheus exposition from per-rank snapshot
    files/dicts — `render_prometheus(aggregate_snapshots(...))`."""
    return render_prometheus(aggregate_snapshots(sources))


__all__ = [
    "PHASES", "StepPhaseProfiler", "TIMELINE_CAPACITY", "WORK_FIELDS",
    "get_timeline", "timeline_dropped", "perf_to_unix_ns", "phase_spans",
    "record_request",
    "dump_snapshot", "aggregate_snapshots", "aggregate_prometheus_text",
    "render_prometheus",
]
