"""Performance introspection: cost-model MFU accounting, step phase
attribution, cross-rank metric aggregation.

ROADMAP item 2 ("profile the step, then attack") needs the repo to
explain its own step time before anything cuts it. Four instruments,
all riding the PR 5 telemetry substrate:

  CostModel            per-compiled-program flops / bytes-accessed /
                       peak-memory from XLA cost analysis
                       (`lowered.compile().cost_analysis()`), with an
                       analytic fallback for backends that return
                       nothing. Yields exact MFU (measured step time x
                       program flops / device peak), arithmetic
                       intensity, and a roofline classification — the
                       flops/bytes accounting the TPP (arXiv
                       2104.05755) and weight-update-sharding (arXiv
                       2004.13336) work both lean on to decide WHERE
                       to optimize. `perf_report()` lands the numbers
                       as registry gauges and a dict.
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
                       ring per new trace, `dl4j_jit_compiles_total`);
                       `CostModel.register_jit_entry` attaches cost
                       digests to the ring.
  aggregate_snapshots  rank-0 pull path: merge per-rank
                       MetricsRegistry snapshot dumps (written by
                       `dump_snapshot`, e.g. from distributed_worker
                       at exit) into ONE fleet-level snapshot —
                       counters summed, histogram buckets merged,
                       gauges re-keyed per rank — rendered through the
                       same `render_prometheus` as a single /metrics
                       body.

Everything here is host-side bookkeeping: no jax import at module
scope, so the aggregation path stays usable in no-jax drills
(cluster supervisor, tier-1 tests).
"""

from __future__ import annotations

import json
import os
import time
from collections import defaultdict, deque
from typing import Dict, List, Optional, Tuple

from deeplearning4j_tpu.observability import metrics as _obs
from deeplearning4j_tpu.observability.metrics import render_prometheus

# Per-chip peaks, keyed by `device_kind`: (bf16 FLOP/s, HBM bytes/s) —
# the two roofline axes. Sources: Google Cloud TPU documentation,
# "TPU v5e" (197 TFLOP/s bf16, 819 GB/s), "TPU v4" (275 TFLOP/s,
# 1228 GB/s), "TPU v3" (123 TFLOP/s, 900 GB/s). A device that is not
# in the table is an error, not a default: a utilization against a
# guessed peak is a made-up number.
PEAKS = {
    "TPU v5 lite": (197e12, 819e9),
    "TPU v4": (275e12, 1228e9),
    "TPU v3": (123e12, 900e9),
}


def device_peaks(device=None) -> Tuple[float, float, str]:
    """(peak_flops, peak_bytes_per_s, device_kind) for `device` (default
    jax.devices()[0]). Raises KeyError for a kind `PEAKS` does not
    list — the CPU among them."""
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = str(device.device_kind)
    if kind not in PEAKS:
        raise KeyError(
            f"no published peak for device kind {kind!r} (known: "
            f"{sorted(PEAKS)}); add it to observability.perf.PEAKS "
            "with its source")
    flops, bw = PEAKS[kind]
    return flops, bw, kind


# ------------------------------------------------ analytic flop counts
def matmul_flops(m: int, k: int, n: int) -> float:
    """[m,k] @ [k,n]: one multiply + one add per MAC."""
    return 2.0 * m * k * n


def conv2d_flops(batch: int, out_h: int, out_w: int, c_out: int,
                 kh: int, kw: int, c_in: int) -> float:
    """Direct convolution MACs x2 (XLA's accounting for VALID padding;
    SAME padding does fewer real MACs at the edges, which XLA also
    counts exactly — use this only as the fallback/cross-check)."""
    return 2.0 * batch * out_h * out_w * c_out * kh * kw * c_in


def train_step_flops_from_params(n_params: int, rows: int) -> float:
    """The classic 6NB estimate (2NB forward + 4NB backward) for a
    dense model with N params on a B-row batch — the coarse analytic
    fallback when XLA reports nothing and no exact count is known."""
    return 6.0 * float(n_params) * float(rows)


# ------------------------------------------------- XLA cost extraction
def extract_cost(target, *args, **kwargs) -> Optional[dict]:
    """Pull {flops, bytes_accessed, peak_bytes} from XLA cost analysis.

    `target` is either a `jax.jit`-wrapped callable — lowered and
    compiled here with the given example (or ShapeDtypeStruct) args —
    or an already-compiled jax.stages object (the AOT path benches use
    to avoid a duplicate compile). Returns None when `target` is
    neither, or when the compiler counted no flops (the
    analytic-fallback trigger)."""
    compiled = target
    if not hasattr(compiled, "cost_analysis"):
        if not hasattr(target, "lower"):
            return None
        compiled = target.lower(*args, **kwargs).compile()
    ca = compiled.cost_analysis()    # one dict per executable (jax 0.9)
    flops = float(ca.get("flops", 0.0))
    if flops <= 0.0:
        return None
    mem = compiled.memory_analysis()
    return {"flops": flops,
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "peak_bytes": int(mem.temp_size_in_bytes
                              + mem.argument_size_in_bytes
                              + mem.output_size_in_bytes)}


class CostModel:
    """Per-program flops/bytes registry + MFU / roofline arithmetic.

    Register each compiled program once (outside the timed region),
    then `perf_report(key, seconds_per_call=...)` turns a measured
    step time into MFU, arithmetic intensity, and a roofline verdict —
    and lands them as `dl4j_perf_*` registry gauges so the dashboard
    and /metrics see the same numbers the bench JSON records."""

    def __init__(self, peak_flops: Optional[float] = None,
                 peak_bytes_per_s: Optional[float] = None,
                 device=None):
        """Peaks come from `PEAKS` by the device's kind unless both are
        given. On the CPU platform there is no peak: flops and bytes
        are still counted, and `mfu`/`roofline` answer None — a CPU run
        reports no utilization rather than one against an invented
        peak. Any other device missing from the table raises."""
        if device is None:
            import jax

            device = jax.devices()[0]
        self.device_kind = str(device.device_kind)
        if peak_flops and peak_bytes_per_s:
            self.peak_flops = float(peak_flops)
            self.peak_bytes_per_s = float(peak_bytes_per_s)
        elif device.platform == "cpu":
            self.peak_flops = self.peak_bytes_per_s = None
        else:
            self.peak_flops, self.peak_bytes_per_s, _ = \
                device_peaks(device)
        self._entries: Dict[str, dict] = {}

    # ------------------------------------------------------- register
    def register_compiled(self, key, target, *args,
                          analytic_flops: Optional[float] = None,
                          analytic_bytes: Optional[float] = None,
                          **kwargs) -> dict:
        """XLA cost analysis first; `analytic_*` are the fallback for
        backends whose cost analysis returns nothing. Raises ValueError
        only when BOTH sources are empty."""
        entry = extract_cost(target, *args, **kwargs)
        if entry is not None:
            entry["source"] = "xla_cost_analysis"
        elif analytic_flops:
            entry = {"flops": float(analytic_flops),
                     "bytes_accessed": float(analytic_bytes or 0.0),
                     "peak_bytes": None, "source": "analytic"}
        else:
            raise ValueError(
                f"no cost available for {key!r}: XLA cost analysis "
                "returned nothing and no analytic fallback was given")
        self._entries[str(key)] = entry
        return dict(entry)

    def register_analytic(self, key, flops: float,
                          bytes_accessed: float = 0.0) -> dict:
        entry = {"flops": float(flops),
                 "bytes_accessed": float(bytes_accessed),
                 "peak_bytes": None, "source": "analytic"}
        self._entries[str(key)] = entry
        return dict(entry)

    def register_jit_entry(self, cache, key, *args,
                           analytic_flops: Optional[float] = None,
                           analytic_bytes: Optional[float] = None,
                           **kwargs) -> Optional[dict]:
        """Cost for a JitCache entry: unwraps the cache's forensics
        wrapper, extracts/falls back, and hands the digest back to the
        cache so its recompile ring carries it. Returns None (instead
        of raising) when no cost is available — serving warmup calls
        this opportunistically."""
        fn = cache.get(key)
        if fn is None:
            return None
        fn = getattr(fn, "__wrapped__", fn)
        try:
            entry = self.register_compiled(
                key, fn, *args, analytic_flops=analytic_flops,
                analytic_bytes=analytic_bytes, **kwargs)
        except ValueError:
            return None
        if hasattr(cache, "register_cost"):
            cache.register_cost(key, entry)
        return entry

    # ----------------------------------------------------------- reads
    def entry(self, key) -> Optional[dict]:
        e = self._entries.get(str(key))
        return dict(e) if e is not None else None

    def keys(self) -> List[str]:
        return list(self._entries)

    def arithmetic_intensity(self, key) -> Optional[float]:
        e = self._entries.get(str(key))
        if e is None or not e.get("bytes_accessed"):
            return None
        return e["flops"] / e["bytes_accessed"]

    def mfu(self, key, seconds_per_call: float) -> Optional[float]:
        """Model flops utilization: program flops / wall seconds /
        device peak. The honest headline — counts the flops the model
        NEEDS (as compiled), not the flops the kernel burned."""
        e = self._entries.get(str(key))
        if e is None or seconds_per_call <= 0.0 or not self.peak_flops:
            return None
        return e["flops"] / seconds_per_call / self.peak_flops

    def roofline(self, key) -> Optional[dict]:
        """Where this program sits on the roofline: arithmetic
        intensity vs the ridge point (peak_flops / peak_bw), plus the
        bandwidth-bound attainable flops ceiling."""
        ai = self.arithmetic_intensity(key)
        if ai is None or not self.peak_flops:
            return None
        ridge = self.peak_flops / self.peak_bytes_per_s
        return {
            "arithmetic_intensity": ai,
            "ridge_point": ridge,
            "bound": "compute" if ai >= ridge else "memory",
            "attainable_flops_per_s": min(
                self.peak_flops, ai * self.peak_bytes_per_s),
        }

    def perf_report(self, key, seconds_per_call: Optional[float] = None,
                    items_per_call: Optional[float] = None) -> dict:
        """One dict with everything ROADMAP item 2 needs to cite:
        flops, bytes, arithmetic intensity, roofline verdict, and (when
        a measured `seconds_per_call` is given) MFU + achieved
        flops/s. Also lands the numbers as `dl4j_perf_*` gauges."""
        e = self._entries.get(str(key))
        if e is None:
            raise KeyError(f"no cost registered for {key!r}")
        report = {
            "program": str(key),
            "source": e["source"],
            "flops": e["flops"],
            "bytes_accessed": e["bytes_accessed"],
            "peak_bytes": e.get("peak_bytes"),
            "device_kind": self.device_kind,
            "peak_flops": self.peak_flops,
            "peak_bytes_per_s": self.peak_bytes_per_s,
        }
        roof = self.roofline(key)
        if roof is not None:
            report.update(roof)
        if items_per_call:
            report["flops_per_item"] = e["flops"] / items_per_call
        if seconds_per_call:
            report["seconds_per_call"] = seconds_per_call
            report["achieved_flops_per_s"] = \
                e["flops"] / seconds_per_call
            report["mfu"] = self.mfu(key, seconds_per_call)
        labels = {"program": str(key)}
        _obs.set_gauge("dl4j_perf_program_flops", e["flops"],
                       labels=labels)
        _obs.set_gauge("dl4j_perf_program_bytes", e["bytes_accessed"],
                       labels=labels)
        if roof is not None:
            _obs.set_gauge("dl4j_perf_arithmetic_intensity",
                           roof["arithmetic_intensity"], labels=labels)
        if report.get("mfu") is not None:
            _obs.set_gauge("dl4j_perf_mfu", report["mfu"],
                           labels=labels)
        return report

    def digest(self, key) -> Optional[dict]:
        """Compact {flops, bytes, ai} for the JitCache forensics ring."""
        e = self._entries.get(str(key))
        if e is None:
            return None
        ai = self.arithmetic_intensity(key)
        return {"flops": e["flops"],
                "bytes_accessed": e["bytes_accessed"],
                "arithmetic_intensity":
                    round(ai, 3) if ai is not None else None}


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
#   (owner, step, t_begin, marks, t_end)   marks: [(phase, t_start), ..]
#   (owner, "request", step at submit, t_submit, t_placed, t_first_token)
TIMELINE_CAPACITY = 4096
_TIMELINE: deque = deque(maxlen=TIMELINE_CAPACITY)
# both host clocks read back to back, once: what converts a record's
# perf_counter seconds to the Unix time a device trace's
# `profile_start_time` is in
CLOCK_ANCHOR = (time.perf_counter_ns(), time.time_ns())


def get_timeline() -> deque:
    """The bounded ring of step and request records (newest last)."""
    return _TIMELINE


def perf_to_unix_ns(t_perf_s: float) -> int:
    """A record's perf_counter seconds as Unix nanoseconds."""
    return int(t_perf_s * 1e9) - CLOCK_ANCHOR[0] + CLOCK_ANCHOR[1]


def phase_spans(marks, t_end: float):
    """[(phase, start, end)] of one step's marks: a phase runs from its
    mark to the next mark, the last to the step's end."""
    return [(ph, t, marks[i + 1][1] if i + 1 < len(marks) else t_end)
            for i, (ph, t) in enumerate(marks)]


def record_request(owner: str, step_at_submit: int, t_submit: float,
                   t_placed: float, t_first_token: float) -> None:
    """One record per request, written at its first token."""
    _TIMELINE.append((owner, "request", step_at_submit, t_submit,
                      t_placed, t_first_token))


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

    def end_step(self, step=None) -> None:
        """The step ends now. `step` names it where `begin_step` could
        not yet (the engine counts a step only once it has run)."""
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
        _TIMELINE.append((self.owner, step, self._t_begin, marks, t_end))
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

    def top_phases(self, n: int = 2) -> List[Tuple[str, float]]:
        """The n largest phases by share — the dashboard line's view."""
        attributed = sum(self.totals.values())
        if attributed <= 0.0:
            return []
        ranked = sorted(self.totals.items(), key=lambda kv: -kv[1])
        return [(p, s / attributed) for p, s in ranked[:n] if s > 0.0]


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
    body MULTICHIP benches and the cluster supervisor report instead
    of rank-local numbers."""
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
    "PEAKS", "PHASES",
    "CostModel", "StepPhaseProfiler", "TIMELINE_CAPACITY",
    "get_timeline", "perf_to_unix_ns", "phase_spans", "record_request",
    "device_peaks", "extract_cost",
    "matmul_flops", "conv2d_flops", "train_step_flops_from_params",
    "dump_snapshot", "aggregate_snapshots", "aggregate_prometheus_text",
    "render_prometheus",
]
