"""The window as the program's own records tell it, for the readers
under `layer_metrics/` that time it from the host's clock.

A step record carries the step its call harvested and, as `work`, what
the call dispatched (`observability.perf.WORK_FIELDS`); with a step
always in flight the device runs that work between this record's
`harvest` mark and the next record's (`DecodeEngine.step_once`). A
TURN is that interval with its `work`: `window_step_ms.serve` and
`window_chunk_ms.serve` are medians over kinds of turn. A request
record carries its three clocks and what the time to its first token
was made of: `fill_ms_per_chunk.serve`. On a program whose records
carry neither, every function here returns None."""

from collections import Counter

import numpy as np

from benchmark import timeline
from deeplearning4j_tpu.observability import perf

MIN_TURNS = 30      # fewer turns of a kind, and a median says little
# where a record's `work` keeps each field; None on a program without
WORK_FIELDS = getattr(perf, "WORK_FIELDS", None)
AHEAD, WIDTH, ROWS, LIVE, COPIES, CHUNKS, EARLIER = (
    WORK_FIELDS.index(f) for f in (
        "ahead", "width", "rows", "live_pages", "copies", "chunks",
        "earlier")) if WORK_FIELDS else (None,) * 7
# a request record's fields after its clocks
PROMPT_TOKENS, PAGES_MAPPED, REQUEST_CHUNKS = 6, 7, 8


def turns(facts):
    """The window's turns, computed and logged once a run: [(seconds
    from the record's `harvest` mark to the next record's, its `work`)]
    for every record of the window that ran ahead and whose successor,
    also in the window, carries nothing a call without a record
    dispatched in between. None where the records carry no `work`."""
    if "window_turns" in facts:
        return facts["window_turns"]
    from benchmark.run import log

    window = timeline.analysis(facts).get("window") or []
    out = facts["window_turns"] = None
    if WORK_FIELDS is None or not window \
            or any(len(r) < 6 or r[5] is None for r in window):
        return out
    at = [next((t for name, t in r[3] if name == "harvest"), None)
          for r in window]
    out = facts["window_turns"] = [
        (t1 - t0, a[5]) for a, b, t0, t1 in zip(window, window[1:], at,
                                                at[1:])
        if b[1] == a[1] + 1 and t0 is not None and t1 is not None
        and a[5][AHEAD] and not b[5][EARLIER]]
    log_sums(facts, window, log)
    log_kinds(out, log)
    return out


def log_sums(facts, window, log) -> None:
    """What the window's records say their calls dispatched, beside the
    window's counters."""
    calls = [w for r in window for w in (*r[5][EARLIER], r[5])]
    by_width = Counter(w[WIDTH] for w in calls if w[WIDTH])
    d = facts["delta"]
    log(f"the work of the window's {len(window)} records: chunks "
        f"{sum(len(w[CHUNKS]) for w in calls)} (counted "
        f"{d.get('prefill_chunks')}), copies "
        f"{sum(w[COPIES] for w in calls)} ({d.get('cow_copies')}), live "
        f"pages {sum(w[LIVE] for w in calls)} ({d.get('kv_pages_live')}), "
        f"decode steps by width {dict(sorted(by_width.items()))} "
        f"({d.get('steps')} harvested), not ahead "
        f"{sum(1 for w in calls if w[WIDTH] and not w[AHEAD])}")


def log_kinds(found, log) -> None:
    kinds = {}
    for s, w in found:
        kinds.setdefault((w[WIDTH], len(w[CHUNKS]), w[COPIES]),
                         []).append((s, w[ROWS], w[LIVE]))
    log("device time of a turn by what its call dispatched, `harvest` to "
        "`harvest` (step width in pages, chunks, copies: turns, median "
        "ms, mean rows, mean live pages):")
    for kind in sorted(kinds, key=lambda k: -len(kinds[k])):
        s, rows, live = zip(*kinds[kind])
        log(f"    {kind[0]:4d} {kind[1]:2d} {kind[2]:2d}: {len(s):6d} "
            f"{np.median(s) * 1e3:9.4f} {np.mean(rows):8.2f} "
            f"{np.mean(live):9.1f}")


def alone(found, width):
    """Seconds of the turns whose call dispatched a decode step of
    `width` pages and nothing beside it."""
    return [s for s, w in found if w[WIDTH] == width and not w[CHUNKS]
            and not w[COPIES]]


def steady_width(found):
    """The widest step the window ran alone in `MIN_TURNS` turns or
    more, or None: where a window's contexts grow through a ladder of
    widths it is the one they settle at. No vote between widths: a
    stall that takes a second's turns from one of them cannot move
    it."""
    counts = Counter(w[WIDTH] for _, w in found
                     if w[WIDTH] and not w[CHUNKS] and not w[COPIES])
    return max((w for w, n in counts.items() if n >= MIN_TURNS),
               default=None)


def median_ms(seconds):
    """Their median in ms, None under `MIN_TURNS` of them."""
    if len(seconds) < MIN_TURNS:
        return None
    return float(np.median(seconds)) * 1e3


def requests(facts):
    """The request records submitted in the window, or None where they
    do not say what their first token waited for."""
    _, found = timeline.split_records("serve")
    warm = int(facts["mix"]["warmup_steps"])
    n = int(facts["delta"]["steps"])
    mine = [r for r in found or [] if warm <= r[2] < warm + n]
    if not mine or any(len(r) <= REQUEST_CHUNKS or r[REQUEST_CHUNKS] is None
                       for r in mine):
        return None
    return mine
