"""The serving run and the study of `correct`'s limits for an engine
whose model keeps a per-slot state beside its page pool, over any such
model: the caller gives `build` (the model and its programs from the
configuration) and `open_engine` (the seed's weights, a fresh pool and
state, every program compiled), and the rest is the same `DecodeEngine`,
clients, window and sample as `drivers/serve.py` (`drive`,
`window_metrics`, `served_sample`), the gap numbers of
`drivers/serve_latent.py` and the reference a row at a time and the
engine's close of `drivers/serve_hybrid.py`.

A `witness` may read what the tokens cannot: called after the window,
while the engine still holds its programs and its pool, with the
sample the run compares, it returns `numbers(w)`, which the comparison
calls once the engine is closed and adds to the gaps' numbers (in a
study, one dict of numbers a side).
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from benchmark.drivers.serve import (
    COUNTERS as ENGINE_COUNTERS,
    drive,
    mean_context,
    mix_width,
    served_sample,
    window_metrics,
)
from benchmark.drivers.serve_hybrid import STATE_COUNTERS, _gaps, close_engine
from benchmark.drivers.serve_latent import gap_numbers


def run(ctx, build, open_engine, witness=None):
    cfg, mix = ctx.config, ctx.mix
    # the harness's timeline keys its planes by the driver's name
    # (`timeline.analysis`): this driver writes the serving plane's
    # records, the engine's own
    cfg["driver"] = "serve"
    prog = build(ctx)
    ctx.mark("model built")
    w, eng = open_engine(ctx, prog, ctx.seed)
    ctx.mark("weights made, page pool and state filled, programs compiled "
             "or loaded")
    win = drive(ctx, eng, ctx.seed, ctx.seconds, ctx.trace)
    setup_s = win.t0 - ctx.t_start
    t0, t1, s0, s1 = win.t0, win.t1, win.s0, win.s1
    window_s = t1 - t0
    ttft, tpot, attempted, failed, finished = window_metrics(
        win.rows, t0, t1, win.t_end)
    # the engine's counters, its state's, and the expert layer's, which
    # its stats carry under the model's own names
    delta = {k: s1[k] - s0[k] for k in ENGINE_COUNTERS + STATE_COUNTERS
             + tuple(prog.model.step_counters)}
    by_width = {w_: n - s0["dispatches"]["step_by_width"][w_] for w_, n in
                s1["dispatches"]["step_by_width"].items()}
    ctx.log(f"window: {window_s:.3f} s, {delta}, {len(ttft)} TTFT and "
            f"{len(tpot)} TPOT samples, {attempted} sent, {failed} failed; "
            f"first tokens waited for {win.t_end - t1:.1f} s past it; "
            f"prompts filled {s1['prefills'] - s0['prefills']}, steps by "
            f"window width in pages {by_width}, state {s1['state_bytes']} "
            f"bytes, prefix cache {'on' if s1['prefix_cache'] else 'off'}")
    ctx.log("TTFT ms, slowest first: "
            + " ".join(f"{v:.0f}" for v in sorted(ttft, reverse=True)))
    ctx.log("TPOT ms, slowest first: "
            + " ".join(f"{v:.1f}" for v in sorted(tpot, reverse=True)))
    facts = {
        "window_s": window_s, "delta": delta,
        "max_slots": eng.max_slots, "ttft_samples": len(ttft),
        "tpot_samples": len(tpot),
        "compiles_in_window": sum(s1["trace_counts"].values())
        - sum(s0["trace_counts"].values()),
        "mean_context": mean_context(win.rows, t0, t1),
    }
    e2e = {"decode_tok_per_s": delta["tokens_total"] / window_s,
           "ttft_p95_ms": float(np.percentile(ttft, 95)) if ttft
           else float("nan"),
           "tpot_p95_ms": float(np.percentile(tpot, 95)) if tpot
           else float("nan"),
           "setup_s": setup_s}
    sample = served_sample(finished, ctx.seed, mix_width(mix),
                           int(ctx.cell["sample_rows"])) if finished \
        else None
    later = witness(ctx, w, eng, *sample) \
        if witness is not None and sample is not None else None
    state = {"eng": eng}

    def check():
        if sample is None:
            return {}
        tokens, served = sample
        gaps = _gaps(ctx)(w, tokens)[served]
        ctx.log(f"reference over {gaps.size} served tokens of "
                f"{len(tokens)} requests: gap mean {np.mean(gaps):.6f}, "
                f"99th percentile {np.percentile(gaps, 99):.6f}")
        return {**gap_numbers(gaps), **(later(w) if later else {})}

    return SimpleNamespace(attempted=attempted, failed=failed, facts=facts,
                           free=lambda: close_engine(state.pop("eng")),
                           check=check, end_to_end=e2e)


def study(ctx, seeds, build, open_engine, controls, witness=None):
    """For each seed a window at the cell's own load, then over the
    sample a run would compare, the numbers `correct` holds and the
    harness's own verdict on them for the program, each of `controls`
    (side name -> the reference's `control`: the reference in the
    program's place at another precision) and the altered-token fault;
    with a `witness`, called with `study=True`, the sides it names
    besides (one dict of numbers a side; a side's numbers it leaves
    out are the program's, and so are a side's gaps it alone names).
    Yields one dict per seed."""
    from benchmark.correct import verdict

    prog = build(ctx)
    sides = {"program": _gaps(ctx),
             **{k: _gaps(ctx, c) for k, c in controls.items()}}
    vocab = int(ctx.config["vocab_size"])

    for seed in seeds:
        w, eng = open_engine(ctx, prog, seed)
        win = drive(ctx, eng, seed, ctx.seconds, False)
        _, _, attempted, failed, finished = window_metrics(
            win.rows, win.t0, win.t1, win.t_end)
        tokens, served = served_sample(finished, seed, mix_width(ctx.mix),
                                       int(ctx.cell["sample_rows"]))
        later = witness(ctx, w, eng, tokens, served, study=True) \
            if witness is not None else None
        close_engine(eng)
        altered = tokens.copy()
        row = np.flatnonzero(served[0])
        at = int(row[len(row) // 2])
        altered[0, at + 1] = (altered[0, at + 1] + 1) % vocab
        got = {k: gap_numbers(fn(w, tokens)[served])
               for k, fn in sides.items()}
        got["fault_token_altered"] = gap_numbers(
            sides["program"](w, altered)[served])
        more = later(w) if later is not None else {}
        got = {k: {**got.get(k, got["program"]),
                   **more.get(k, more.get("program", {}))}
               for k in list(got) + [k for k in more if k not in got]}
        yield {"seed": seed, "finished": len(finished),
               "attempted": attempted, "failed": failed,
               "served_tokens": int(served.sum()),
               **{k: {**n, "correct": verdict(n, ctx.cell["limits"])[0]}
                  for k, n in got.items()}}
        del w
