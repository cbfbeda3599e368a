"""The serving plane: `DecodeEngine` driven in-process through `submit`
and its own `step_once`, from one thread (this one), which is the
clients and the engine's loop in turn. Closed loop: each client submits
its next request when the last returns, before the next step. Open
loop: requests are submitted when due.

Set-up builds the engine with weights made from the seed, compiles the
three programs and runs the traffic for the mix's `warmup_steps` engine
steps so that every slot is decoding; the window then goes on with the
same engine, the same clients and the same lists.
Times are the handle's own clocks (`t_submit`, `t_first_token`,
`t_last_token`); counts are `stats()` deltas over the window.
"""

from __future__ import annotations

import gc
import threading
import time
from types import SimpleNamespace

import numpy as np

IDLE_S = 0.002
DRAIN_S = 60.0       # how long a first token is waited for past the close
BLOCK = 8            # rows of the sample the reference takes at a time
COUNTERS = ("steps", "tokens_total", "prefill_chunks", "prefix_hits",
            "cow_copies", "completed", "evictions", "quarantines",
            "kv_pages_gathered", "kv_pages_live")
STALL = 3.0          # a step over so many times the window's median


def build(ctx):
    """The model and its three programs, the weights not yet the seed's."""
    from deeplearning4j_tpu.engine.decode_program import DecodeProgram
    from deeplearning4j_tpu.zoo.decoder import CausalTransformer

    cfg, eng_cfg = ctx.config, ctx.cell["engine"]
    model = CausalTransformer(
        vocab_size=cfg["vocab_size"], d_model=cfg["n_embd"],
        n_heads=cfg["n_head"], n_layers=cfg["n_layer"],
        d_ff=cfg["n_inner"], max_ctx=cfg["n_positions"],
        **cfg["constructor"])
    model.params = {}           # the seed's come with `open_engine`
    return DecodeProgram(model, max_slots=eng_cfg["max_slots"],
                         page_size=eng_cfg["page_size"],
                         n_pages=eng_cfg.get("n_pages"))


def open_engine(ctx, prog, seed: int):
    """(w, engine): the seed's weights in the model, a fresh page pool,
    every program compiled or loaded."""
    import jax
    from deeplearning4j_tpu.serving.continuous import DecodeEngine

    from benchmark import weights

    w = weights.make_weights(ctx.reference.param_shapes(ctx.config), seed,
                             ctx.config["init"])
    prog.model.params = dict(w, layers=tuple(
        w["layers"][i] for i in range(len(w["layers"]))))
    eng = DecodeEngine(program=prog,
                       **ctx.cell["engine"].get("engine_kwargs", {}))
    eng.kv = prog.warmup(eng.kv)
    jax.block_until_ready(eng.kv)
    return w, eng


class Load:
    """The clients: who has what in flight, and every request sent."""

    def __init__(self, eng, lists, open_loop: bool):
        self.eng, self.lists, self.open = eng, lists, open_loop
        self.next = [0] * len(lists)
        self.live = [None] * len(lists)
        self.sent = []          # (request, handle or None, due, sent at)
        self.t_open = time.perf_counter()

    def pump(self) -> None:
        """Between two engine steps: a closed-loop client whose last
        request has returned submits its next, in the clients' order;
        an open-loop request is submitted once it is due."""
        now = time.perf_counter()
        for c, reqs in enumerate(self.lists):
            if self.next[c] >= len(reqs):
                continue
            req = reqs[self.next[c]]
            if self.open:
                if self.t_open + req["due_s"] > now:
                    continue
            elif self.live[c] is not None and not self.live[c].done:
                continue
            try:
                h = self.eng.submit(req["prompt"], req["max_new"])
            except Exception:   # noqa: BLE001 - refused: counted, not fatal
                h = None
            self.live[c] = h
            self.next[c] += 1
            self.sent.append((req, h, self.t_open + req["due_s"]
                              if self.open else None, now))

    def snapshot(self):
        """Every request sent, as it stands now: the engine's `stop`
        fails what is still in flight, so the window is read first."""
        rows = []
        for req, h, due, at in self.sent:
            if h is None:
                rows.append(SimpleNamespace(req=req, refused=True, due=due,
                                            failed=True, t_submit=at))
                continue
            rows.append(SimpleNamespace(
                req=req, refused=False, due=due, failed=h.failed,
                done=h.done, tokens=h.tokens_so_far(), t_submit=h.t_submit,
                t_placed=h.t_placed, t_first=h.t_first_token,
                t_last=h.t_last_token))
        return rows


def window_metrics(rows, t0: float, t1: float, t_end: float):
    """TTFT over every request submitted in the window, its first token
    waited for past the close (until `t_end`) where it has to be; one
    that failed, was refused or has no first token by then counts as
    failed, at the time it has waited. Time per output token over every
    request finished in the window."""
    ttft, tpot, attempted, failed, finished = [], [], 0, 0, []
    for r in rows:
        t_sub = r.due if r.due is not None else r.t_submit
        if t0 <= t_sub <= t1:
            attempted += 1
            if r.failed or r.t_first is None:
                failed += 1
                ttft.append((t_end - t_sub) * 1e3)
            else:
                ttft.append((r.t_first - t_sub) * 1e3)
        if not r.failed and r.done and t0 <= r.t_last <= t1:
            finished.append(r)
            if len(r.tokens) > 1:
                tpot.append((r.t_last - r.t_first)
                            / (len(r.tokens) - 1) * 1e3)
    return ttft, tpot, attempted, failed, finished


def mean_context(rows, t0: float, t1: float) -> float:
    """Live context of the average token emitted in the window, from
    the window's own requests: a request's tokens sit at prompt length
    + half of what it has emitted, weighted by that count."""
    num = den = 0.0
    for r in rows:
        if r.refused or r.t_first is None or r.t_first > t1 \
                or (r.done and r.t_last < t0):
            continue
        n = len(r.tokens)
        num += n * (len(r.req["prompt"]) + n / 2.0)
        den += n
    return num / den if den else 0.0


def served_sample(finished, seed: int, width: int, rows: int):
    """`rows` finished requests drawn from the seed, the longest in it,
    as one token matrix right-padded to `width` (the mix's longest
    request, so the reference compiles once) and the mask of served
    positions."""
    rng = np.random.default_rng([int(seed), 3])
    total = [len(r.req["prompt"]) + len(r.tokens) for r in finished]
    longest = int(np.argmax(total))
    rest = [i for i in range(len(finished)) if i != longest]
    pick = [longest] + rng.permutation(rest)[:rows - 1].tolist()
    tokens = np.zeros((rows, width), np.int32)
    served = np.zeros((rows, width - 1), bool)
    for row, i in enumerate(pick):
        seq = finished[i].req["prompt"] + finished[i].tokens
        tokens[row, :len(seq)] = seq
        served[row, len(finished[i].req["prompt"]) - 1:len(seq) - 1] = True
    return tokens, served


def stalls(laps):
    """Of a window's turns `(engine step, s in `step_once`, s around
    it)`: those that took over `STALL` times the window's median, as
    (engine step, s); that median; the longest turn under the mark. The
    whole turn counts: the machine stalls the process, not a line of
    it."""
    took = [(n, inside + around) for n, inside, around in laps]
    median = float(np.median([s for _, s in took]))
    mark = STALL * median
    return ([(n, s) for n, s in took if s > mark], median,
            max((s for _, s in took if s <= mark), default=0.0))


def log_window_phases(ctx, first: int, last: int) -> None:
    """The host's phases over the engine steps first..last, from the
    program's step records: the table a traced run's readers log
    (`timeline.serve_numbers`), for a run that has no readers."""
    from benchmark import timeline

    steps, _ = timeline.split_records("serve")
    secs = [timeline.phase_seconds(r)
            for r in timeline.pick_steps(steps or [], first, last)]
    if not secs:
        return
    # the turn before the window's first step began before it opened
    secs[0].pop("between_steps", None)
    timeline.log_phases(secs, ctx.log)


def mix_width(mix: dict) -> int:
    return (int(mix.get("shared_prefix", 0)) + mix["prompt_tokens"][1]
            + mix["output_tokens"][1])


def drive(ctx, eng, seed: int, seconds: float, trace: bool):
    """Warm the engine up on the seed's traffic, then the window. This
    one thread is the clients and the engine's loop: it calls the
    engine's own `step_once` (all that `DecodeEngine._loop` does) and
    lets the clients submit between two steps, so which step a request
    joins does not hang on which thread woke first, whatever a step
    takes. The window opens after `warmup_steps` engine steps in a
    traced run as in any other, and closes as a step ends. After it the
    load goes on until every request sent in the window has its first
    token, a minute at the most. Returns what the window saw; the
    engine is stopped."""
    from benchmark.traffic import generate

    mix = ctx.mix
    lists = generate.requests(mix, seed, int(ctx.config["vocab_size"]))
    load = Load(eng, lists, mix.get("loop") == "open")
    warm = int(mix["warmup_steps"])
    # the traced slice: `trace_steps` steps that end `trace_settle_steps`
    # before the window, which the profiler has to write its trace out
    t_stop = warm - int(ctx.cell.get("trace_settle_steps", 0))
    t_start = t_stop - int(ctx.cell.get("trace_steps", 0))

    pages = []          # pages of the pool in use after each step
    laps = []           # (step count, s in `step_once`, s around it)

    def step() -> int:
        t_a = time.perf_counter()
        load.pump()
        t_b = time.perf_counter()
        if not eng.step_once():
            time.sleep(IDLE_S)  # an open loop with nothing due yet
        t_c = time.perf_counter()
        st = eng.stats()
        pages.append(st["pages"]["total"] - st["pages"]["free"])
        laps.append((st["steps"], t_c - t_b,
                     t_b - t_a + time.perf_counter() - t_c))
        return st["steps"]

    # what set-up left behind is no garbage of the window's: without
    # this a full collection walks the whole heap of the imports
    gc.collect()
    gc.freeze()
    writer = None
    try:
        n, traced = 0, 0 if trace else 2    # 0 before, 1 in, 2 past it
        while n < warm:
            if traced == 0 and n >= t_start:
                ctx.start_trace()
                traced = 1
            n = step()
            if traced == 1 and n >= t_stop:
                # the steps go on while the profiler writes its trace
                writer = threading.Thread(target=ctx.stop_trace,
                                          name="bench-trace-stop")
                writer.start()
                traced = 2
        if writer is not None:
            writer.join()
        # a request that finishes in the window carries a stall of the
        # warm-up in its time per token
        before = stalls(laps)[0]
        del pages[:], laps[:]
        gc0 = [g["collections"] for g in gc.get_stats()]
        s0 = eng.stats()
        cpu0 = time.process_time()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            step()
        s1 = eng.stats()
        t1 = time.perf_counter()
        cpu_s = time.process_time() - cpu0
        in_window = len(laps)
        left = [len(reqs) - sent for reqs, sent in zip(lists, load.next)]
        ctx.log(f"pages in use over the window: mean {np.mean(pages):.0f}, "
                f"most {max(pages)} of {s1['pages']['total']}")
        ctx.log("collections in the window, by generation: "
                f"{[g['collections'] - a for g, a in zip(gc.get_stats(), gc0)]}")

        def waiting() -> bool:
            return any(h is not None and t0 <= at <= t1 and not h.done
                       and h.t_first_token is None
                       for _, h, _, at in load.sent)

        while waiting() and time.perf_counter() - t1 < DRAIN_S:
            step()
        t_end = time.perf_counter()
        # which of its modes the run is in: the machine's stalls land on
        # every request in flight (PERF.md, section 2)
        stalled, median, under = stalls(laps[:in_window])
        ctx.log(f"stalls, window steps over {STALL:g} times the median of "
                f"{median:.4f} s: {len(stalled)}, "
                f"{sum(s for _, s in stalled):.3f} s in all (engine step, "
                "s): " + ", ".join(f"{n} {s:.3f}" for n, s in stalled)
                + f"; the longest step under it {under:.3f}; most "
                "between two steps "
                f"{max(lap[2] for lap in laps[:in_window]):.4f}; in the "
                "warm-up, by its own median: "
                + (", ".join(f"{n} {s:.3f}" for n, s in before) or "none"))
        # a slow host takes more of it for the same steps, and moves every
        # reading of a cell whose host work the device does not hide
        ctx.log(f"this process's CPU time over the window: {cpu_s:.2f} s of "
                f"{t1 - t0:.2f} s, {cpu_s / in_window * 1e3:.3f} ms a step")
        ctx.log("requests left to send at the close, the client with the "
                f"fewest: {min(left)} of {len(lists[0])}; clients with "
                f"none: {sum(n == 0 for n in left)}")
        if not trace:
            log_window_phases(ctx, s0["steps"] + 1, s1["steps"])
        rows = load.snapshot()
    finally:
        if writer is not None:
            writer.join()
        eng.stop()
    return SimpleNamespace(rows=rows, t0=t0, t1=t1, t_end=t_end, s0=s0,
                           s1=s1)


def run(ctx):
    cfg, mix = ctx.config, ctx.mix
    prog = build(ctx)
    ctx.mark("model built")
    w, eng = open_engine(ctx, prog, ctx.seed)
    ctx.mark("weights made, page pool filled, programs compiled or loaded")
    win = drive(ctx, eng, ctx.seed, ctx.seconds, ctx.trace)
    setup_s = win.t0 - ctx.t_start
    t0, t1, s0, s1 = win.t0, win.t1, win.s0, win.s1
    window_s = t1 - t0
    ttft, tpot, attempted, failed, finished = window_metrics(
        win.rows, t0, t1, win.t_end)
    delta = {k: s1[k] - s0[k] for k in COUNTERS}
    ctx.log(f"window: {window_s:.3f} s, {delta}, {len(ttft)} TTFT and "
            f"{len(tpot)} TPOT samples, {attempted} sent, {failed} failed; "
            f"first tokens waited for {win.t_end - t1:.1f} s past it")
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
    state = {"eng": eng}

    def free():
        e = state.pop("eng")
        e.kv = None
        e.program.model.params = None

    def check():
        if not finished:
            return {}
        tokens, served = served_sample(finished, ctx.seed, mix_width(mix),
                                       int(ctx.cell["sample_rows"]))
        gaps = _gaps(ctx)(w, tokens)[served]
        ctx.log(f"reference over {gaps.size} served tokens of "
                f"{len(tokens)} requests: gap mean {np.mean(gaps):.5f}, "
                f"99th percentile {np.percentile(gaps, 99):.5f}")
        return widest_gap(gaps)

    return SimpleNamespace(attempted=attempted, failed=failed, facts=facts,
                           free=free, check=check, end_to_end=e2e)


def widest_gap(gaps) -> dict:
    """Over the served positions, the widest gap by which a served
    token's logit lies below the reference's best."""
    return {"served_logit_gap": float(np.max(gaps))}


def _gaps(ctx, control=None):
    """tokens -> the reference's gap at every position, as numpy."""
    import jax
    import jax.numpy as jnp

    n_head = int(ctx.config["n_head"])
    fn = jax.jit(lambda w, t: ctx.reference.served_gaps(
        w, t, n_head, control))
    # BLOCK rows at a time: one compiled shape, and a block fits
    return lambda w, tokens: np.concatenate([
        np.asarray(fn(w, jnp.asarray(tokens[i:i + BLOCK])))
        for i in range(0, len(tokens), BLOCK)])


def study(ctx, seeds):
    """For each seed, in this one process: a window of `ctx.seconds` at
    the cell's own load, then over the sample a run would compare: the
    program's widest gap, the control's (the reference in bfloat16 in
    the program's place: at each served position the gap of the token
    it puts first) and the altered-token fault's (one served token of
    the longest request replaced). Yields one dict per seed."""
    prog = build(ctx)
    gaps = _gaps(ctx)
    controls = {c: _gaps(ctx, c) for c in ("bfloat16", "fp8")}
    vocab = int(ctx.config["vocab_size"])
    for seed in seeds:
        w, eng = open_engine(ctx, prog, seed)
        win = drive(ctx, eng, seed, ctx.seconds, False)
        eng.kv = None
        _, _, attempted, failed, finished = window_metrics(
            win.rows, win.t0, win.t1, win.t_end)
        tokens, served = served_sample(finished, seed, mix_width(ctx.mix),
                                       int(ctx.cell["sample_rows"]))
        altered = tokens.copy()
        at = int(np.flatnonzero(served[0])[len(np.flatnonzero(served[0])) // 2])
        altered[0, at + 1] = (altered[0, at + 1] + 1) % vocab
        sides = {"program": gaps(w, tokens),
                 **{f"control_{c}": fn(w, tokens)
                    for c, fn in controls.items()},
                 "fault_token_altered": gaps(w, altered)}
        yield {"seed": seed, "finished": len(finished),
               "attempted": attempted, "failed": failed,
               "served_tokens": int(served.sum()),
               **{k: widest_gap(g[served]) for k, g in sides.items()}}
        prog.model.params = None
