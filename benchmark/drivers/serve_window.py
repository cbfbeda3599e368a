"""The serving plane for a decoder of sliding-window attention layers
over a per-slot ring beside full-attention layers over a bfloat16 K/V
page pool (`WindowMoETransformer`: Laguna's block): the same
`DecodeEngine`, clients, window and sample as `drivers/serve.py`
(`drive`, `window_metrics`, `served_sample` are its own), the weights a
leaf at a time and the gap numbers as `drivers/serve_latent.py` has
them (`make_weights`, `gap_numbers`), the reference a row at a time
and the state's counters as `drivers/serve_hybrid.py` has them
(`_gaps`, `STATE_COUNTERS`, `close_engine`). What is written here: the
model from the published `config.json` keys, and the engine opened
with its rings.
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
from benchmark.drivers.serve_latent import gap_numbers, make_weights
from benchmark.reference.laguna import KINDS


def build(ctx):
    """The model and its three programs, the weights not yet the seed's."""
    from deeplearning4j_tpu.engine.decode_program import DecodeProgram
    from deeplearning4j_tpu.zoo.window_moe import WindowMoETransformer

    cfg, eng_cfg = ctx.config, ctx.cell["engine"]
    rope = cfg["rope_parameters"]
    model = WindowMoETransformer(
        layer_kinds=[KINDS[k] for k in cfg["layer_types"]],
        heads=cfg["num_attention_heads_per_layer"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        window=cfg["sliding_window"],
        window_theta=rope["sliding_attention"]["rope_theta"],
        full_rope=rope["full_attention"],
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        dense_ff=cfg["intermediate_size"],
        moe_ff=cfg["moe_intermediate_size"],
        n_experts=cfg["router_experts"], top_k=cfg["num_experts_per_tok"],
        experts_held=cfg["experts_held"],
        n_shared=cfg["shared_expert_intermediate_size"]
        // cfg["moe_intermediate_size"],
        routed_scale=cfg["moe_routed_scaling_factor"],
        n_dense_layers=int(cfg["first_k_dense_replace"]),
        max_ctx=eng_cfg["max_ctx"], eps=cfg["rms_norm_eps"],
        **cfg["constructor"])
    model.params = {}           # the seed's come with `open_engine`
    return DecodeProgram(model, max_slots=eng_cfg["max_slots"],
                         page_size=eng_cfg["page_size"],
                         n_pages=eng_cfg.get("n_pages"))


def open_engine(ctx, prog, seed: int):
    """(w, engine): the seed's weights in the model, a fresh page pool
    and rings, every program compiled or loaded. Program and reference
    hold the same arrays."""
    import jax
    from deeplearning4j_tpu.serving.continuous import DecodeEngine

    w = make_weights(ctx.reference.param_shapes(ctx.config), seed,
                     ctx.config["init"],
                     ctx.config["constructor"]["param_dtype"])
    prog.model.params = w
    eng = DecodeEngine(program=prog,
                       **ctx.cell["engine"].get("engine_kwargs", {}))
    eng.kv, eng.state = prog.warmup(eng.kv, state=eng.state)
    jax.block_until_ready((eng.kv, eng.state))
    return w, eng


def run(ctx):
    cfg, mix = ctx.config, ctx.mix
    # the harness's timeline keys its planes by the driver's name
    # (`timeline.analysis`): this driver writes the serving plane's
    # records, the engine's own
    cfg["driver"] = "serve"
    prog = build(ctx)
    ctx.mark("model built")
    w, eng = open_engine(ctx, prog, ctx.seed)
    ctx.mark("weights made, page pool and rings filled, programs compiled "
             "or loaded")
    win = drive(ctx, eng, ctx.seed, ctx.seconds, ctx.trace)
    setup_s = win.t0 - ctx.t_start
    t0, t1, s0, s1 = win.t0, win.t1, win.s0, win.s1
    window_s = t1 - t0
    ttft, tpot, attempted, failed, finished = window_metrics(
        win.rows, t0, t1, win.t_end)
    # the engine's counters, its state's, and the model's (the expert
    # layer's and the rings' live cells), which its stats carry under
    # the model's own names
    delta = {k: s1[k] - s0[k] for k in ENGINE_COUNTERS + STATE_COUNTERS
             + tuple(prog.model.step_counters)}
    by_width = {w_: n - s0["dispatches"]["step_by_width"][w_] for w_, n in
                s1["dispatches"]["step_by_width"].items()}
    ctx.log(f"window: {window_s:.3f} s, {delta}, {len(ttft)} TTFT and "
            f"{len(tpot)} TPOT samples, {attempted} sent, {failed} failed; "
            f"first tokens waited for {win.t_end - t1:.1f} s past it; "
            f"prompts filled {s1['prefills'] - s0['prefills']}, steps by "
            f"window width in pages {by_width}, rings {s1['state_bytes']} "
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
    state = {"eng": eng}

    def check():
        if not finished:
            return {}
        tokens, served = served_sample(finished, ctx.seed, mix_width(mix),
                                       int(ctx.cell["sample_rows"]))
        gaps = _gaps(ctx)(w, tokens)[served]
        ctx.log(f"reference over {gaps.size} served tokens of "
                f"{len(tokens)} requests: gap mean {np.mean(gaps):.5f}, "
                f"share over 0.05 {np.mean(gaps > 0.05):.5f}")
        return gap_numbers(gaps)

    return SimpleNamespace(attempted=attempted, failed=failed, facts=facts,
                           free=lambda: close_engine(state.pop("eng")),
                           check=check, end_to_end=e2e)


def study(ctx, seeds):
    """`drivers/serve_hybrid.py`'s study over this driver's model: for
    each seed a window at the cell's own load, then over the sample a
    run would compare, the numbers `correct` holds and the harness's
    own verdict on them for the program, the control (the reference
    with every product's operands in float8 e4m3), a witness (the same
    in bfloat16), the gate's fault (the tokens the reference without
    its heads' gates puts first) and the altered-token fault. Yields
    one dict per seed."""
    from benchmark.correct import verdict

    prog = build(ctx)
    sides = {"program": _gaps(ctx), "control_fp8": _gaps(ctx, "fp8"),
             "witness_bfloat16": _gaps(ctx, "bfloat16"),
             "fault_gate_dropped": _gaps(ctx, "no_gate")}
    vocab = int(ctx.config["vocab_size"])

    def judged(g):
        numbers = gap_numbers(g)
        return {**numbers,
                "correct": verdict(numbers, ctx.cell["limits"])[0]}

    for seed in seeds:
        w, eng = open_engine(ctx, prog, seed)
        win = drive(ctx, eng, seed, ctx.seconds, False)
        close_engine(eng)
        _, _, attempted, failed, finished = window_metrics(
            win.rows, win.t0, win.t1, win.t_end)
        tokens, served = served_sample(finished, seed, mix_width(ctx.mix),
                                       int(ctx.cell["sample_rows"]))
        altered = tokens.copy()
        row = np.flatnonzero(served[0])
        at = int(row[len(row) // 2])
        altered[0, at + 1] = (altered[0, at + 1] + 1) % vocab
        got = {k: fn(w, tokens) for k, fn in sides.items()}
        got["fault_token_altered"] = sides["program"](w, altered)
        yield {"seed": seed, "finished": len(finished),
               "attempted": attempted, "failed": failed,
               "served_tokens": int(served.sum()),
               **{k: judged(g[served]) for k, g in got.items()}}
        del w
