"""The serving plane for a latent-attention, sparse-expert decoder
(`LatentMoETransformer`): the same `DecodeEngine`, clients, window and
sample as `drivers/serve.py` (`drive`, `Load`, `window_metrics`,
`served_sample` are its own), with what that file ties to GPT-2 done
here: the model is built from the published `config.json` keys, the
weights are drawn a leaf at a time and rounded to bfloat16 (one jitted
call over every leaf in float32 would be 19.7 GB), the reference takes
one row at a time, and the expert layer's counters join the window's
deltas.
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
    widest_gap,
    window_metrics,
)

BLOCK = 1            # rows of the sample the reference takes at a time:
#                      a row is 4,096 positions at the published widths


def gap_numbers(gaps) -> dict:
    """What `correct` holds of the served positions' gaps: the widest,
    which a router near-tie that bfloat16 turns over sets (one held
    expert's term enters or leaves), and the 99th percentile, which
    such a token in a few thousand does not reach and an error spread
    over many tokens does."""
    return {**widest_gap(gaps),
            "served_logit_gap_p99": float(np.percentile(gaps, 99))}


def build(ctx):
    """The model and its three programs, the weights not yet the seed's."""
    from deeplearning4j_tpu.engine.decode_program import DecodeProgram
    from deeplearning4j_tpu.zoo.latent_moe import LatentMoETransformer

    cfg, eng_cfg = ctx.config, ctx.cell["engine"]
    n_dense = int(cfg["first_k_dense_replace"])
    model = LatentMoETransformer(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        dense_ff=cfg["intermediate_size"],
        moe_ff=cfg["moe_intermediate_size"],
        n_experts=cfg["router_experts"], top_k=cfg["num_experts_per_tok"],
        experts_held=cfg["experts_held"], n_shared=cfg["n_shared_experts"],
        routed_scale=cfg["routed_scaling_factor"], n_dense_layers=n_dense,
        n_moe_layers=int(cfg["num_hidden_layers"]) - n_dense,
        max_ctx=eng_cfg["max_ctx"], rope_theta=cfg["rope_theta"],
        eps=cfg["rms_norm_eps"], **cfg["constructor"])
    model.params = {}           # the seed's come with `open_engine`
    return DecodeProgram(model, max_slots=eng_cfg["max_slots"],
                         page_size=eng_cfg["page_size"],
                         n_pages=eng_cfg.get("n_pages"))


def make_weights(shapes: dict, seed: int, init: dict, dtype: str):
    """The nesting of `shapes` as device arrays from the seed, one
    jitted draw a leaf so that no float32 copy of more than a leaf
    exists: matrices normal(0, w_std) rounded to `dtype` (the
    configuration's bfloat16), gains 1 + 0.1 n in float32."""
    import jax
    import jax.numpy as jnp

    std = float(init["w_std"])

    def draw(key, shape):
        n = jax.random.normal(key, shape, jnp.float32)
        if len(shape) == 1:
            return 1.0 + 0.1 * n
        return (std * n).astype(dtype)

    draw = jax.jit(draw, static_argnums=1)
    seed = int(seed)
    root = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(0), seed & 0xFFFFFFFF), seed >> 32)

    def group(key, leaves):
        return {name: draw(jax.random.fold_in(key, i), tuple(leaves[name]))
                for i, name in enumerate(sorted(leaves))}

    top = {k: v for k, v in shapes.items() if k != "layers"}
    out = group(jax.random.fold_in(root, 0), top)
    out["layers"] = tuple(group(jax.random.fold_in(root, i + 1), layer)
                          for i, layer in enumerate(shapes["layers"]))
    return out


def open_engine(ctx, prog, seed: int):
    """(w, engine): the seed's weights in the model, a fresh page pool,
    every program compiled or loaded. Program and reference hold the
    same arrays."""
    import jax
    from deeplearning4j_tpu.serving.continuous import DecodeEngine

    w = make_weights(ctx.reference.param_shapes(ctx.config), seed,
                     ctx.config["init"],
                     ctx.config["constructor"]["param_dtype"])
    prog.model.params = w
    eng = DecodeEngine(program=prog,
                       **ctx.cell["engine"].get("engine_kwargs", {}))
    eng.kv = prog.warmup(eng.kv)
    jax.block_until_ready(eng.kv)
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
    ctx.mark("weights made, page pool filled, programs compiled or loaded")
    win = drive(ctx, eng, ctx.seed, ctx.seconds, ctx.trace)
    setup_s = win.t0 - ctx.t_start
    t0, t1, s0, s1 = win.t0, win.t1, win.s0, win.s1
    window_s = t1 - t0
    ttft, tpot, attempted, failed, finished = window_metrics(
        win.rows, t0, t1, win.t_end)
    # the engine's counters and the expert layer's, which its stats
    # carry under the model's own names
    delta = {k: s1[k] - s0[k]
             for k in ENGINE_COUNTERS + tuple(prog.model.step_counters)}
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
                f"share over 0.05 {np.mean(gaps > 0.05):.5f}")
        return gap_numbers(gaps)

    return SimpleNamespace(attempted=attempted, failed=failed, facts=facts,
                           free=free, check=check, end_to_end=e2e)


def _gaps(ctx, control=None):
    """tokens -> the reference's gap at every position, as numpy."""
    import jax
    import jax.numpy as jnp

    cfg = ctx.config
    fn = jax.jit(lambda w, t: ctx.reference.served_gaps(w, t, cfg, control))
    return lambda w, tokens: np.concatenate([
        np.asarray(fn(w, jnp.asarray(tokens[i:i + BLOCK])))
        for i in range(0, len(tokens), BLOCK)])


def study(ctx, seeds):
    """For each seed, in this one process: a window of `ctx.seconds` at
    the cell's own load, then over the sample a run would compare, the
    numbers `correct` holds and what the cell's limits make of them
    (`correct`, by the harness's own `verdict`) for the program, the
    control (the reference with the operands of every product rounded
    to float8 e4m3, the precision below the configuration's bfloat16,
    in the program's place: at each served position the gap of the
    token it puts first), a witness (the same with bfloat16 operands,
    the program's own precision in the reference's path) and the
    altered-token fault (one served token of the longest request
    replaced). Yields one dict per seed."""
    from benchmark.correct import verdict

    prog = build(ctx)
    gaps = _gaps(ctx)
    controls = {"control_fp8": _gaps(ctx, "fp8"),
                "witness_bfloat16": _gaps(ctx, "bfloat16")}
    vocab = int(ctx.config["vocab_size"])

    def judged(g):
        numbers = gap_numbers(g)
        return {**numbers,
                "correct": verdict(numbers, ctx.cell["limits"])[0]}

    for seed in seeds:
        w, eng = open_engine(ctx, prog, seed)
        win = drive(ctx, eng, seed, ctx.seconds, False)
        eng.kv = None
        prog.model.params = None
        _, _, attempted, failed, finished = window_metrics(
            win.rows, win.t0, win.t1, win.t_end)
        tokens, served = served_sample(finished, seed, mix_width(ctx.mix),
                                       int(ctx.cell["sample_rows"]))
        altered = tokens.copy()
        row = np.flatnonzero(served[0])
        at = int(row[len(row) // 2])
        altered[0, at + 1] = (altered[0, at + 1] + 1) % vocab
        sides = {"program": gaps(w, tokens),
                 **{k: fn(w, tokens) for k, fn in controls.items()},
                 "fault_token_altered": gaps(w, altered)}
        yield {"seed": seed, "finished": len(finished),
               "attempted": attempted, "failed": failed,
               "served_tokens": int(served.sum()),
               **{k: judged(g[served]) for k, g in sides.items()}}
        del w
