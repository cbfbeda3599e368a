"""Laguna-S-2.1 (`laguna`; the `config.json` named in
`configs/laguna-s-2.1.json`), plain, as one chip of a group of eight
holds it: the experts it holds of every expert layer it has, the shared
expert, the vocabulary's slice.

Full causal forward pass over whole sequences in float32 `jax.numpy` at
`highest` matmul precision: no cache, no pages, no ring, no chunks.
Attention repeats every key and value head over its group of query
heads and takes the queries a block at a time over the keys a block can
see (every earlier one in a full layer, the last `sliding_window` in a
window layer), so that 10,240 positions fit. The weights come in as the
program stores them (bfloat16) and are raised to float32 a matrix, and
an expert, at a time.

    a = x + Attn(norm(x));  y = a + FFN(norm(a))        RMSNorm, eps 1e-6
    Attn, u = norm(x): q = u W_q (heads x 128, heads by
        num_attention_heads_per_layer), k = u W_k, v = u W_v (8 x 128);
        full_attention: lanes 0-63 of each head rotated, pairs i and
        i + 32, YaRN's frequencies (theta 500,000, factor 128, original
        8,192, beta_fast 32, beta_slow 1) with cos and sin times
        attention_factor; sliding_attention: all 128 lanes rotated,
        pairs i and i + 64, theta 10,000; scores q.k / sqrt(128), causal,
        a sliding layer's query at t seeing keys t-511..t (a key k is
        seen where k > t - sliding_window); o_h = softmax(.) V of head
        h's K/V head h // (heads / 8); o_h *= sigmoid(u W_gate)_h
        (W_gate [hidden, heads]); Attn = concat(o) W_o
    FFN: W_2(silu(W_1 x) * W_3 x) of width 12,288 in mlp_only_layers;
        after it p = softmax(x W_r) over all 256 experts, the 10 largest,
        w = 2.5 p_top / sum(p_top), sum over the held experts of
        w_i E_i(x), plus the shared expert E_s(x) (width 1,024, no gate
        of its own)
    logits = norm(x_L) W_head (untied)

Departures, shared with the program: layers 0-4 of 48; 32 of 256
routed experts held (ids 0-31), so an expert layer's routed part is
this chip's partial sum; 12,544 of 100,352 vocabulary rows.

Also here: the operations and bytes this chip's share requires. It
imports nothing of the program.
"""

from __future__ import annotations

import math

HEAD_GROUP = 8      # query heads a block of attention scores holds at a time
QUERY_BLOCK = 512   # queries a block of attention scores holds at a time
BYTES = 2           # the configuration's stored precision: bfloat16
KINDS = {"full_attention": "full", "sliding_attention": "window"}


def dims(cfg: dict) -> dict:
    d = {"h": "hidden_size", "kv": "num_key_value_heads", "d": "head_dim",
         "ff": "intermediate_size", "moe_ff": "moe_intermediate_size",
         "shared_ff": "shared_expert_intermediate_size",
         "top_k": "num_experts_per_tok", "layers": "num_hidden_layers",
         "vocab": "vocab_size", "window": "sliding_window",
         "router": "router_experts"}
    out = {k: int(cfg[v]) for k, v in d.items()}
    out["held"] = [int(e) for e in cfg["experts_held"]]
    out["kinds"] = [KINDS[k] for k in cfg["layer_types"]]
    out["heads"] = [int(h) for h in cfg["num_attention_heads_per_layer"]]
    out["dense"] = [t == "dense" for t in cfg["mlp_layer_types"]]
    if not len(out["kinds"]) == len(out["heads"]) == len(out["dense"]) \
            == out["layers"]:
        raise ValueError("layer_types, num_attention_heads_per_layer and "
                         "mlp_layer_types must name num_hidden_layers layers")
    out["eps"] = float(cfg["rms_norm_eps"])
    out["scale"] = float(cfg["moe_routed_scaling_factor"])
    return out


def param_shapes(cfg: dict) -> dict:
    """Matrices [in, out]; the held experts stacked in the order of
    `experts_held`; the shared expert `sg`, `su`, `sd`."""
    d = dims(cfg)
    h, dh, e, f, fs = d["h"], d["d"], len(d["held"]), d["moe_ff"], \
        d["shared_ff"]
    dense = {"norm_pre_mlp": (h,), "w_gate": (h, d["ff"]),
             "w_up": (h, d["ff"]), "w_down": (d["ff"], h)}
    moe = {"norm_pre_mlp": (h,), "router": (h, d["router"]),
           "eg": (e, h, f), "eu": (e, h, f), "ed": (e, f, h),
           "sg": (h, fs), "su": (h, fs), "sd": (fs, h)}

    def attn(heads):
        return {"norm_in": (h,), "wq": (h, heads * dh),
                "wk": (h, d["kv"] * dh), "wv": (h, d["kv"] * dh),
                "attn_gate": (h, heads), "wo": (heads * dh, h)}

    return {"tok_emb": (d["vocab"], h), "final_norm": (h,),
            "head": (h, d["vocab"]),
            "layers": [dict(attn(heads), **(dense if is_dense else moe))
                       for heads, is_dense in zip(d["heads"], d["dense"])]}


# ------------------------------------------------------------- counts
def _prod(shape) -> int:
    n = 1
    for s in shape:
        n *= int(s)
    return n


def n_params(cfg: dict) -> int:
    shapes = param_shapes(cfg)
    return sum(_prod(s) for k, s in shapes.items() if k != "layers") \
        + sum(_prod(s) for layer in shapes["layers"] for s in layer.values())


def attn_params(cfg: dict, heads: int) -> int:
    """The five matrices of one attention layer of `heads` query heads:
    q, k, v, o and the gate."""
    d = dims(cfg)
    return 2 * d["h"] * d["d"] * (heads + d["kv"]) + d["h"] * heads


def expert_params(cfg: dict) -> int:
    d = dims(cfg)
    return 3 * d["h"] * d["moe_ff"]


def _moe_fixed_params(cfg: dict) -> int:
    """What every row of an expert layer goes through: the router and
    the shared expert."""
    d = dims(cfg)
    return d["h"] * d["router"] + 3 * d["h"] * d["shared_ff"]


def cell_bytes(cfg: dict) -> int:
    """What one token keeps in one attention layer: a K row and a V
    row."""
    d = dims(cfg)
    return 2 * d["kv"] * d["d"] * BYTES


def _layers(cfg: dict, kind: str):
    """The head counts of the layers of one kind."""
    d = dims(cfg)
    return [h for h, k in zip(d["heads"], d["kinds"]) if k == kind]


def _n_moe(cfg: dict) -> int:
    return dims(cfg)["dense"].count(False)


def matmul_params(cfg: dict) -> int:
    """Matrix parameters this chip holds, the head's included (the
    embedding's look-up reads a row a token)."""
    d = dims(cfg)
    return sum(attn_params(cfg, h) for h in d["heads"]) \
        + d["dense"].count(True) * 3 * d["h"] * d["ff"] \
        + _n_moe(cfg) * (_moe_fixed_params(cfg)
                         + len(d["held"]) * expert_params(cfg)) \
        + d["h"] * d["vocab"]


def attn_context_flops(cfg: dict, heads: int, context: float) -> float:
    """Scores and values of one position over `context` live ones, one
    layer of `heads` query heads (128 + 128 numbers a head a
    position)."""
    d = dims(cfg)
    return 2.0 * heads * 2 * d["d"] * context


def flops_per_token(cfg: dict, context: float) -> float:
    """One position through this chip's share: 2 per matrix parameter
    it multiplies through — of each expert layer the `top_k` experts
    it routes to that are held, at the mean (10 x 32 / 256), not the 32
    the layer holds —, attention over `context` live positions in each
    full layer and over at most `sliding_window` in each window
    layer."""
    d = dims(cfg)
    routed = d["top_k"] * len(d["held"]) / d["router"]
    through = matmul_params(cfg) - _n_moe(cfg) * (
        len(d["held"]) - routed) * expert_params(cfg)
    return 2.0 * through \
        + sum(attn_context_flops(cfg, h, context)
              for h in _layers(cfg, "full")) \
        + sum(attn_context_flops(cfg, h, min(context, d["window"]))
              for h in _layers(cfg, "window"))


def experts_hit(cfg: dict, rows: float) -> float:
    """Of one layer's held experts, how many get at least one of `rows`
    tokens at the mean, each token keeping `top_k` of the router's
    experts with no favourite: 22.9 of 32 at 32 rows."""
    d = dims(cfg)
    return len(d["held"]) * (1.0 - (1.0 - d["top_k"] / d["router"]) ** rows)


def _unread(cfg: dict, n_moe: int, slots: float,
            counted: float | None) -> float:
    """Held experts no row of a step chose, over its `n_moe` expert
    layers: all the held less `counted`, the hits the program counted
    a step, or where that is None less the `experts_hit` that `slots`
    rows reach a layer at the mean."""
    held = len(dims(cfg)["held"])
    if counted is None:
        return n_moe * (held - experts_hit(cfg, slots))
    return n_moe * held - counted


def decode_step_bytes(cfg: dict, live_cells: float, slots: float,
                      experts_hit: float | None = None) -> float:
    """What one decode step must move whatever implements it: every
    matrix that a row multiplies through once (of the expert layers'
    held experts those some row chose: `experts_hit`, summed over the
    layers, as the program counted them a step; where it is None the
    `experts_hit` that `slots` rows reach a layer at the mean), the
    `live_cells` K and V rows of the active slots once in each full
    layer and at most `sliding_window` a slot of them in each window
    layer's ring, and one new pair a slot in every attention layer."""
    d = dims(cfg)
    n_full, n_win = len(_layers(cfg, "full")), len(_layers(cfg, "window"))
    unread = _unread(cfg, _n_moe(cfg), slots, experts_hit) \
        * expert_params(cfg)
    ring = min(live_cells, slots * d["window"])
    return (matmul_params(cfg) - unread) * BYTES \
        + (n_full * live_cells + n_win * ring
           + (n_full + n_win) * slots) * cell_bytes(cfg)


def moe_step(cfg: dict, rows: float, assignments_held: float,
             experts_hit: float):
    """(operations, bytes) the expert layers of one step require, from
    the program's counts summed over its expert layers: `rows` tokens a
    layer through the router and the shared expert, `assignments_held`
    token-expert pairs that fell on held experts, `experts_hit` held
    experts that got at least one."""
    n_moe = _n_moe(cfg)
    flops = 2.0 * (assignments_held * expert_params(cfg)
                   + n_moe * rows * _moe_fixed_params(cfg))
    nbytes = BYTES * (experts_hit * expert_params(cfg)
                      + n_moe * _moe_fixed_params(cfg))
    return flops, nbytes


def gqa_step(cfg: dict, rows: float, live_cells: float):
    """(operations, bytes) the full attention layers of one step
    require: the five matrices once and `rows` tokens through them, the
    `live_cells` K and V rows of the active slots once and attended
    over, one pair written a slot."""
    flops = nbytes = 0.0
    for heads in _layers(cfg, "full"):
        flops += 2.0 * rows * attn_params(cfg, heads) \
            + attn_context_flops(cfg, heads, live_cells)
        nbytes += attn_params(cfg, heads) * BYTES \
            + (live_cells + rows) * cell_bytes(cfg)
    return flops, nbytes


def swa_step(cfg: dict, rows: float, cells_live: float):
    """(operations, bytes) the window layers of one step require: the
    five matrices once and `rows` tokens through them, the `cells_live`
    ring cells the active rows attend (summed over the window layers,
    as the program's `window_cells_live` counts them) read once and
    attended over, one pair written a row and layer."""
    heads = _layers(cfg, "window")
    if not heads:
        return 0.0, 0.0
    per_cell = sum(heads) / len(heads)      # one head count in Laguna
    flops = sum(2.0 * rows * attn_params(cfg, h) for h in heads) \
        + attn_context_flops(cfg, per_cell, cells_live)
    nbytes = sum(attn_params(cfg, h) for h in heads) * BYTES \
        + (cells_live + len(heads) * rows) * cell_bytes(cfg)
    return flops, nbytes


# ---------------------------------------------------------- the model
def fp8(a):
    """Round to float8 e4m3 and back: the precision below bfloat16."""
    import jax.numpy as jnp

    return a.astype(jnp.float8_e4m3fn).astype(a.dtype)


def bf16(a):
    import jax.numpy as jnp

    return a.astype(jnp.bfloat16).astype(a.dtype)


# "no_gate" rounds nothing and leaves the heads' gate out: the fault a
# limit of `correct` has to catch beside the precision below
ROUND = {None: lambda a: a, "fp8": fp8, "bfloat16": bf16,
         "no_gate": lambda a: a}


def _rms(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * g


def _mm(control):
    import jax
    import jax.numpy as jnp

    q = ROUND[control]
    return lambda a, w: jnp.matmul(q(a), q(w.astype(jnp.float32)),
                                   precision=jax.lax.Precision.HIGHEST)


def _mlp(mm, x, wg, wu, wd):
    import jax

    return mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)


def yarn_frequencies(rope: dict, dim: int):
    """The published YaRN formula (Peng et al. 2023, as Hugging Face's
    `_compute_yarn_parameters` has it, bounds truncated), in float64:
    [dim / 2] inverse frequencies of a rotation over `dim` lanes."""
    import numpy as np

    theta, factor = float(rope["rope_theta"]), float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])

    def correction(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(correction(float(rope["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    freq = theta ** (np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    extrapolated = 1.0 - ramp
    return (1.0 / (factor * freq)) * (1.0 - extrapolated) \
        + (1.0 / freq) * extrapolated


def rotation(cfg: dict, kind: str):
    """(inverse frequencies [r / 2] as numpy, factor on cos and sin) of
    a layer kind's rotation over its first r lanes."""
    import numpy as np

    rope = cfg["rope_parameters"][{"full": "full_attention",
                                   "window": "sliding_attention"}[kind]]
    dim = int(dims(cfg)["d"] * float(rope.get("partial_rotary_factor", 1)))
    if rope.get("rope_type") == "yarn":
        return yarn_frequencies(rope, dim), float(rope["attention_factor"])
    return 1.0 / float(rope["rope_theta"]) ** (np.arange(0, dim, 2) / dim), \
        1.0


def _rotate(x, inv, factor):
    """x [N, T, H, D] rotated by its position on axis 1 over its first
    2 len(inv) lanes, pairs i and i + len(inv); the others pass."""
    import jax.numpy as jnp

    t, half = x.shape[1], len(inv)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)                           # [T, r/2]
    cos = (jnp.cos(ang) * factor)[:, None]
    sin = (jnp.sin(ang) * factor)[:, None]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _attend(q, k, v, window, rnd):
    """q [N, T, H, D], k / v [N, T, H, D] (repeated over their groups)
    -> [N, T, H, D]: causal attention, a query at t seeing keys in
    (t - window, t] where `window` is not None, the queries
    `QUERY_BLOCK` at a time over the keys a block can see."""
    import jax
    import jax.numpy as jnp

    hp = jax.lax.Precision.HIGHEST
    n, t, h, dh = q.shape
    blk = min(QUERY_BLOCK, t)
    tp = -(-t // blk) * blk
    span = tp if window is None else min(tp, blk + window - 1)
    back = span - blk           # keys a block reads before its first query
    pad = lambda a, front: jnp.pad(  # noqa: E731
        a, ((0, 0), (front, tp - t), (0, 0), (0, 0)))
    q, k, v = pad(q, 0), pad(k, back), pad(v, back)

    def block(i):
        q0 = i * blk
        qi = jax.lax.dynamic_slice_in_dim(q, q0, blk, axis=1)
        ki = jax.lax.dynamic_slice_in_dim(k, q0, span, axis=1)
        vi = jax.lax.dynamic_slice_in_dim(v, q0, span, axis=1)
        qpos = q0 + jnp.arange(blk)
        kpos = q0 - back + jnp.arange(span)
        see = (kpos[None] >= 0) & (kpos[None] <= qpos[:, None])
        if window is not None:
            see = see & (kpos[None] > qpos[:, None] - window)
        out = []
        for g in range(0, h, HEAD_GROUP):
            hs = slice(g, g + HEAD_GROUP)
            s = jnp.einsum("nthd,nuhd->nhtu", rnd(qi[:, :, hs]),
                           rnd(ki[:, :, hs]), precision=hp) / math.sqrt(dh)
            s = jnp.where(see[None, None], s, -jnp.inf)
            out.append(jnp.einsum("nhtu,nuhd->nthd",
                                  rnd(jax.nn.softmax(s, axis=-1)),
                                  rnd(vi[:, :, hs]), precision=hp))
        return jnp.concatenate(out, axis=2)

    blocks = jax.lax.map(block, jnp.arange(tp // blk))   # [B, N, blk, H, D]
    out = jnp.moveaxis(blocks, 0, 1).reshape(n, tp, h, dh)
    return out[:, :t]


def attn_mix(lp, u, cfg: dict, kind: str, control=None):
    """One attention layer's token mixing over whole sequences: normed
    input `u` [N, T, h] -> [N, T, h], the gate on each head's output."""
    import jax
    import jax.numpy as jnp

    d, mm, rnd = dims(cfg), _mm(control), ROUND[control]
    kv, dh = d["kv"], d["d"]
    n, t, _ = u.shape
    heads = lp["attn_gate"].shape[1]
    q = mm(u, lp["wq"]).reshape(n, t, heads, dh)
    k = mm(u, lp["wk"]).reshape(n, t, kv, dh)
    v = mm(u, lp["wv"]).reshape(n, t, kv, dh)
    inv, factor = rotation(cfg, kind)
    q, k = _rotate(q, inv, factor), _rotate(k, inv, factor)
    k, v = (jnp.repeat(a, heads // kv, axis=2) for a in (k, v))
    o = _attend(q, k, v, d["window"] if kind == "window" else None, rnd)
    if control != "no_gate":
        o = o * jax.nn.sigmoid(mm(u, lp["attn_gate"]))[..., None]
    return mm(o.reshape(n, t, heads * dh), lp["wo"])


def expert_ffn(lp, xn, cfg: dict, control=None, held=None):
    """The expert layer's feed-forward on normed input `xn` [.., h]:
    softmax scores over all experts, the `top_k` largest renormalised
    over their sum and scaled, the terms of the experts in `held`
    (default `experts_held`; stacked in `lp` in that order), plus the
    shared expert."""
    import jax
    import jax.numpy as jnp

    d, mm = dims(cfg), _mm(control)
    held = d["held"] if held is None else held
    scores = jax.nn.softmax(mm(xn, lp["router"]), axis=-1)
    top_s, top_i = jax.lax.top_k(scores, d["top_k"])
    top_w = d["scale"] * top_s / jnp.sum(top_s, axis=-1, keepdims=True)

    def term(y, expert):
        e, wg, wu, wd = expert
        w = jnp.sum(jnp.where(top_i == e, top_w, 0.0), axis=-1)
        return y + w[..., None] * _mlp(mm, xn, wg, wu, wd), None

    y, _ = jax.lax.scan(
        term, jnp.zeros_like(xn),
        (jnp.asarray(held, jnp.int32), lp["eg"], lp["eu"], lp["ed"]))
    return y + _mlp(mm, xn, lp["sg"], lp["su"], lp["sd"])


def logits_fn(params, tokens, cfg: dict, control=None):
    """tokens [N, T] -> logits [N, T, vocab], float32. `control` None is
    the reference; "fp8" and "bfloat16" keep float32 arithmetic and
    round the operands of every matrix product (weights, activations,
    keys, values, softmax weights, the router's, the gate's, the
    head's) to that precision; "no_gate" is the reference with every
    head's gate left out."""
    import jax.numpy as jnp

    d, mm = dims(cfg), _mm(control)
    x = params["tok_emb"][tokens].astype(jnp.float32)
    for lp, kind in zip(params["layers"], d["kinds"]):
        x = x + attn_mix(lp, _rms(x, lp["norm_in"], d["eps"]), cfg, kind,
                         control)
        xn = _rms(x, lp["norm_pre_mlp"], d["eps"])
        if "router" in lp:
            x = x + expert_ffn(lp, xn, cfg, control)
        else:
            x = x + _mlp(mm, xn, lp["w_gate"], lp["w_up"], lp["w_down"])
    return mm(_rms(x, params["final_norm"], d["eps"]), params["head"])


def served_gaps(params, tokens, cfg: dict, control=None):
    """For each position p < T-1 of each row: how far the reference's
    logit of the token at p+1 lies below the reference's best logit at
    p. With a `control`, the token judged is the one the lower
    precision puts first instead of the one in `tokens`. [N, T-1]."""
    import jax.numpy as jnp

    ref = logits_fn(params, tokens, cfg)[:, :-1]
    if control is None:
        judged = tokens[:, 1:]
    else:
        judged = jnp.argmax(logits_fn(params, tokens, cfg, control)[:, :-1],
                            axis=-1)
    got = jnp.take_along_axis(ref, judged[..., None], axis=-1)[..., 0]
    return jnp.max(ref, axis=-1) - got
