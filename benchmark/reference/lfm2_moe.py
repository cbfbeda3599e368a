"""LFM2-24B-A2B (`lfm2_moe`; the `config.json` named in
`configs/lfm2-24b-a2b.json`; Hugging Face `transformers` `Lfm2Moe*`),
plain, as the first of five pipeline stages holds it: every expert of
every layer it has.

Full causal forward pass over whole sequences in float32 `jax.numpy` at
`highest` matmul precision: no cache, no pages, no chunks, no tail
carried between calls. The short convolution runs as three shifted
products over the whole sequence; attention repeats every key and value
head over its group of query heads and runs a group of heads at a time.
The weights come in as the program stores them (bfloat16) and are
raised to float32 a matrix, and an expert, at a time.

    a = x + Op(norm(x));  y = a + FFN(norm(a))       RMSNorm, eps 1e-5
    Op = ShortConv (layer_types "conv"), u = norm(x):
        [B | C | z] = u W_in  (2048 x 6144, no bias);  s = B * z
        c_t = sum_{j<3} w_j * s_{t-2+j}  (depthwise, causal, s before
        position 0 is 0, no bias, no activation);  Op = (C * c) W_out
    Op = Attention (layer_types "full_attention"):
        q = u W_q (32 x 64), k = u W_k (8 x 64), v = u W_v (8 x 64);
        q, k through an RMSNorm a head (a gain of 64), then rotated
        over all 64 numbers (half-split pairs, theta 1e6);
        causal softmax(q k^T / 8) v, query head h on K/V head h // 4; W_o
    FFN: W_2(silu(W_1 x) * W_3 x) of width 11776 below num_dense_layers;
        after it s = sigmoid(x W_r) over 64 experts, the 4 largest of
        s + expert_bias, w = s_top / (sum(s_top) + 1e-6) (times
        routed_scaling_factor, 1), sum_i w_i E_i(x), no shared expert
    logits = norm(x_L) E^T, E the embedding (tied)

Departures, shared with the program: layers 1-9 of 40 (numbered from
0). Every expert is held, so the expert layer's result is the model's.

Also here: the operations and bytes this stage requires. It imports
nothing of the program.
"""

from __future__ import annotations

import math

HEAD_GROUP = 8      # query heads a block of attention scores holds at a time
BYTES = 2           # the configuration's stored precision: bfloat16
STATE_BYTES = 4     # the convolution's tail: float32
KINDS = {"conv": "conv", "full_attention": "attn"}


def dims(cfg: dict) -> dict:
    d = {"h": "hidden_size", "heads": "num_attention_heads",
         "kv": "num_key_value_heads", "ff": "intermediate_size",
         "moe_ff": "moe_intermediate_size", "top_k": "num_experts_per_tok",
         "layers": "num_hidden_layers", "dense": "num_dense_layers",
         "vocab": "vocab_size", "taps": "conv_L_cache",
         "router": "router_experts"}
    out = {k: int(cfg[v]) for k, v in d.items()}
    # the head size: hidden / heads (`assumed` in the file)
    out["d"] = out["h"] // out["heads"]
    out["held"] = [int(e) for e in cfg["experts_held"]]
    out["kinds"] = [KINDS[k] for k in cfg["layer_types"]]
    if len(out["kinds"]) != out["layers"]:
        raise ValueError(f"layer_types names {len(out['kinds'])} layers, "
                         f"num_hidden_layers {out['layers']}")
    out["theta"] = float(cfg["rope_parameters"]["rope_theta"])
    out["eps"] = float(cfg["norm_eps"])
    out["route_eps"] = float(cfg["route_norm_eps"])
    out["scale"] = float(cfg["routed_scaling_factor"])
    return out


def _conv_shapes(d: dict) -> dict:
    h = d["h"]
    return {"norm_in": (h,), "w_in": (h, 3 * h), "conv_w": (d["taps"], h),
            "w_out": (h, h)}


def _attn_shapes(d: dict) -> dict:
    h, dh = d["h"], d["d"]
    return {"norm_in": (h,), "wq": (h, d["heads"] * dh),
            "wk": (h, d["kv"] * dh), "wv": (h, d["kv"] * dh),
            "q_norm": (dh,), "k_norm": (dh,), "wo": (d["heads"] * dh, h)}


def param_shapes(cfg: dict) -> dict:
    """Matrices [in, out]; the held experts stacked in the order of
    `experts_held`; no head (the embedding's rows are it)."""
    d = dims(cfg)
    h, e, f = d["h"], len(d["held"]), d["moe_ff"]
    dense = {"norm_pre_mlp": (h,), "w_gate": (h, d["ff"]),
             "w_up": (h, d["ff"]), "w_down": (d["ff"], h)}
    moe = {"norm_pre_mlp": (h,), "router": (h, d["router"]),
           "router_bias": (d["router"],), "eg": (e, h, f), "eu": (e, h, f),
           "ed": (e, f, h)}
    return {"tok_emb": (d["vocab"], h), "final_norm": (h,),
            "layers": [dict(_conv_shapes(d) if kind == "conv"
                            else _attn_shapes(d),
                            **(dense if i < d["dense"] else moe))
                       for i, kind in enumerate(d["kinds"])]}


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


def conv_params(cfg: dict) -> int:
    """The two matrices of one short-convolution layer."""
    d = dims(cfg)
    return 4 * d["h"] * d["h"]


def attn_params(cfg: dict) -> int:
    """The four projections of one attention layer."""
    d = dims(cfg)
    return 2 * d["h"] * d["d"] * (d["heads"] + d["kv"])


def expert_params(cfg: dict) -> int:
    d = dims(cfg)
    return 3 * d["h"] * d["moe_ff"]


def cell_bytes(cfg: dict) -> int:
    """What one token keeps in one attention layer: a K row and a V
    row."""
    d = dims(cfg)
    return 2 * d["kv"] * d["d"] * BYTES


def state_bytes(cfg: dict) -> int:
    """What one short-convolution layer keeps for one slot: the last
    taps - 1 rows of `B * z`, float32."""
    d = dims(cfg)
    return STATE_BYTES * (d["taps"] - 1) * d["h"]


def mix_flops(cfg: dict) -> float:
    """The convolution's own arithmetic for one token, one layer: the
    two gates (1 a channel each) and the taps (2 a channel a tap)."""
    d = dims(cfg)
    return (2.0 + 2.0 * d["taps"]) * d["h"]


def _counts(cfg: dict):
    d = dims(cfg)
    n_conv = d["kinds"].count("conv")
    return d, n_conv, d["layers"] - n_conv, d["layers"] - d["dense"]


def _router_params(cfg: dict) -> int:
    d = dims(cfg)
    return d["h"] * d["router"]


def matmul_params(cfg: dict) -> int:
    """Matrix parameters this stage holds, the tied embedding once (as
    the head: the look-up reads a row a token)."""
    d, n_conv, n_attn, n_moe = _counts(cfg)
    return n_conv * conv_params(cfg) + n_attn * attn_params(cfg) \
        + d["dense"] * 3 * d["h"] * d["ff"] \
        + n_moe * (_router_params(cfg)
                   + len(d["held"]) * expert_params(cfg)) \
        + d["h"] * d["vocab"]


def attn_context_flops(cfg: dict, context: float) -> float:
    """Scores and values of one position over `context` live ones, all
    query heads, one attention layer (64 + 64 numbers a head a
    position)."""
    d = dims(cfg)
    return 2.0 * d["heads"] * 2 * d["d"] * context


def flops_per_token(cfg: dict, context: float) -> float:
    """One position through this stage: 2 per matrix parameter it
    multiplies through — of each expert layer the `top_k` experts it
    routes to (every one is held), not the 64 the program runs —, the
    convolutions' own arithmetic, and attention over `context` live
    positions in each attention layer."""
    d, n_conv, n_attn, n_moe = _counts(cfg)
    routed = d["top_k"] * len(d["held"]) / d["router"]
    through = n_conv * conv_params(cfg) + n_attn * attn_params(cfg) \
        + d["dense"] * 3 * d["h"] * d["ff"] \
        + n_moe * (_router_params(cfg) + routed * expert_params(cfg)) \
        + d["h"] * d["vocab"]
    return 2.0 * through + n_conv * mix_flops(cfg) \
        + n_attn * attn_context_flops(cfg, context)


def experts_hit(cfg: dict, rows: float) -> float:
    """Of one layer's held experts, how many get at least one of `rows`
    tokens at the mean, each token keeping `top_k` of the router's
    experts with no favourite: 64.0 of 64 at 128 rows, 63.96 at 120."""
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
    live K and V rows of the active slots once and one new pair a slot
    in each attention layer, and each active slot's tail read and
    written once in each short-convolution layer."""
    _, n_conv, n_attn, n_moe = _counts(cfg)
    unread = _unread(cfg, n_moe, slots, experts_hit) * expert_params(cfg)
    return (matmul_params(cfg) - unread) * BYTES \
        + (live_cells + slots) * n_attn * cell_bytes(cfg) \
        + 2.0 * slots * n_conv * state_bytes(cfg)


def moe_step(cfg: dict, rows: float, assignments_held: float,
             experts_hit: float):
    """(operations, bytes) the expert layers of one step require, from
    the program's counts summed over its expert layers: `rows` tokens a
    layer through the router, `assignments_held` token-expert pairs
    (every pair: all experts are held), `experts_hit` experts that got
    at least one. No shared expert."""
    _, _, _, n_moe = _counts(cfg)
    flops = 2.0 * (assignments_held * expert_params(cfg)
                   + n_moe * rows * _router_params(cfg))
    nbytes = BYTES * (experts_hit * expert_params(cfg)
                      + n_moe * _router_params(cfg))
    return flops, nbytes


def gqa_step(cfg: dict, rows: float, live_cells: float):
    """(operations, bytes) the attention layers of one step require:
    the four projections' weights once and `rows` tokens through them,
    the `live_cells` K and V rows of the active slots once and attended
    over, one pair written a slot."""
    _, _, n_attn, _ = _counts(cfg)
    flops = n_attn * (2.0 * rows * attn_params(cfg)
                      + attn_context_flops(cfg, live_cells))
    nbytes = n_attn * (attn_params(cfg) * BYTES
                       + (live_cells + rows) * cell_bytes(cfg))
    return flops, nbytes


def conv_step(cfg: dict, rows: float):
    """(operations, bytes) the short-convolution layers of one step
    require: each layer's two matrices read once and `rows` tokens
    through them, the gates and taps, each row's tail read and written
    once in float32."""
    _, n_conv, _, _ = _counts(cfg)
    flops = n_conv * rows * (2.0 * conv_params(cfg) + mix_flops(cfg))
    nbytes = n_conv * (conv_params(cfg) * BYTES
                       + 2.0 * rows * state_bytes(cfg))
    return flops, nbytes


# ---------------------------------------------------------- the model
def fp8(a):
    """Round to float8 e4m3 and back: the precision below bfloat16."""
    import jax.numpy as jnp

    return a.astype(jnp.float8_e4m3fn).astype(a.dtype)


def bf16(a):
    import jax.numpy as jnp

    return a.astype(jnp.bfloat16).astype(a.dtype)


ROUND = {None: lambda a: a, "fp8": fp8, "bfloat16": bf16}


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


def expert_ffn(lp, xn, cfg: dict, control=None, held=None):
    """The expert layer's feed-forward on normed input `xn` [.., h]:
    sigmoid scores over all experts, the `top_k` largest of score +
    bias, their own scores renormalised over their sum + 1e-6 and
    scaled, the terms of the experts in `held` (default
    `experts_held`; stacked in `lp` in that order). No shared expert."""
    import jax
    import jax.numpy as jnp

    d, mm = dims(cfg), _mm(control)
    held = d["held"] if held is None else held
    scores = jax.nn.sigmoid(mm(xn, lp["router"]))
    _, top_i = jax.lax.top_k(scores + lp["router_bias"], d["top_k"])
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    top_w = d["scale"] * top_s / (jnp.sum(top_s, axis=-1, keepdims=True)
                                  + d["route_eps"])

    def term(y, expert):
        e, wg, wu, wd = expert
        w = jnp.sum(jnp.where(top_i == e, top_w, 0.0), axis=-1)
        return y + w[..., None] * _mlp(mm, xn, wg, wu, wd), None

    y, _ = jax.lax.scan(
        term, jnp.zeros_like(xn),
        (jnp.asarray(held, jnp.int32), lp["eg"], lp["eu"], lp["ed"]))
    return y


def conv_mix(lp, u, cfg: dict, control=None):
    """One short-convolution layer's token mixing over whole sequences:
    normed input `u` [N, T, h] -> [N, T, h], the taps as shifted
    products of the whole sequence behind taps - 1 rows of zeros."""
    import jax.numpy as jnp

    d, mm = dims(cfg), _mm(control)
    t, taps = u.shape[1], d["taps"]
    b, c, z = jnp.split(mm(u, lp["w_in"]), 3, axis=-1)
    w = lp["conv_w"].astype(jnp.float32)
    pad = jnp.pad(b * z, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(w[j] * pad[:, j:j + t] for j in range(taps))
    return mm(c * conv, lp["w_out"])


def _rotary(x, theta: float):
    """x [N, T, H, D] rotated by its position on axis 1: half-split
    pairs (i with i + D/2), angle pos * theta^(-2i/D)."""
    import jax.numpy as jnp

    t, half = x.shape[1], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv      # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attn_mix(lp, u, cfg: dict, control=None):
    """One attention layer's token mixing: full causal attention, every
    key and value head repeated over its group of query heads."""
    import jax
    import jax.numpy as jnp

    hp = jax.lax.Precision.HIGHEST
    d, mm, rnd = dims(cfg), _mm(control), ROUND[control]
    heads, kv, dh = d["heads"], d["kv"], d["d"]
    n, t, _ = u.shape
    q = mm(u, lp["wq"]).reshape(n, t, heads, dh)
    k = mm(u, lp["wk"]).reshape(n, t, kv, dh)
    v = mm(u, lp["wv"]).reshape(n, t, kv, dh)
    q = _rotary(_rms(q, lp["q_norm"], d["eps"]), d["theta"])
    k = _rotary(_rms(k, lp["k_norm"], d["eps"]), d["theta"])
    k, v = (jnp.repeat(a, heads // kv, axis=2) for a in (k, v))
    causal = jnp.tril(jnp.ones((t, t), bool))
    att = []
    for g in range(0, heads, HEAD_GROUP):
        hs = slice(g, g + HEAD_GROUP)
        s = jnp.einsum("nthd,nuhd->nhtu", rnd(q[:, :, hs]),
                       rnd(k[:, :, hs]), precision=hp) / math.sqrt(dh)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        att.append(jnp.einsum("nhtu,nuhd->nthd",
                              rnd(jax.nn.softmax(s, axis=-1)),
                              rnd(v[:, :, hs]), precision=hp))
    return mm(jnp.concatenate(att, axis=2).reshape(n, t, heads * dh),
              lp["wo"])


def logits_fn(params, tokens, cfg: dict, control=None):
    """tokens [N, T] -> logits [N, T, vocab], float32. `control` None is
    the reference; "fp8" and "bfloat16" keep float32 arithmetic and
    round the operands of every matrix product (weights, activations,
    keys, values, softmax weights, the router's, the head's) to that
    precision."""
    import jax
    import jax.numpy as jnp

    d, mm, rnd = dims(cfg), _mm(control), ROUND[control]
    x = params["tok_emb"][tokens].astype(jnp.float32)
    for lp, kind in zip(params["layers"], d["kinds"]):
        mix = conv_mix if kind == "conv" else attn_mix
        x = x + mix(lp, _rms(x, lp["norm_in"], d["eps"]), cfg, control)
        xn = _rms(x, lp["norm_pre_mlp"], d["eps"])
        if "router" in lp:
            x = x + expert_ffn(lp, xn, cfg, control)
        else:
            x = x + _mlp(mm, xn, lp["w_gate"], lp["w_up"], lp["w_down"])
    # the tied head: the embedding's rows, no transpose made
    return jnp.einsum(
        "ntd,vd->ntv", rnd(_rms(x, params["final_norm"], d["eps"])),
        rnd(params["tok_emb"].astype(jnp.float32)),
        precision=jax.lax.Precision.HIGHEST)


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
