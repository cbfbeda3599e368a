"""openPangu-Ultra-MoE-718B (`pangu_ultra_moe`; the `config.json` named
in `configs/pangu-ultra-moe-718b.json`), plain, as one chip of sixteen
holds it.

Full causal forward pass over whole sequences in float32 `jax.numpy` at
`highest` matmul precision: no cache, no pages, no chunks, no absorbed
products. The weights come in as the program stores them (bfloat16) and
are raised to float32 one matrix, and one expert, at a time; attention
runs a group of heads at a time, so that a row of 4,096 tokens at the
published widths fits beside 9.84 GB of weights.

    a = x + norm_post_attn(MLA(norm_in(x)))
    y = a + norm_post_mlp(FFN(norm_pre_mlp(a)))           RMSNorm, eps 1e-5
    MLA: c_q = norm(x W_qa); [q_nope | q_rope] = c_q W_qb per head;
         [c_kv | k_rope] = x W_kva; c_kv = norm(c_kv); rotary on q_rope
         and on k_rope (shared by the heads); [k_nope | v] = c_kv W_kvb
         per head; softmax((q_nope.k_nope + q_rope.k_rope)/sqrt(192)) v;
         W_o
    FFN: W_down(silu(W_gate x) * W_up x) in the leading dense layer;
         after it s = sigmoid(x W_g) over all 256 experts, the 8
         largest, w = 2.5 s_top / sum(s_top),
         sum_{i held} w_i E_i(x) + E_shared(x)

Departures, shared with the program: of each layer's 256 routed experts
only those in `experts_held` are computed (what the others would add is
left out, and that partial result goes on); the vocabulary is its first
`vocab_size` rows; no multi-token-prediction layer; rotary pairs
dimension i with i + 32 (the checkpoint's interleaved pairs, permuted).

Also here: the operations and bytes this chip's share requires. It
imports nothing of the program.
"""

from __future__ import annotations

HEAD_GROUP = 8      # heads a block of attention scores holds at a time
BYTES = 2           # the configuration's stored precision: bfloat16


def dims(cfg: dict) -> dict:
    d = {"h": "hidden_size", "heads": "num_attention_heads",
         "q_rank": "q_lora_rank", "kv_rank": "kv_lora_rank",
         "nope": "qk_nope_head_dim", "rope": "qk_rope_head_dim",
         "v": "v_head_dim", "ff": "intermediate_size",
         "moe_ff": "moe_intermediate_size", "top_k": "num_experts_per_tok",
         "shared": "n_shared_experts", "layers": "num_hidden_layers",
         "dense": "first_k_dense_replace", "vocab": "vocab_size"}
    out = {k: int(cfg[v]) for k, v in d.items()}
    out["held"] = [int(e) for e in cfg["experts_held"]]
    out["router"] = int(cfg["router_experts"])
    return out


def param_shapes(cfg: dict) -> dict:
    """Matrices [in, out]; the held experts stacked in the order of
    `experts_held`."""
    d = dims(cfg)
    h, heads = d["h"], d["heads"]
    attn = {"norm_in": (h,), "wq_a": (h, d["q_rank"]),
            "q_norm": (d["q_rank"],),
            "wq_b": (d["q_rank"], heads * (d["nope"] + d["rope"])),
            "wkv_a": (h, d["kv_rank"] + d["rope"]),
            "kv_norm": (d["kv_rank"],),
            "wkv_b": (d["kv_rank"], heads * (d["nope"] + d["v"])),
            "wo": (heads * d["v"], h), "norm_post_attn": (h,),
            "norm_pre_mlp": (h,), "norm_post_mlp": (h,)}
    e, f, fs = len(d["held"]), d["moe_ff"], d["shared"] * d["moe_ff"]
    dense = dict(attn, w_gate=(h, d["ff"]), w_up=(h, d["ff"]),
                 w_down=(d["ff"], h))
    moe = dict(attn, router=(h, d["router"]), eg=(e, h, f), eu=(e, h, f),
               ed=(e, f, h), sg=(h, fs), su=(h, fs), sd=(fs, h))
    return {"tok_emb": (d["vocab"], h), "final_norm": (h,),
            "head": (h, d["vocab"]),
            "layers": [dict(dense if i < d["dense"] else moe)
                       for i in range(d["layers"])]}


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


def attn_params(cfg: dict) -> int:
    """The five projections of one layer's attention."""
    d = dims(cfg)
    return d["h"] * d["q_rank"] \
        + d["q_rank"] * d["heads"] * (d["nope"] + d["rope"]) \
        + d["h"] * (d["kv_rank"] + d["rope"]) \
        + d["kv_rank"] * d["heads"] * (d["nope"] + d["v"]) \
        + d["heads"] * d["v"] * d["h"]


def expert_params(cfg: dict) -> int:
    d = dims(cfg)
    return 3 * d["h"] * d["moe_ff"]


def cell_bytes(cfg: dict) -> int:
    """One cached row: a token, a layer."""
    d = dims(cfg)
    return (d["kv_rank"] + d["rope"]) * BYTES


def _moe_fixed_params(cfg: dict) -> int:
    """What every token of an expert layer multiplies through whatever
    it routes to: the router and the shared expert."""
    d = dims(cfg)
    return d["h"] * d["router"] + d["shared"] * expert_params(cfg)


def matmul_params(cfg: dict) -> int:
    """Matrix parameters this chip holds (not the embedding look-up)."""
    d = dims(cfg)
    n_moe = d["layers"] - d["dense"]
    return d["layers"] * attn_params(cfg) \
        + d["dense"] * 3 * d["h"] * d["ff"] \
        + n_moe * (_moe_fixed_params(cfg)
                   + len(d["held"]) * expert_params(cfg)) \
        + d["h"] * d["vocab"]


def attn_context_flops(cfg: dict, context: float) -> float:
    """Scores and values of one position over `context` live ones, all
    heads, one layer, as the definition has them (192 and 128 numbers a
    head a position): the absorbed form does more (576 and 512) to read
    less, and the least work is what counts."""
    d = dims(cfg)
    return 2.0 * d["heads"] * (d["nope"] + d["rope"] + d["v"]) * context


def flops_per_token(cfg: dict, context: float) -> float:
    """One position through this chip's share: 2 per matrix parameter
    it multiplies through — of the routed experts the
    `top_k * held / router` (0.5) a layer that fall here at the mean —
    and attention over `context` live positions in each layer."""
    d = dims(cfg)
    n_moe = d["layers"] - d["dense"]
    routed = d["top_k"] * len(d["held"]) / d["router"]
    through = d["layers"] * attn_params(cfg) \
        + d["dense"] * 3 * d["h"] * d["ff"] \
        + n_moe * (_moe_fixed_params(cfg) + routed * expert_params(cfg)) \
        + d["h"] * d["vocab"]
    return 2.0 * through + d["layers"] * attn_context_flops(cfg, context)


def experts_hit(cfg: dict, rows: float) -> float:
    """Of one layer's held experts, how many get at least one of `rows`
    tokens at the mean, each token keeping `top_k` of the router's
    experts with no favourite: 9.8 of 16 at 30 rows, all 16 at the
    deployment's 512."""
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
    matrix that a row multiplies through once — of the expert layers'
    held experts those some row chose, not the others: `experts_hit`,
    summed over the layers, as the program counted them a step; where
    it is None the `experts_hit` that `slots` rows reach a layer at the
    mean —, the live latent rows of the active
    slots once, one new row per slot."""
    d = dims(cfg)
    n_moe = d["layers"] - d["dense"]
    unread = _unread(cfg, n_moe, slots, experts_hit) * expert_params(cfg)
    return (matmul_params(cfg) - unread) * BYTES \
        + (live_cells + slots) * d["layers"] * cell_bytes(cfg)


def moe_step(cfg: dict, rows: float, assignments_held: float,
             experts_hit: float):
    """(operations, bytes) the expert layers of one step require, from
    the program's counts summed over its expert layers: `rows` tokens a
    layer, `assignments_held` token-expert pairs that fell on held
    experts, `experts_hit` held experts that got at least one."""
    d = dims(cfg)
    n_moe = d["layers"] - d["dense"]
    flops = 2.0 * (assignments_held * expert_params(cfg)
                   + n_moe * rows * _moe_fixed_params(cfg))
    nbytes = BYTES * (experts_hit * expert_params(cfg)
                      + n_moe * _moe_fixed_params(cfg))
    return flops, nbytes


def mla_step(cfg: dict, rows: float, live_cells: float):
    """(operations, bytes) the attention of one step requires, all
    layers: the projections' weights once and `rows` tokens through
    them, the `live_cells` latent rows of the active slots once, one
    row written a slot."""
    d = dims(cfg)
    flops = d["layers"] * (2.0 * rows * attn_params(cfg)
                           + attn_context_flops(cfg, live_cells))
    nbytes = d["layers"] * (attn_params(cfg) * BYTES
                            + (live_cells + rows) * cell_bytes(cfg))
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


def _rotary(x, theta: float):
    """x [N, T, .., d] at positions 0..T-1: pairs (i, i + d/2)."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    ang = ang.reshape((1, x.shape[1]) + (1,) * (x.ndim - 3) + (half,))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def _mm(control):
    import jax
    import jax.numpy as jnp

    q = ROUND[control]
    return lambda a, w: jnp.matmul(q(a), q(w.astype(jnp.float32)),
                                   precision=jax.lax.Precision.HIGHEST)


def _mlp(mm, x, wg, wu, wd):
    import jax

    return mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)


def expert_ffn(lp, xn, cfg: dict, control=None):
    """The expert layer's feed-forward on normed input `xn` [.., h]:
    sigmoid scores over all experts, the `top_k` largest renormalised
    and scaled, the terms of the experts in `experts_held` (stacked in
    `lp` in that order) and the shared expert."""
    import jax
    import jax.numpy as jnp

    d, mm = dims(cfg), _mm(control)
    scores = jax.nn.sigmoid(mm(xn, lp["router"]))
    top_s, top_i = jax.lax.top_k(scores, d["top_k"])
    top_w = float(cfg["routed_scaling_factor"]) * top_s \
        / jnp.sum(top_s, axis=-1, keepdims=True)
    y = _mlp(mm, xn, lp["sg"], lp["su"], lp["sd"])
    for j, e in enumerate(d["held"]):
        w = jnp.sum(jnp.where(top_i == e, top_w, 0.0), axis=-1)
        y = y + w[..., None] * _mlp(mm, xn, lp["eg"][j], lp["eu"][j],
                                    lp["ed"][j])
    return y


def logits_fn(params, tokens, cfg: dict, control=None):
    """tokens [N, T] -> logits [N, T, vocab], float32. `control` None is
    the reference; "fp8" and "bfloat16" keep float32 arithmetic and
    round the operands of every matrix product (weights, activations,
    keys, values, the router's too) to that precision."""
    import jax
    import jax.numpy as jnp

    hp = jax.lax.Precision.HIGHEST
    f32 = jnp.float32
    q = ROUND[control]
    d = dims(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    heads, nope, rope, dv = d["heads"], d["nope"], d["rope"], d["v"]
    mm = _mm(control)

    n, t = tokens.shape
    x = params["tok_emb"][tokens].astype(f32)
    causal = jnp.tril(jnp.ones((t, t), bool))
    layers = params["layers"]
    for i in range(len(layers)):
        lp = layers[i]
        xn = _rms(x, lp["norm_in"], eps)
        cq = _rms(mm(xn, lp["wq_a"]), lp["q_norm"], eps)
        qh = mm(cq, lp["wq_b"]).reshape(n, t, heads, nope + rope)
        q_nope, q_rope = qh[..., :nope], _rotary(qh[..., nope:], theta)
        kv = mm(xn, lp["wkv_a"])
        c_kv = _rms(kv[..., :d["kv_rank"]], lp["kv_norm"], eps)
        k_rope = _rotary(kv[..., d["kv_rank"]:], theta)         # [N, T, R]
        kvh = mm(c_kv, lp["wkv_b"]).reshape(n, t, heads, nope + dv)
        k_nope, v = kvh[..., :nope], kvh[..., nope:]
        att = []
        for g in range(0, heads, HEAD_GROUP):
            hs = slice(g, g + HEAD_GROUP)
            s = (jnp.einsum("nthd,nuhd->nhtu", q(q_nope[:, :, hs]),
                            q(k_nope[:, :, hs]), precision=hp)
                 + jnp.einsum("nthr,nur->nhtu", q(q_rope[:, :, hs]),
                              q(k_rope), precision=hp)) \
                / jnp.sqrt(jnp.asarray(nope + rope, f32))
            s = jnp.where(causal[None, None], s, -jnp.inf)
            att.append(jnp.einsum("nhtu,nuhd->nthd",
                                  q(jax.nn.softmax(s, axis=-1)),
                                  q(v[:, :, hs]), precision=hp))
        att = jnp.concatenate(att, axis=2).reshape(n, t, heads * dv)
        x = x + _rms(mm(att, lp["wo"]), lp["norm_post_attn"], eps)
        xn = _rms(x, lp["norm_pre_mlp"], eps)
        if "router" in lp:
            y = expert_ffn(lp, xn, cfg, control)
        else:
            y = _mlp(mm, xn, lp["w_gate"], lp["w_up"], lp["w_down"])
        x = x + _rms(y, lp["norm_post_mlp"], eps)
    return mm(_rms(x, params["final_norm"], eps), params["head"])


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
