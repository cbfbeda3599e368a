"""Granite-4.0-H-Small (`granitemoehybrid`; the `config.json` named in
`configs/granite-4.0-h-small.json`; Hugging Face `transformers`
`GraniteMoeHybrid*`), plain, as one chip of a group of four holds it:
the experts it holds of every layer, the shared expert, the
vocabulary's slice.

Full causal forward pass over whole sequences in float32 `jax.numpy` at
`highest` matmul precision: no cache, no pages, no chunks, no state
carried between calls. The Mamba-2 recurrence runs as a SEQUENTIAL scan
over the positions, one token at a time, as its equations are written
(not the chunked form the program's prefill uses); attention repeats
every key and value head over its group of query heads and runs a group
of heads at a time. The weights come in as the program stores them
(bfloat16) and are raised to float32 a matrix, and an expert, at a
time.

    x_0 = 12 E[id]
    a = x + 0.22 Mix(norm(x));  y = a + 0.22 (MoE(norm(a)) + Shared(norm(a)))
                                                   RMSNorm, eps 1e-5
    Mix = Mamba-2 (layer_types "mamba"), u = norm(x), 128 heads of 64,
        d_state 128, one group, d_inner 8,192 (expand 2), 4 taps:
        [z | xBC | dt] = u W_in (4096 x (8192 + 8448 + 128), no bias);
        xBC = silu(causal depthwise conv(xBC) + conv_b) over 8,448
        channels; x (128 x 64), B (128), C (128) = xBC;
        delta = softplus(dt + dt_bias), no clamp; A = -exp(A_log);
        S_t = exp(delta_t A) S_{t-1} + delta_t x_t (x) B_t a head
        (64 x 128), y_t = S_t C_t + D x_t;
        Mix = (rmsnorm(y * silu(z)) w) W_out over all 8,192 channels
    Mix = attention (layer_types "attention"): q = u W_q (32 x 128),
        k = u W_k, v = u W_v (8 x 128), no rotation (position_embedding_type
        nope), no norm a head; causal softmax(q k^T / 128) v
        (attention_multiplier 1/128), query head h on K/V head h // 4;
        W_o
    MoE: the 10 largest of the router's 72 logits, softmax over those
        10, sum over the held experts of w_i E_i(x), E(x) =
        W_2(silu(W_1 x) * W_3 x) of width 768; Shared: the same form of
        width 1,536
    logits = norm(x_L) E^T / 16, E the embedding (tied)

Departures, shared with the program: layers 0-9 of 40; 18 of 72 routed
experts held (ids 0-17), so an expert layer's routed part is this
chip's partial sum; 25,088 of 100,352 vocabulary rows.

`final_states` gives each row's Mamba-2 states after its first tokens,
from the same sequential scan: what a slot's state holds there. Also
here: the operations and bytes this chip's share requires. It imports
nothing of the program.
"""

from __future__ import annotations

HEAD_GROUP = 8      # query heads a block of attention scores holds at a time
BYTES = 2           # the configuration's stored precision: bfloat16
STATE_BYTES = 4     # the Mamba-2 state and the convolution's tail: float32
KINDS = {"mamba": "mamba", "attention": "attn"}


def dims(cfg: dict) -> dict:
    d = {"h": "hidden_size", "heads": "num_attention_heads",
         "kv": "num_key_value_heads", "moe_ff": "intermediate_size",
         "shared_ff": "shared_intermediate_size",
         "top_k": "num_experts_per_tok", "layers": "num_hidden_layers",
         "vocab": "vocab_size", "router": "router_experts",
         "ssm_heads": "mamba_n_heads", "p": "mamba_d_head",
         "n": "mamba_d_state", "taps": "mamba_d_conv",
         "expand": "mamba_expand", "groups": "mamba_n_groups"}
    out = {k: int(cfg[v]) for k, v in d.items()}
    # the attention head size: hidden / heads (`assumed` in the file)
    out["d"] = out["h"] // out["heads"]
    out["inner"] = out["ssm_heads"] * out["p"]
    if out["inner"] != out["expand"] * out["h"] or out["groups"] != 1:
        raise ValueError("mamba_n_heads x mamba_d_head must be mamba_expand "
                         "x hidden_size, over one group")
    out["channels"] = out["inner"] + 2 * out["n"]
    out["held"] = [int(e) for e in cfg["experts_held"]]
    out["kinds"] = [KINDS[k] for k in cfg["layer_types"]]
    if len(out["kinds"]) != out["layers"]:
        raise ValueError(f"layer_types names {len(out['kinds'])} layers, "
                         f"num_hidden_layers {out['layers']}")
    for k in ("rms_norm_eps", "embedding_multiplier", "residual_multiplier",
              "logits_scaling", "attention_multiplier"):
        out[k] = float(cfg[k])
    return out


def _mamba_shapes(d: dict) -> dict:
    h, c, heads = d["h"], d["channels"], d["ssm_heads"]
    return {"norm_in": (h,), "w_in": (h, d["inner"] + c + heads),
            "conv_w": (d["taps"], c), "conv_b": (c,), "dt_bias": (heads,),
            "A_log": (heads,), "D": (heads,), "norm_y": (d["inner"],),
            "w_out": (d["inner"], h)}


def _attn_shapes(d: dict) -> dict:
    h, dh = d["h"], d["d"]
    return {"norm_in": (h,), "wq": (h, d["heads"] * dh),
            "wk": (h, d["kv"] * dh), "wv": (h, d["kv"] * dh),
            "wo": (d["heads"] * dh, h)}


def param_shapes(cfg: dict) -> dict:
    """Matrices [in, out]; the held experts stacked in the order of
    `experts_held`; the shared expert `sg`, `su`, `sd`; no head (the
    embedding's rows are it)."""
    d = dims(cfg)
    h, e, f, fs = d["h"], len(d["held"]), d["moe_ff"], d["shared_ff"]
    moe = {"norm_pre_mlp": (h,), "router": (h, d["router"]),
           "eg": (e, h, f), "eu": (e, h, f), "ed": (e, f, h),
           "sg": (h, fs), "su": (h, fs), "sd": (fs, h)}
    return {"tok_emb": (d["vocab"], h), "final_norm": (h,),
            "layers": [dict(_mamba_shapes(d) if kind == "mamba"
                            else _attn_shapes(d), **moe)
                       for kind in d["kinds"]]}


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


def mamba_params(cfg: dict) -> int:
    """The two matrices of one Mamba-2 layer (its taps and vectors,
    0.05% of it, are left out)."""
    d = dims(cfg)
    return d["h"] * (2 * d["inner"] + 2 * d["n"] + d["ssm_heads"]) \
        + d["inner"] * d["h"]


def attn_params(cfg: dict) -> int:
    """The four projections of one attention layer."""
    d = dims(cfg)
    return 2 * d["h"] * d["d"] * (d["heads"] + d["kv"])


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


def state_bytes(cfg: dict) -> int:
    """What one Mamba-2 layer keeps for one slot: the matrix a head and
    the convolution's last taps - 1 inputs, float32 (4,295,680 bytes at
    the published widths)."""
    d = dims(cfg)
    return STATE_BYTES * (d["ssm_heads"] * d["p"] * d["n"]
                          + (d["taps"] - 1) * d["channels"])


def scan_flops(cfg: dict) -> float:
    """The Mamba-2 layer's own arithmetic for one token: the taps (2 a
    channel a tap) and their bias, the decay, the outer product and the
    add on every number of the state (3), its read against C (2), D x
    and the gate (4 a channel)."""
    d = dims(cfg)
    return 2.0 * d["taps"] * d["channels"] + d["channels"] \
        + 5.0 * d["ssm_heads"] * d["p"] * d["n"] + 4.0 * d["inner"]


def _counts(cfg: dict):
    d = dims(cfg)
    n_mamba = d["kinds"].count("mamba")
    return d, n_mamba, d["layers"] - n_mamba, d["layers"]


def matmul_params(cfg: dict) -> int:
    """Matrix parameters this chip holds, the tied embedding once (as
    the head: the look-up reads a row a token)."""
    d, n_mamba, n_attn, n_moe = _counts(cfg)
    return n_mamba * mamba_params(cfg) + n_attn * attn_params(cfg) \
        + n_moe * (_moe_fixed_params(cfg)
                   + len(d["held"]) * expert_params(cfg)) \
        + d["h"] * d["vocab"]


def attn_context_flops(cfg: dict, context: float) -> float:
    """Scores and values of one position over `context` live ones, all
    query heads, one attention layer (128 + 128 numbers a head a
    position)."""
    d = dims(cfg)
    return 2.0 * d["heads"] * 2 * d["d"] * context


def flops_per_token(cfg: dict, context: float) -> float:
    """One position through this chip's share: 2 per matrix parameter
    it multiplies through — of each expert layer the `top_k` experts
    it routes to that are held, at the mean (10 x 18 / 72), not the 18
    the layer holds —, the Mamba-2 layers' own arithmetic, and
    attention over `context` live positions in each attention layer."""
    d, n_mamba, n_attn, n_moe = _counts(cfg)
    routed = d["top_k"] * len(d["held"]) / d["router"]
    through = matmul_params(cfg) - n_moe * (
        len(d["held"]) - routed) * expert_params(cfg)
    return 2.0 * through + n_mamba * scan_flops(cfg) \
        + n_attn * attn_context_flops(cfg, context)


def experts_hit(cfg: dict, rows: float) -> float:
    """Of one layer's held experts, how many get at least one of `rows`
    tokens at the mean, each token keeping `top_k` of the router's
    experts with no favourite: 18.0 of 18 at 128 rows."""
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
    in each attention layer, and each active slot's state read and
    written once in float32 in each Mamba-2 layer."""
    _, n_mamba, n_attn, n_moe = _counts(cfg)
    unread = _unread(cfg, n_moe, slots, experts_hit) * expert_params(cfg)
    return (matmul_params(cfg) - unread) * BYTES \
        + (live_cells + slots) * n_attn * cell_bytes(cfg) \
        + 2.0 * slots * n_mamba * state_bytes(cfg)


def state_share(cfg: dict, live_cells: float, slots: float) -> float:
    """The Mamba-2 states' part of `decode_step_bytes`."""
    _, n_mamba, _, _ = _counts(cfg)
    return 2.0 * slots * n_mamba * state_bytes(cfg) \
        / decode_step_bytes(cfg, live_cells, slots)


def moe_step(cfg: dict, rows: float, assignments_held: float,
             experts_hit: float):
    """(operations, bytes) the expert layers of one step require, from
    the program's counts summed over its expert layers: `rows` tokens a
    layer through the router and the shared expert, `assignments_held`
    token-expert pairs that fell on held experts, `experts_hit` held
    experts that got at least one."""
    _, _, _, n_moe = _counts(cfg)
    flops = 2.0 * (assignments_held * expert_params(cfg)
                   + n_moe * rows * _moe_fixed_params(cfg))
    nbytes = BYTES * (experts_hit * expert_params(cfg)
                      + n_moe * _moe_fixed_params(cfg))
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


def ssd_step(cfg: dict, rows: float):
    """(operations, bytes) the Mamba-2 layers of one step require: each
    layer's two matrices read once and `rows` tokens through them, the
    recurrence's own arithmetic, each row's state (the matrices and the
    tail) read and written once in float32."""
    _, n_mamba, _, _ = _counts(cfg)
    flops = n_mamba * rows * (2.0 * mamba_params(cfg) + scan_flops(cfg))
    nbytes = n_mamba * (mamba_params(cfg) * BYTES
                        + 2.0 * rows * state_bytes(cfg))
    return flops, nbytes


# ---------------------------------------------------------- the model
def fp8(a):
    """Round to float8 e4m3 and back: the precision below bfloat16."""
    import jax.numpy as jnp

    return a.astype(jnp.float8_e4m3fn).astype(a.dtype)


def bf16(a):
    """Round to bfloat16's 8 bits of mantissa, keeping the type: with
    `reduce_precision`, which the chip's compiler keeps, where a cast
    there and back inside a fusion may be carried in float32."""
    import jax

    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _same(a):
    return a


# how each side rounds the operands of every product (ROUND) and the
# Mamba-2 state after every token (STATE): "bfloat16" and "fp8" round
# both, "state_bfloat16" the state alone
ROUND = {None: _same, "fp8": fp8, "bfloat16": bf16, "state_bfloat16": _same}
STATE = {None: _same, "fp8": fp8, "bfloat16": bf16, "state_bfloat16": bf16}


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


def mamba_mix(lp, u, cfg: dict, control=None):
    """One Mamba-2 layer's token mixing over whole sequences: normed
    input `u` [N, T, h] -> [N, T, h], the recurrence a token at a time
    from a zero state."""
    return _mamba(lp, u, cfg, control)[0]


def _mamba(lp, u, cfg: dict, control=None, lengths=None):
    """(`mamba_mix`'s output, and with `lengths` [N] each row's state
    S [N, H, P, N_state] after its first `lengths` tokens, else None)."""
    import jax
    import jax.numpy as jnp

    d, mm, rnd, keep = dims(cfg), _mm(control), ROUND[control], \
        STATE[control]
    n, t, _ = u.shape
    inner, heads, taps = d["inner"], d["ssm_heads"], d["taps"]
    zxd = mm(u, lp["w_in"])
    z, xbc, dt = (zxd[..., :inner], zxd[..., inner:inner + d["channels"]],
                  zxd[..., inner + d["channels"]:])
    w = lp["conv_w"].astype(jnp.float32)
    pad = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(w[j] * pad[:, j:j + t] for j in range(taps))
                      + lp["conv_b"])
    x = xbc[..., :inner].reshape(n, t, heads, d["p"])
    b, c = xbc[..., inner:inner + d["n"]], xbc[..., inner + d["n"]:]
    delta = jax.nn.softplus(dt + lp["dt_bias"])               # [N, T, H]
    a = -jnp.exp(lp["A_log"])

    def token(s, row):
        xt, bt, ct, dt_ = row
        s = keep(jnp.exp(dt_ * a)[..., None, None] * s
                 + (dt_[..., None] * rnd(xt))[..., None]
                 * rnd(bt)[:, None, None, :])
        return s, jnp.sum(rnd(s) * rnd(ct)[:, None, None, :], axis=-1)

    s0 = jnp.zeros((n, heads, d["p"], d["n"]), jnp.float32)
    rows = tuple(jnp.swapaxes(v, 0, 1) for v in (x, b, c, delta))
    if lengths is None:
        _, ys = jax.lax.scan(token, s0, rows)
        end = None
    else:
        def kept(carry, row):
            (s, end), (i, row) = carry, row
            s, y = token(s, row)
            # a row's state stops at its own length: what it holds
            # after its last token, whatever the padding after it
            take = (i < lengths)[:, None, None, None]
            return (s, jnp.where(take, s, end)), y

        (_, end), ys = jax.lax.scan(kept, (s0, s0),
                                    (jnp.arange(t), rows))
    y = jnp.swapaxes(ys, 0, 1) + lp["D"][:, None] * x          # [N,T,H,P]
    g = y.reshape(n, t, inner) * jax.nn.silu(z)
    return mm(_rms(g, lp["norm_y"], d["rms_norm_eps"]), lp["w_out"]), end


def attn_mix(lp, u, cfg: dict, control=None):
    """One attention layer's token mixing: full causal attention, no
    rotation, every key and value head repeated over its group of
    query heads, scores times `attention_multiplier`."""
    import jax
    import jax.numpy as jnp

    hp = jax.lax.Precision.HIGHEST
    d, mm, rnd = dims(cfg), _mm(control), ROUND[control]
    heads, kv, dh = d["heads"], d["kv"], d["d"]
    n, t, _ = u.shape
    q = mm(u, lp["wq"]).reshape(n, t, heads, dh)
    k = mm(u, lp["wk"]).reshape(n, t, kv, dh)
    v = mm(u, lp["wv"]).reshape(n, t, kv, dh)
    k, v = (jnp.repeat(a, heads // kv, axis=2) for a in (k, v))
    causal = jnp.tril(jnp.ones((t, t), bool))
    att = []
    for g in range(0, heads, HEAD_GROUP):
        hs = slice(g, g + HEAD_GROUP)
        s = jnp.einsum("nthd,nuhd->nhtu", rnd(q[:, :, hs]),
                       rnd(k[:, :, hs]), precision=hp) \
            * d["attention_multiplier"]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        att.append(jnp.einsum("nhtu,nuhd->nthd",
                              rnd(jax.nn.softmax(s, axis=-1)),
                              rnd(v[:, :, hs]), precision=hp))
    return mm(jnp.concatenate(att, axis=2).reshape(n, t, heads * dh),
              lp["wo"])


def expert_ffn(lp, xn, cfg: dict, control=None, held=None):
    """The expert layer's feed-forward on normed input `xn` [.., h]:
    the `top_k` largest router logits, a softmax over those alone
    (`GraniteMoeTopKGating`), the terms of the experts in `held`
    (default `experts_held`; stacked in `lp` in that order), plus the
    shared expert."""
    import jax
    import jax.numpy as jnp

    d, mm = dims(cfg), _mm(control)
    held = d["held"] if held is None else held
    top_l, top_i = jax.lax.top_k(mm(xn, lp["router"]), d["top_k"])
    top_w = jax.nn.softmax(top_l, axis=-1)

    def term(y, expert):
        e, wg, wu, wd = expert
        w = jnp.sum(jnp.where(top_i == e, top_w, 0.0), axis=-1)
        return y + w[..., None] * _mlp(mm, xn, wg, wu, wd), None

    y, _ = jax.lax.scan(
        term, jnp.zeros_like(xn),
        (jnp.asarray(held, jnp.int32), lp["eg"], lp["eu"], lp["ed"]))
    return y + _mlp(mm, xn, lp["sg"], lp["su"], lp["sd"])


def _stream(params, tokens, cfg: dict, control=None, lengths=None):
    """The stream after the last layer [N, T, h], and with `lengths`
    each Mamba-2 layer's states after each row's first `lengths` tokens
    (a list, in layer order)."""
    import jax.numpy as jnp

    d = dims(cfg)
    eps, res = d["rms_norm_eps"], d["residual_multiplier"]
    x = params["tok_emb"][tokens].astype(jnp.float32) \
        * d["embedding_multiplier"]
    states = []
    for lp, kind in zip(params["layers"], d["kinds"]):
        u = _rms(x, lp["norm_in"], eps)
        if kind == "mamba":
            m, end = _mamba(lp, u, cfg, control, lengths)
            states.append(end)
        else:
            m = attn_mix(lp, u, cfg, control)
        x = x + res * m
        x = x + res * expert_ffn(lp, _rms(x, lp["norm_pre_mlp"], eps), cfg,
                                 control)
    return x, states


def logits_fn(params, tokens, cfg: dict, control=None):
    """tokens [N, T] -> logits [N, T, vocab], float32. `control` None is
    the reference; "fp8" and "bfloat16" keep float32 arithmetic and
    round the operands of every product (weights, activations, keys,
    values, softmax weights, the router's, the head's, the state's
    products with x, B and C) and the Mamba-2 state after every token
    to that precision; "state_bfloat16" rounds the state alone."""
    import jax
    import jax.numpy as jnp

    d, rnd = dims(cfg), ROUND[control]
    x, _ = _stream(params, tokens, cfg, control)
    # the tied head: the embedding's rows, no transpose made
    return jnp.einsum(
        "ntd,vd->ntv", rnd(_rms(x, params["final_norm"], d["rms_norm_eps"])),
        rnd(params["tok_emb"].astype(jnp.float32)),
        precision=jax.lax.Precision.HIGHEST) / d["logits_scaling"]


def final_states(params, tokens, lengths, cfg: dict, control=None):
    """Each row's Mamba-2 states after its first `lengths` tokens of
    `tokens` [N, T]: [N, Mamba-2 layers, H, P, N_state] float32, the
    matrices the recurrence carries from token to token (`control` as
    `logits_fn` has it)."""
    import jax.numpy as jnp

    last = max(i for i, k in enumerate(dims(cfg)["kinds"]) if k == "mamba")
    cut = dict(params, layers=params["layers"][:last + 1])
    _, states = _stream(cut, tokens, dict(
        cfg, layer_types=cfg["layer_types"][:last + 1],
        num_hidden_layers=last + 1), control, jnp.asarray(lengths))
    return jnp.stack(states, axis=1)


def served_gaps(params, tokens, cfg: dict, control=None):
    """For each position p < T-1 of each row: how far the reference's
    logit of the token at p+1 lies below the reference's best logit at
    p. With a `control`, the token judged is the one that side puts
    first instead of the one in `tokens`. [N, T-1]."""
    import jax.numpy as jnp

    ref = logits_fn(params, tokens, cfg)[:, :-1]
    if control is None:
        judged = tokens[:, 1:]
    else:
        judged = jnp.argmax(logits_fn(params, tokens, cfg, control)[:, :-1],
                            axis=-1)
    got = jnp.take_along_axis(ref, judged[..., None], axis=-1)[..., 0]
    return jnp.max(ref, axis=-1) - got
