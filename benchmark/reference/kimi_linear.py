"""Kimi-Linear-48B-A3B (`kimi_linear`; the `config.json` named in
`configs/kimi-linear-48b-a3b.json`; "Kimi Linear", arXiv:2510.26692),
plain, as one chip of four that share each layer holds it.

Full causal forward pass over whole sequences in float32 `jax.numpy` at
`highest` matmul precision: no cache, no pages, no chunks, no state
carried between calls. The Kimi Delta Attention layers run as the
recurrence itself, token by token (`lax.scan` over positions); latent
attention expands every key and value and runs a group of heads at a
time. The weights come in as the program stores them (bfloat16) and are
raised to float32 a matrix, and an expert, at a time.

    a = x + Mix(norm(x));  y = a + FFN(norm(a))      RMSNorm, eps 1e-5
    KDA (layers in `kda_layers`), u = norm(x), head h of 32, D = 128:
        q~, k~, v~ = u W_q, u W_k, u W_v, each through a causal
        depthwise convolution of 4 taps over time (no bias) and SiLU;
        q = l2norm(q~_h) / sqrt(D), k = l2norm(k~_h), v = v~_h
        g = -exp(A_log_h) softplus(u W_f1 W_f2 + dt_bias)_h  (a channel)
        beta = sigmoid(u w_b)_h
        S' = Diag(e^g) S;  S = S' + beta k (v - S'^T k)^T;  o = S^T q
        out = [RMSNorm_h(o) * sigmoid(u W_g1 W_g2)_h] W_o
    MLA (layers in `full_attn_layers`): q = u W_q per head (128 + 64);
        [c | k_pe] = u W_kva, c = norm(c); no rotation on q_pe or k_pe;
        [k_nope | v] = c W_kvb per head;
        softmax((q_nope.k_nope + q_pe.k_pe)/sqrt(192)) v; W_o
    FFN: W_down(silu(W_gate x) * W_up x) in layer 1; after it
        s = sigmoid(x W_r) over all 256 experts, the 8 largest of
        s + bias, w = 2.446 s_top / sum(s_top),
        sum_{i held} w_i E_i(x) + E_shared(x)

Departures, shared with the program: of each layer's 256 routed experts
only those in `experts_held` are computed (what the others would add is
left out, and that partial sum goes on); the vocabulary is its first
`vocab_size` rows; layers 1-8 of 27.

Also here: the operations and bytes this chip's share requires. It
imports nothing of the program.
"""

from __future__ import annotations

import math

HEAD_GROUP = 4      # heads a block of attention scores holds at a time
BYTES = 2           # the configuration's stored precision: bfloat16
STATE_BYTES = 4     # the recurrent state's: float32
L2_EPS = 1e-6


def dims(cfg: dict) -> dict:
    d = {"h": "hidden_size", "heads": "num_attention_heads",
         "kv_rank": "kv_lora_rank", "nope": "qk_nope_head_dim",
         "rope": "qk_rope_head_dim", "v": "v_head_dim",
         "ff": "intermediate_size", "moe_ff": "moe_intermediate_size",
         "top_k": "num_experts_per_token", "shared": "num_shared_experts",
         "layers": "num_hidden_layers", "dense": "first_k_dense_replace",
         "vocab": "vocab_size"}
    out = {k: int(cfg[v]) for k, v in d.items()}
    lin = cfg["linear_attn_config"]
    out["held"] = [int(e) for e in cfg["experts_held"]]
    out["router"] = int(cfg["router_experts"])
    out["kda_heads"], out["kda_dim"] = int(lin["num_heads"]), \
        int(lin["head_dim"])
    out["taps"] = int(lin["short_conv_kernel_size"])
    # the low-rank gates' rank: the head size (`assumed` in the file)
    out["gate_rank"] = out["kda_dim"]
    kda = {int(i) for i in lin["kda_layers"]}
    out["kinds"] = ["kda" if i + 1 in kda else "mla"
                    for i in range(out["layers"])]
    assert {i + 1 for i, k in enumerate(out["kinds"]) if k == "mla"} \
        == {int(i) for i in lin["full_attn_layers"]}
    return out


def _kda_shapes(d: dict) -> dict:
    h, c, r = d["h"], d["kda_heads"] * d["kda_dim"], d["gate_rank"]
    return {"norm_in": (h,), "wq": (h, c), "wk": (h, c), "wv": (h, c),
            "conv_q": (d["taps"], c), "conv_k": (d["taps"], c),
            "conv_v": (d["taps"], c), "wf_a": (h, r), "wf_b": (r, c),
            "dt_bias": (c,), "A_log": (d["kda_heads"],),
            "wb": (h, d["kda_heads"]), "wg_a": (h, r), "wg_b": (r, c),
            "o_norm": (d["kda_dim"],), "wo": (c, h)}


def _mla_shapes(d: dict) -> dict:
    h, heads = d["h"], d["heads"]
    return {"norm_in": (h,), "wq": (h, heads * (d["nope"] + d["rope"])),
            "wkv_a": (h, d["kv_rank"] + d["rope"]),
            "kv_norm": (d["kv_rank"],),
            "wkv_b": (d["kv_rank"], heads * (d["nope"] + d["v"])),
            "wo": (heads * d["v"], h)}


def param_shapes(cfg: dict) -> dict:
    """Matrices [in, out]; the held experts stacked in the order of
    `experts_held`."""
    d = dims(cfg)
    h = d["h"]
    e, f, fs = len(d["held"]), d["moe_ff"], d["shared"] * d["moe_ff"]
    dense = {"norm_pre_mlp": (h,), "w_gate": (h, d["ff"]),
             "w_up": (h, d["ff"]), "w_down": (d["ff"], h)}
    moe = {"norm_pre_mlp": (h,), "router": (h, d["router"]),
           "router_bias": (d["router"],), "eg": (e, h, f), "eu": (e, h, f),
           "ed": (e, f, h), "sg": (h, fs), "su": (h, fs), "sd": (fs, h)}
    return {"tok_emb": (d["vocab"], h), "final_norm": (h,),
            "head": (h, d["vocab"]),
            "layers": [dict(_kda_shapes(d) if kind == "kda"
                            else _mla_shapes(d),
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


def _matrices(shapes: dict) -> int:
    return sum(_prod(s) for k, s in shapes.items()
               if len(s) == 2 and not k.startswith("conv_"))


def kda_params(cfg: dict) -> int:
    """The matrices of one KDA layer: q, k, v, the two low-rank gates,
    beta, `W_o`."""
    return _matrices(_kda_shapes(dims(cfg)))


def attn_params(cfg: dict) -> int:
    """The four projections of one latent-attention layer."""
    return _matrices(_mla_shapes(dims(cfg)))


def expert_params(cfg: dict) -> int:
    d = dims(cfg)
    return 3 * d["h"] * d["moe_ff"]


def cell_bytes(cfg: dict) -> int:
    """One cached row: a token, a latent-attention layer."""
    d = dims(cfg)
    return (d["kv_rank"] + d["rope"]) * BYTES


def state_bytes(cfg: dict) -> int:
    """What one KDA layer keeps for one slot: the matrices of its
    heads and the convolution's last inputs, float32."""
    d = dims(cfg)
    c = d["kda_heads"] * d["kda_dim"]
    return STATE_BYTES * (c * d["kda_dim"] + (d["taps"] - 1) * 3 * c)


def state_flops(cfg: dict) -> float:
    """The recurrence of one token through one KDA layer: the decay (1
    an element of S), S'^T k, the outer-product update and S^T q (2 a
    multiply-add each)."""
    d = dims(cfg)
    return 7.0 * d["kda_heads"] * d["kda_dim"] ** 2


def _counts(cfg: dict):
    d = dims(cfg)
    n_kda = d["kinds"].count("kda")
    return d, n_kda, d["layers"] - n_kda, d["layers"] - d["dense"]


def _moe_fixed_params(cfg: dict) -> int:
    """What every token of an expert layer multiplies through whatever
    it routes to: the router and the shared expert."""
    d = dims(cfg)
    return d["h"] * d["router"] + d["shared"] * expert_params(cfg)


def matmul_params(cfg: dict) -> int:
    """Matrix parameters this chip holds (not the embedding look-up)."""
    d, n_kda, n_mla, n_moe = _counts(cfg)
    return n_kda * kda_params(cfg) + n_mla * attn_params(cfg) \
        + d["dense"] * 3 * d["h"] * d["ff"] \
        + n_moe * (_moe_fixed_params(cfg)
                   + len(d["held"]) * expert_params(cfg)) \
        + d["h"] * d["vocab"]


def attn_context_flops(cfg: dict, context: float) -> float:
    """Scores and values of one position over `context` live ones, all
    heads, one latent-attention layer, as the definition has them (192
    and 128 numbers a head a position)."""
    d = dims(cfg)
    return 2.0 * d["heads"] * (d["nope"] + d["rope"] + d["v"]) * context


def flops_per_token(cfg: dict, context: float) -> float:
    """One position through this chip's share: 2 per matrix parameter
    it multiplies through — of the routed experts the
    `top_k * held / router` (2) a layer that fall here at the mean —,
    the recurrence of each KDA layer, and attention over `context`
    live positions in each latent-attention layer."""
    d, n_kda, n_mla, n_moe = _counts(cfg)
    routed = d["top_k"] * len(d["held"]) / d["router"]
    through = n_kda * kda_params(cfg) + n_mla * attn_params(cfg) \
        + d["dense"] * 3 * d["h"] * d["ff"] \
        + n_moe * (_moe_fixed_params(cfg) + routed * expert_params(cfg)) \
        + d["h"] * d["vocab"]
    return 2.0 * through + n_kda * state_flops(cfg) \
        + n_mla * attn_context_flops(cfg, context)


def experts_hit(cfg: dict, rows: float) -> float:
    """Of one layer's held experts, how many get at least one of `rows`
    tokens at the mean, each token keeping `top_k` of the router's
    experts with no favourite: 54.5 of 64 at 60 rows."""
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
    live latent rows of the active slots once and one new row a slot
    in each latent-attention layer, and each active slot's state read
    and written once in each KDA layer."""
    _, n_kda, n_mla, n_moe = _counts(cfg)
    unread = _unread(cfg, n_moe, slots, experts_hit) * expert_params(cfg)
    return (matmul_params(cfg) - unread) * BYTES \
        + (live_cells + slots) * n_mla * cell_bytes(cfg) \
        + 2.0 * slots * n_kda * state_bytes(cfg)


def moe_step(cfg: dict, rows: float, assignments_held: float,
             experts_hit: float):
    """(operations, bytes) the expert layers of one step require, from
    the program's counts summed over its expert layers: `rows` tokens a
    layer, `assignments_held` token-expert pairs that fell on held
    experts, `experts_hit` held experts that got at least one."""
    _, _, _, n_moe = _counts(cfg)
    flops = 2.0 * (assignments_held * expert_params(cfg)
                   + n_moe * rows * _moe_fixed_params(cfg))
    nbytes = BYTES * (experts_hit * expert_params(cfg)
                      + n_moe * _moe_fixed_params(cfg))
    return flops, nbytes


def mla_step(cfg: dict, rows: float, live_cells: float):
    """(operations, bytes) the latent attention of one step requires,
    its two layers: the projections' weights once and `rows` tokens
    through them, the `live_cells` latent rows of the active slots
    once, one row written a slot."""
    _, _, n_mla, _ = _counts(cfg)
    flops = n_mla * (2.0 * rows * attn_params(cfg)
                     + attn_context_flops(cfg, live_cells))
    nbytes = n_mla * (attn_params(cfg) * BYTES
                      + (live_cells + rows) * cell_bytes(cfg))
    return flops, nbytes


def kda_step(cfg: dict, rows: float):
    """(operations, bytes) the KDA layers of one step require: each
    layer's matrices read once and `rows` tokens through them, each
    row's state read and written once in float32 and the recurrence
    over it (`state_flops`): the least work."""
    _, n_kda, _, _ = _counts(cfg)
    flops = n_kda * rows * (2.0 * kda_params(cfg) + state_flops(cfg))
    nbytes = n_kda * (kda_params(cfg) * BYTES
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
    bias, their own scores renormalised and scaled, the terms of the
    experts in `held` (default `experts_held`; stacked in `lp` in that
    order) and the shared expert."""
    import jax
    import jax.numpy as jnp

    d, mm = dims(cfg), _mm(control)
    held = d["held"] if held is None else held
    scores = jax.nn.sigmoid(mm(xn, lp["router"]))
    _, top_i = jax.lax.top_k(scores + lp["router_bias"], d["top_k"])
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    top_w = float(cfg["routed_scaling_factor"]) * top_s \
        / jnp.sum(top_s, axis=-1, keepdims=True)

    def term(y, expert):
        e, wg, wu, wd = expert
        w = jnp.sum(jnp.where(top_i == e, top_w, 0.0), axis=-1)
        return y + w[..., None] * _mlp(mm, xn, wg, wu, wd), None

    y, _ = jax.lax.scan(
        term, _mlp(mm, xn, lp["sg"], lp["su"], lp["sd"]),
        (jnp.asarray(held, jnp.int32), lp["eg"], lp["eu"], lp["ed"]))
    return y


def kda_mix(lp, u, cfg: dict, control=None):
    """One KDA layer's token mixing over whole sequences: normed input
    `u` [N, T, h] -> [N, T, h], the recurrence token by token."""
    import jax
    import jax.numpy as jnp

    d, mm, rnd = dims(cfg), _mm(control), ROUND[control]
    heads, dim, taps = d["kda_heads"], d["kda_dim"], d["taps"]
    n, t, _ = u.shape
    f32 = jnp.float32

    def conv(x, w):
        w = w.astype(f32)
        pad = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
        return jax.nn.silu(sum(w[j] * pad[:, j:j + t] for j in range(taps)))

    def split(x):
        return x.reshape(n, t, heads, dim)

    def unit(x):
        return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                            + L2_EPS)

    q = unit(split(conv(mm(u, lp["wq"]), lp["conv_q"]))) / math.sqrt(dim)
    k = unit(split(conv(mm(u, lp["wk"]), lp["conv_k"])))
    v = split(conv(mm(u, lp["wv"]), lp["conv_v"]))
    g = -jnp.exp(lp["A_log"])[:, None] * split(jax.nn.softplus(
        mm(mm(u, lp["wf_a"]), lp["wf_b"]) + lp["dt_bias"]))
    beta = jax.nn.sigmoid(mm(u, lp["wb"]))                    # [N, T, H]
    gate = jax.nn.sigmoid(split(mm(mm(u, lp["wg_a"]), lp["wg_b"])))

    def token(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs                          # [N, H, ..]
        s = jnp.exp(g_t)[..., None] * s
        pred = jnp.sum(s * k_t[..., None], axis=-2)
        s = s + k_t[..., None] * (b_t[..., None] * (v_t - pred))[..., None, :]
        return s, jnp.sum(s * q_t[..., None], axis=-2)

    time_first = lambda a: jnp.swapaxes(a, 0, 1)  # noqa: E731
    _, o = jax.lax.scan(
        token, jnp.zeros((n, heads, dim, dim), f32),
        tuple(time_first(a) for a in (rnd(q), rnd(k), rnd(v), g, beta)))
    o = _rms(time_first(o), lp["o_norm"], float(cfg["rms_norm_eps"])) * gate
    return mm(o.reshape(n, t, heads * dim), lp["wo"])


def mla_mix(lp, u, cfg: dict, control=None):
    """One latent-attention layer's token mixing: full causal
    attention with every key and value expanded, no rotation."""
    import jax
    import jax.numpy as jnp

    hp = jax.lax.Precision.HIGHEST
    d, mm, rnd = dims(cfg), _mm(control), ROUND[control]
    heads, nope, rope, dv = d["heads"], d["nope"], d["rope"], d["v"]
    n, t, _ = u.shape
    qh = mm(u, lp["wq"]).reshape(n, t, heads, nope + rope)
    q_nope, q_pe = qh[..., :nope], qh[..., nope:]
    kv = mm(u, lp["wkv_a"])
    c = _rms(kv[..., :d["kv_rank"]], lp["kv_norm"],
             float(cfg["rms_norm_eps"]))
    k_pe = kv[..., d["kv_rank"]:]                             # [N, T, R]
    kvh = mm(c, lp["wkv_b"]).reshape(n, t, heads, nope + dv)
    k_nope, v = kvh[..., :nope], kvh[..., nope:]
    causal = jnp.tril(jnp.ones((t, t), bool))
    att = []
    for g in range(0, heads, HEAD_GROUP):
        hs = slice(g, g + HEAD_GROUP)
        s = (jnp.einsum("nthd,nuhd->nhtu", rnd(q_nope[:, :, hs]),
                        rnd(k_nope[:, :, hs]), precision=hp)
             + jnp.einsum("nthr,nur->nhtu", rnd(q_pe[:, :, hs]),
                          rnd(k_pe), precision=hp)) \
            / math.sqrt(nope + rope)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        att.append(jnp.einsum("nhtu,nuhd->nthd",
                              rnd(jax.nn.softmax(s, axis=-1)),
                              rnd(v[:, :, hs]), precision=hp))
    return mm(jnp.concatenate(att, axis=2).reshape(n, t, heads * dv),
              lp["wo"])


def logits_fn(params, tokens, cfg: dict, control=None):
    """tokens [N, T] -> logits [N, T, vocab], float32. `control` None is
    the reference; "fp8" and "bfloat16" keep float32 arithmetic and
    round the operands of every matrix product (weights, activations,
    keys, values, the router's, and q, k, v of the recurrence) to that
    precision."""
    import jax.numpy as jnp

    d, mm = dims(cfg), _mm(control)
    eps = float(cfg["rms_norm_eps"])
    x = params["tok_emb"][tokens].astype(jnp.float32)
    for lp, kind in zip(params["layers"], d["kinds"]):
        mix = kda_mix if kind == "kda" else mla_mix
        x = x + mix(lp, _rms(x, lp["norm_in"], eps), cfg, control)
        xn = _rms(x, lp["norm_pre_mlp"], eps)
        if "router" in lp:
            x = x + expert_ffn(lp, xn, cfg, control)
        else:
            x = x + _mlp(mm, xn, lp["w_gate"], lp["w_up"], lp["w_down"])
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
