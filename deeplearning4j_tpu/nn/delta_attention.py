"""The gated delta rule with a decay a channel (Kimi Delta Attention,
"Kimi Linear", arXiv:2510.26692): linear attention whose memory is one
matrix a head, whatever the context.

For token t, head h, `u` the normed input (D = 128 numbers a head):

    q~, k~, v~ = W_q u, W_k u, W_v u, each through a causal depthwise
                 convolution over time (`conv_*` [K, C]: a weight a
                 channel a tap, tap K-1 on the token itself) and SiLU
    q = l2norm(q~_h) / sqrt(D);  k = l2norm(k~_h);  v = v~_h
    g = -exp(A_log_h) * softplus(W_f2 W_f1 u + dt_bias)_h   in R^D, <= 0
    beta = sigmoid(w_b,h . u)
    S' = Diag(exp g) S;  S_t = S' + beta k (v - S'^T k)^T;  o = S_t^T q
    out = W_o [ RMSNorm_h(o) * sigmoid(W_g2 W_g1 u)_h ]

What a slot keeps between tokens is `S` [H, D, D] in float32 and the
last K-1 inputs of the convolution (`tail`): a STATE, indexed by slot
and not by page (engine/decode_program.py threads it). Two forms:

  step    (decode) one token a row, the recurrence as written, every
          contraction of `S` elementwise in float32 (a float32 dot
          would round `S` to bfloat16 on the chip, and the step is
          bound by reading `S`, not by these sums).
  chunk   (prefill) T tokens of one slot from a given state, in matrix
          products: with G the running sum of g inside the chunk,
          (I + Diag(beta) A) U = Diag(beta) (V - (K e^G) S_0),
          A_ij = (k_i e^{G_i}) . (k_j e^{-G_j}) for j < i,
          O = (Q e^G) S_0 + B U, B_ij the same with q_i for j <= i,
          S_T = e^{G_T} S_0 + (K e^{G_T - G})^T U.
          e^{-G_j} overflows float32 after a few tens of tokens of
          strong decay, so no such factor is formed: rows are cut into
          sub-blocks of `SUB`; across blocks both factors are taken
          against the later block's first decay (both exponents <= 0),
          inside a block e^{G_i - G_j} is formed pair by pair. The unit
          lower-triangular system is solved by inverting the SUB x SUB
          diagonal blocks (a nilpotent's finite series, in log2 SUB
          products) and substituting block by block.

The state's own arithmetic is float32 at the highest matmul precision
(0.8 GFLOP a layer a chunk at the published widths: nothing beside the
projections, which take the weights' stored dtype and sum in float32).

Rows that are not real: the step takes a mask and leaves a masked
row's state and tail as they were; the chunk takes `n_state`, the
rows the returned state absorbs. Row `n_state` itself is still
ANSWERED as a real token (a prompt's last token, which the engine's
first-token step absorbs: serving/continuous.py), rows past it are
padding with beta = 0 and g = 0.
"""

from __future__ import annotations

import math

from deeplearning4j_tpu.nn.attention import mm, rms_norm

SUB = 16            # rows of a sub-block of the chunk form
L2_EPS = 1e-6       # under the root of q's and k's norms


def _heads(x, n_heads: int):
    import jax.numpy as jnp

    return jnp.reshape(x, x.shape[:-1] + (n_heads, -1))


def project(lp: dict, x, n_heads: int, eps: float):
    """The stream [N, h] through the layer's norm -> (qkv [N, 3C]
    before the convolution, g [N, H, D] the log decay, beta [N, H],
    gate [N, H, D])."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("kda/proj"):
        xn = rms_norm(x, lp["norm_in"], eps)
        qkv = jnp.concatenate([mm(xn, lp["wq"]), mm(xn, lp["wk"]),
                               mm(xn, lp["wv"])], axis=-1)
        f = mm(mm(xn, lp["wf_a"]), lp["wf_b"]) + lp["dt_bias"]
        g = -jnp.exp(lp["A_log"])[:, None] \
            * _heads(jax.nn.softplus(f), n_heads)
        beta = jax.nn.sigmoid(mm(xn, lp["wb"]))
        gate = jax.nn.sigmoid(_heads(mm(mm(xn, lp["wg_a"]), lp["wg_b"]),
                                     n_heads))
    return qkv, g, beta, gate


def _taps(lp: dict):
    import jax.numpy as jnp

    return jnp.concatenate([lp["conv_q"], lp["conv_k"], lp["conv_v"]],
                           axis=-1).astype(jnp.float32)      # [K, 3C]


def _qkv_heads(y, n_heads: int):
    """Convolved channels [.., 3C] -> q, k, v [.., H, D], q and k of
    unit length (q scaled by 1/sqrt(D))."""
    import jax
    import jax.numpy as jnp

    q, k, v = (_heads(a, n_heads)
               for a in jnp.split(jax.nn.silu(y), 3, axis=-1))
    unit = lambda a: a / jnp.sqrt(  # noqa: E731
        jnp.sum(jnp.square(a), axis=-1, keepdims=True) + L2_EPS)
    return unit(q) / math.sqrt(q.shape[-1]), unit(k), v


def conv_step(lp: dict, qkv, tail, active, n_heads: int):
    """One token a row: qkv [S, 3C], tail [S, K-1, 3C] -> (q, k, v,
    tail); a row `active` does not mark keeps its tail."""
    import jax.numpy as jnp

    pad = jnp.concatenate([tail, qkv[:, None]], axis=1)       # [S, K, 3C]
    y = jnp.sum(pad * _taps(lp), axis=1)
    tail = jnp.where(active[:, None, None], pad[:, 1:], tail)
    return (*_qkv_heads(y, n_heads), tail)


def conv_chunk(lp: dict, qkv, tail, n_state, n_heads: int):
    """T tokens of one slot: qkv [T, 3C], tail [K-1, 3C] -> (q, k, v,
    tail after `n_state` rows)."""
    import jax
    import jax.numpy as jnp

    w = _taps(lp)
    t = qkv.shape[0]
    pad = jnp.concatenate([tail, qkv], axis=0)            # [K-1 + T, 3C]
    y = sum(w[j] * pad[j:j + t] for j in range(w.shape[0]))
    tail = jax.lax.dynamic_slice_in_dim(pad, n_state, w.shape[0] - 1, 0)
    return (*_qkv_heads(y, n_heads), tail)


def kda_step(q, k, v, g, beta, s, active):
    """The recurrence over one token a row: q, k, v, g [S, H, D],
    beta [S, H], s [S, H, D, D] float32, `active` [S] bool ->
    (o [S, H, D], s). No operation mixes rows. `S_t^T q` is taken as
    `S'^T q + u (k . q)`, so both contractions read the decayed state
    in one pass and the update is the only other."""
    import jax.numpy as jnp

    sd = jnp.exp(g)[..., None] * s
    u = beta[..., None] * (v - jnp.sum(sd * k[..., None], axis=-2))
    o = jnp.sum(sd * q[..., None], axis=-2) \
        + u * jnp.sum(k * q, axis=-1, keepdims=True)
    new = sd + k[..., None] * u[..., None, :]
    return o, jnp.where(active[:, None, None, None], new, s)


def kda_chunk(q, k, v, g, beta, s0, n_state, sub: int = SUB):
    """The same recurrence over T tokens of one slot from state `s0`,
    in the chunkwise form (module docstring): q, k, v, g [T, H, D],
    beta [T, H], s0 [H, D, D] -> (o [T, H, D], the state after the
    first `n_state` rows). T and `sub` are powers of two."""
    import jax
    import jax.numpy as jnp

    t, h, d = q.shape
    c = min(sub, t)
    n = t // c
    f32 = jnp.float32
    hp = jax.lax.Precision.HIGHEST
    rows = jnp.arange(t)
    real, absorbed = rows <= n_state, rows < n_state
    g = jnp.where(real[:, None, None], g, 0.0)
    beta = jnp.where(real[:, None], beta, 0.0).T              # [H, T]
    q, k, v, g = (jnp.transpose(a, (1, 0, 2)) for a in (q, k, v, g))
    cum = jnp.cumsum(g, axis=1)                               # G [H, T, D]
    blk = lambda a: jnp.reshape(a, (h, n, c) + a.shape[2:])  # noqa: E731
    cum_b = blk(cum)
    # a block's reference: G through the row before its first
    ref = jnp.concatenate([jnp.zeros((h, 1, d), f32), cum_b[:, :-1, -1]],
                          axis=1)                             # [H, n, D]
    inside = jnp.exp(cum_b - ref[:, :, None])                 # <= 1
    k_in, q_in = blk(k) * inside, blk(q) * inside
    # every row before block I against I's reference (exponent <= 0)
    early = (rows[None, :] < (jnp.arange(n) * c)[:, None])[None, :, :, None]
    k_out = jnp.where(early, k[:, None] * jnp.exp(jnp.where(
        early, ref[:, :, None] - cum[:, None], 0.0)), 0.0)    # [H, n, T, D]
    off = lambda a: jnp.reshape(jnp.einsum(  # noqa: E731
        "hncd,hntd->hnct", a, k_out, precision=hp), (h, n, c, n, c))
    # inside a block, pair by pair: e^{G_i - G_j} for j <= i
    low = jnp.tril(jnp.ones((c, c), bool))
    pair = jnp.where(low[..., None], jnp.exp(jnp.where(
        low[..., None], cum_b[:, :, :, None] - cum_b[:, :, None], 0.0)),
        0.0)                                                  # [H,n,c,c,D]
    diag = lambda a: jnp.sum(  # noqa: E731
        a[:, :, :, None] * blk(k)[:, :, None] * pair, axis=-1)
    on_diag = jnp.eye(n, dtype=f32)[None, :, None, :, None]
    strict = jnp.tril(jnp.ones((c, c), f32), -1)
    a_mat = off(k_in) + on_diag * (diag(blk(k)) * strict)[:, :, :, None]
    b_mat = off(q_in) + on_diag * diag(blk(q))[:, :, :, None]
    m = blk(beta)[..., None, None] * a_mat                    # [H,n,c,n,c]
    # (I + L)^-1 of the diagonal blocks: L is nilpotent, so the series
    # (I - L)(I + L^2)(I + L^4).. is exact after log2(c) factors
    eye = jnp.eye(c, dtype=f32)
    lo = jnp.stack([m[:, i, :, i] for i in range(n)], axis=1)  # [H,n,c,c]
    inv, power = eye - lo, lo
    for _ in range(max(0, c.bit_length() - 2)):
        power = jnp.matmul(power, power, precision=hp)
        inv = jnp.matmul(inv, eye + power, precision=hp)
    decayed = lambda a: jnp.einsum(  # noqa: E731
        "htd,hdv->htv", a * jnp.exp(cum), s0, precision=hp)
    rhs = blk(beta[..., None] * (v - decayed(k)))             # [H,n,c,D]
    us = []
    for i in range(n):
        r = rhs[:, i]
        if i:
            r = r - jnp.einsum("hcjk,hjkv->hcv", m[:, i, :, :i],
                               jnp.stack(us, axis=1), precision=hp)
        us.append(jnp.matmul(inv[:, i], r, precision=hp))
    u = jnp.concatenate(us, axis=1)                           # [H, T, D]
    o = decayed(q) + jnp.einsum("hts,hsv->htv",
                                jnp.reshape(b_mat, (h, t, t)), u,
                                precision=hp)
    # the state after `n_state` rows: their decays and their terms only
    keep = absorbed[None, :, None]
    end = jnp.sum(jnp.where(keep, g, 0.0), axis=1)            # [H, D]
    carry = jnp.where(keep, jnp.exp(jnp.where(
        keep, end[:, None] - cum, 0.0)), 0.0)
    s1 = jnp.exp(end)[..., None] * s0 + jnp.einsum(
        "htd,htv->hdv", k * carry, u, precision=hp)
    return jnp.transpose(o, (1, 0, 2)), s1


def kda_out(lp: dict, o, gate, eps: float):
    """Head outputs [N, H, D] -> [N, h]: a norm over each head's D with
    a gain, the output gate, `W_o`."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("kda/out"):
        y = rms_norm(o, lp["o_norm"], eps) * gate
        return mm(jnp.reshape(y, (y.shape[0], -1)), lp["wo"])


def state_shapes(n_layers: int, slots: int, n_heads: int, head_dim: int,
                 taps: int) -> dict:
    """What `n_layers` such layers keep for `slots` slots: `s` the
    matrices, `tail` the convolution's last inputs, both float32 with
    whole 128-lane tiles innermost at the published widths."""
    return {"s": (n_layers, slots, n_heads, head_dim, head_dim),
            "tail": (n_layers, slots, taps - 1, 3 * n_heads * head_dim)}


def decode_mix(lp: dict, x, state: dict, si: int, active, n_heads: int,
               eps: float):
    """The layer over one token a row: the stream [S, h] and the whole
    `state` {"s" [L, S, H, D, D], "tail" [L, S, K-1, 3C]}, of which
    this is layer `si` -> (out [S, h], state). The slices and their
    write-back are under the scopes of the work they belong to, so
    `kda/state` is the read, the update and the write of `S`."""
    import jax

    qkv, g, beta, gate = project(lp, x, n_heads, eps)
    with jax.named_scope("kda/conv"):
        q, k, v, tail = conv_step(lp, qkv, state["tail"][si], active,
                                  n_heads)
        tails = state["tail"].at[si].set(tail)
    with jax.named_scope("kda/state"):
        o, s = kda_step(q, k, v, g, beta, state["s"][si], active)
        state = {"s": state["s"].at[si].set(s), "tail": tails}
    return kda_out(lp, o, gate, eps), state


def chunk_mix(lp: dict, x, entry: dict, n_state, n_heads: int, eps: float):
    """The layer over T tokens of one slot: the stream [T, h],
    `entry` {"s" [H, D, D], "tail" [K-1, 3C]} -> (out [T, h], the
    entry after `n_state` rows)."""
    import jax

    qkv, g, beta, gate = project(lp, x, n_heads, eps)
    with jax.named_scope("kda/conv"):
        q, k, v, tail = conv_chunk(lp, qkv, entry["tail"], n_state, n_heads)
    with jax.named_scope("kda/state"):
        o, s = kda_chunk(q, k, v, g, beta, entry["s"], n_state)
    return kda_out(lp, o, gate, eps), {"s": s, "tail": tail}
