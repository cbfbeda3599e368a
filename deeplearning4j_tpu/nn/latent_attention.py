"""Multi-head latent attention (MLA) over a paged LATENT cache.

The cache holds, for each token and layer, ONE row shared by all heads:
`[c_kv after its norm | k_rope after rotation]` (kv_lora_rank +
rope_dim numbers; 576 bfloat16 = 1,152 bytes where 128 heads of K and V
would take 65,536). On the chip the row is stored in whole 128-lane
tiles (640 numbers, the last 64 zero): the v5e's compiler works on the
pool with the row innermost and pads it so in any case, while the
runtime hands a `[.., 128, 576]` pool over with the 128 innermost to
save that padding, and every program then converted the whole pool on
its way in and out (1.5 GB read and written twice a step, seen in the
program compiled for a described v5e). Stored padded, the two layouts
are one. Keys and values are linear in a cached row,
`[k_nope | v] = c_kv W_kvb` per head, so there are two ways to attend
over it and both are here:

  expanded   (chunk prefill) expand every cached row to per-head keys
             and values and attend as usual: the cost of the expansion
             is shared by the chunk's queries (`chunk_tokens` of them).
  absorbed   (decode step) fold `W_kvb`'s key half into the query and
             its value half into the output, and attend in the latent
             space: `(q_nope W_K) . c_kv + q_rope . k_rope`, then
             `(p . c_kv) W_V`. One query a slot, so nothing is
             expanded: the step reads each cached row once.

The two agree to rounding (tests/test_latent_moe.py). Window layout,
ring order, dead-cell zeroing and the one softmax over
[prior cells ; chunk] are nn/attention.py's, for the same reasons.
Matmul operands are in the weights' stored dtype with float32 sums;
norms, rotary, softmax and residuals are float32.
"""

from __future__ import annotations

from deeplearning4j_tpu.nn.attention import MASK_VALUE, _softmax, mm, rms_norm


def rotary(x, positions, theta: float, inv=None, factor: float = 1.0):
    """Rotate the trailing axis of `x` [N, .., d] by `positions` [N]:
    half-split pairing (i with i + d/2), angle pos * theta^(-2i/d),
    float32. Positions are logical and unbounded: only differences
    reach a score.

    A scaled or partial rotation is data: `inv` [r/2] (host numbers,
    such as `yarn_inverse_frequencies`) in place of theta's turns the
    first r lanes alone, i paired with i + r/2, and passes the others
    as they are; `factor` multiplies cos and sin (YaRN's attention
    factor). With neither the program is the one it always was."""
    import jax.numpy as jnp

    if inv is None:
        half = x.shape[-1] // 2
        inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    else:
        half = len(inv)
        inv = jnp.asarray(inv, jnp.float32)
    ang = positions.astype(jnp.float32)[:, None] * inv        # [N, d/2]
    ang = jnp.reshape(ang, ang.shape[:1] + (1,) * (x.ndim - 2)
                      + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x1, x2 = x[..., :half], x[..., half:2 * half]
    out = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    if 2 * half < x.shape[-1]:
        out.append(x[..., 2 * half:])
    return jnp.concatenate(out, axis=-1)


def yarn_inverse_frequencies(dim: int, theta: float, factor: float,
                             original: int, beta_fast: float,
                             beta_slow: float):
    """YaRN's inverse frequencies (numpy, [dim/2]) for a rotation over
    `dim` lanes: theta's own below the band of dimensions that turn
    fewer than `beta_slow` times over `original` positions, theta's
    divided by `factor` above the band that turns more than
    `beta_fast` times, a linear ramp between (Hugging Face
    `_compute_yarn_parameters`, bounds truncated to whole dimensions)."""
    import math

    import numpy as np

    def dim_of(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    base = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    keep = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (1.0 / (factor * base)) * (1.0 - keep) + (1.0 / base) * keep


def row_width(rank: int, d_rope: int) -> int:
    """What a cached row takes in the pool: C + R, in whole 128-lane
    tiles once it fills one (576 -> 640; a test's 24 stays 24)."""
    n = rank + d_rope
    return n if n <= 128 else -(-n // 128) * 128


def _pad(x, width: int):
    import jax.numpy as jnp

    return jnp.pad(x, [(0, 0)] * (x.ndim - 1)
                   + [(0, width - x.shape[-1])])


def latent_project(lp: dict, x, positions, dims, theta, eps: float):
    """x [N, h] -> ((q_nope [N, H, Dn], q_rope [N, H, R]), cell
    [N, W]): the queries through their low-rank bottleneck (or
    straight through `wq` where the layer has none), and the row this
    layer caches of each position, [c_kv | k_rope | 0]. `theta` None:
    no rotation on either side (the R numbers are then one more
    position-free key shared by the heads)."""
    import jax
    import jax.numpy as jnp

    n_heads, d_nope, d_rope, rank = dims
    turn = (lambda a: a) if theta is None \
        else (lambda a: rotary(a, positions, theta))
    with jax.named_scope("q_proj"):
        xn = rms_norm(x, lp["norm_in"], eps)
        if "wq_a" in lp:
            q = mm(rms_norm(mm(xn, lp["wq_a"]), lp["q_norm"], eps),
                   lp["wq_b"])
        else:
            q = mm(xn, lp["wq"])
        q = jnp.reshape(q, (x.shape[0], n_heads, d_nope + d_rope))
        q_nope = q[..., :d_nope]
        q_rope = turn(q[..., d_nope:])
    with jax.named_scope("kv_proj"):
        kv = mm(xn, lp["wkv_a"])
        cell = _pad(jnp.concatenate([
            rms_norm(kv[..., :rank], lp["kv_norm"], eps),
            turn(kv[..., rank:])], axis=-1),
            row_width(rank, d_rope))
    return (q_nope, q_rope), cell


def _kvb(lp: dict, dims, d_v: int):
    """`wkv_b` [C, H * (Dn + Dv)] as its key and value halves
    [C, H, Dn], [C, H, Dv]."""
    import jax.numpy as jnp

    n_heads, d_nope, _, rank = dims
    w = jnp.reshape(lp["wkv_b"], (rank, n_heads, d_nope + d_v))
    return w[..., :d_nope], w[..., d_nope:]


def absorb_query(lp: dict, q, dims, d_v: int):
    """(q_nope, q_rope) -> [S, H, W]: the query against a cached row
    as it is stored, `W_kvb`'s key half folded in."""
    import jax.numpy as jnp

    q_nope, q_rope = q
    w_k, _ = _kvb(lp, dims, d_v)
    q_abs = jnp.einsum("shd,chd->shc", q_nope.astype(w_k.dtype), w_k,
                       preferred_element_type=jnp.float32)
    return _pad(jnp.concatenate([q_abs, q_rope], axis=-1),
                row_width(dims[3], dims[2]))


def latent_decode_attention(q, window, live, scale: float):
    """One position a slot against its gathered latent window (the
    DECODE shape, absorbed): `q` [S, H, W] from `absorb_query`,
    `window` [S, P, page, W] in ring order with the new position's row
    already written, `live[s]` readable cells. Returns the attention
    output in the latent space, [S, H, W] (its first C numbers are the
    result: the contraction takes the row whole so that no sliced copy
    of the window is made)."""
    import jax.numpy as jnp

    s, p, t, _ = window.shape
    h = q.shape[1]
    mask = jnp.reshape(jnp.arange(p * t)[None, :] < live[:, None],
                       (s, p, t))
    window = jnp.where(mask[..., None], window, 0)
    scores = jnp.einsum("shc,sptc->shpt", q.astype(window.dtype), window,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask[:, None], scores, MASK_VALUE)
    w = _softmax(jnp.reshape(scores, (s, h, p * t)))
    return jnp.einsum("shpt,sptc->shc",
                      jnp.reshape(w, (s, h, p, t)).astype(window.dtype),
                      window, preferred_element_type=jnp.float32)


def unabsorb_output(lp: dict, att, dims, d_v: int):
    """Latent attention output [S, H, W] -> merged heads
    [S, H * Dv] through `W_kvb`'s value half."""
    import jax.numpy as jnp

    _, w_v = _kvb(lp, dims, d_v)
    rank = dims[3]
    v = jnp.einsum("shc,chd->shd", att[..., :rank].astype(w_v.dtype), w_v,
                   preferred_element_type=jnp.float32)
    return jnp.reshape(v, (v.shape[0], -1))


def _expand(lp: dict, rows, dims, d_v: int):
    """Cached rows [.., W] -> (k_nope [.., H, Dn], v [.., H, Dv]) in
    the rows' dtype."""
    import jax.numpy as jnp

    w_k, w_v = _kvb(lp, dims, d_v)
    c = rows[..., :dims[3]].astype(w_k.dtype)
    k = jnp.einsum("...c,chd->...hd", c, w_k,
                   preferred_element_type=jnp.float32)
    v = jnp.einsum("...c,chd->...hd", c, w_v,
                   preferred_element_type=jnp.float32)
    return k.astype(rows.dtype), v.astype(rows.dtype)


def latent_chunk_attention(lp: dict, q, cell, window, n_prior, dims,
                           d_v: int, scale: float):
    """One prompt chunk attending to its prior context and to itself
    (the CHUNK-PREFILL shape, expanded): `q` = (q_nope [T, H, Dn],
    q_rope [T, H, R]) of positions n_prior..n_prior+T-1, `cell`
    [T, W] the chunk's own rows in the pool's precision, `window`
    [P, page, W] the prior rows (cells >= n_prior are scratch: zeroed
    and masked). ONE softmax spans [prior cells ; chunk]. Returns
    merged heads [T, H * Dv]."""
    import jax.numpy as jnp

    q_nope, q_rope = q
    t = q_nope.shape[0]
    p, ps, _ = window.shape
    h, d_rope, rank = dims[0], dims[2], dims[3]
    f32 = jnp.float32
    prior = jnp.reshape(jnp.arange(p * ps) < n_prior, (p, ps))
    window = jnp.where(prior[..., None], window, 0)
    dt = window.dtype
    qn, qr = q_nope.astype(dt), q_rope.astype(dt)
    rope = slice(rank, rank + d_rope)
    kp, vp = _expand(lp, window, dims, d_v)              # [P, ps, H, D]
    ki, vi = _expand(lp, cell, dims, d_v)                # [T, H, D]
    sp = (jnp.einsum("thd,pchd->htpc", qn, kp, preferred_element_type=f32)
          + jnp.einsum("thr,pcr->htpc", qr, window[..., rope],
                       preferred_element_type=f32)) * scale
    sp = jnp.reshape(jnp.where(prior[None, None], sp, MASK_VALUE),
                     (h, t, p * ps))
    si = (jnp.einsum("thd,uhd->htu", qn, ki, preferred_element_type=f32)
          + jnp.einsum("thr,ur->htu", qr, cell[..., rope],
                       preferred_element_type=f32)) * scale
    causal = jnp.tril(jnp.ones((t, t), bool))
    si = jnp.where(causal[None], si, MASK_VALUE)
    w = _softmax(jnp.concatenate([sp, si], axis=-1)).astype(dt)
    wp = jnp.reshape(w[..., :p * ps], (h, t, p, ps))
    att = (jnp.einsum("htpc,pchd->thd", wp, vp, preferred_element_type=f32)
           + jnp.einsum("htu,uhd->thd", w[..., p * ps:], vi,
                        preferred_element_type=f32))
    return jnp.reshape(att, (t, -1))
