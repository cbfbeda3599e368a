"""The Mamba-2 mixer (Dao & Gu 2024, "Transformers are SSMs",
arXiv:2405.21060; the `mamba` layers of Granite-4.0-H,
`GraniteMoeHybridMambaLayer`): a selective state-space recurrence a
head, its input through a short causal convolution, its output through
a gated norm.

For token t, `u` the normed input (h numbers), H heads of P channels,
a state of N a channel, ONE group (B and C shared by every head), K
taps, C = H P + 2 N the convolution's channels:

    [z | xBC | dt] = u W_in          W_in [h, H P + C + H], no bias
    xBC = silu(conv(xBC) + conv_b)   depthwise, causal: `conv_w` [K, C],
                                     tap K-1 on the token itself, inputs
                                     before position 0 are 0
    [x | B | C] = xBC                x [H, P] head-major, B [N], C [N]
    delta = softplus(dt + dt_bias)   [H], no clamp (time_step_limit
                                     (0, inf))
    S_t = exp(delta A) S_{t-1} + delta x_t (x) B_t     A = -exp(A_log),
                                     S [H, P, N] a head's P x N matrix
    y_t = S_t C_t + D x_t
    out = (rms_norm(y * silu(z)) w_norm) W_out    the norm over all H P
                                     channels (one group), W_out [H P, h]

What a slot keeps between tokens is `S` and the convolution's last
K - 1 inputs (`tail`), both float32: a STATE in
engine/decode_program.py's sense, indexed by slot and not by page,
whatever the context. `S` is stored as rows of heads: [G, N, W], the
P channels of r = W / P heads side by side in a row of lanes, N on the
sublanes (nn/helpers/pallas_ssd.py says why; `_to_heads` and
`_to_rows` turn it to and from [H, P, N]). Two forms:

  step    (decode) one token a row: the taps over [tail ; xBC], then
          the matrix decayed, the row's outer product added and read
          against C in one pass over it (the kernel of
          nn/helpers/pallas_ssd.py).
  chunk   (prefill) T tokens of one slot from the entry the chunk found,
          in the chunked (SSD) form, which is the recurrence exactly for
          any T: with a_t = delta_t A and L_t = a_0 + .. + a_t a head,
              y_t = e^{L_t} S_0 C_t
                    + sum_{s <= t} e^{L_t - L_s} delta_s (C_t . B_s) x_s
                    + D x_t
              S_n = e^{L_{n-1}} S_0
                    + sum_{s < n} e^{L_{n-1} - L_s} delta_s x_s (x) B_s
          Every exponent is a sum of a_r <= 0 over rows after s: no term
          grows, and the matrices are [T, T] a head.

Both projections take the weights' stored dtype and sum in float32
(nn/attention.py `mm`); the convolution, delta, the decay, the state,
its products with B and C (float32 on the vector unit in the step, at
the highest matmul precision in the chunk), the gate and the norm are
float32.

Rows that are not real: the step takes a mask and leaves a masked
row's entry as it was; the chunk takes `n_state`, the rows the returned
entry absorbs, so a pad row never enters it. Every row of a chunk is
answered from the rows before it whatever `n_state` says.

Named scopes, the same in both programs: `ssd/in_proj` (norm and input
projection), `ssd/conv` (the taps with the tail's read and write),
`ssd/scan` (delta, the decay and the read, update and write-back of
`S` with its products), `ssd/out` (gate, norm, output projection).
"""

from __future__ import annotations

from deeplearning4j_tpu.nn.attention import mm, rms_norm


def state_shapes(n_layers: int, slots: int, n_heads: int, head_dim: int,
                 d_state: int, taps: int) -> dict:
    """What `n_layers` such layers keep for `slots` slots: `s` the
    matrices as rows of heads [G, N, W] (W = 128 lanes at the published
    widths: two heads of 64), `tail` the convolution's last K - 1
    inputs."""
    from deeplearning4j_tpu.nn.helpers.pallas_ssd import heads_per_row

    r = heads_per_row(n_heads, head_dim)
    return {"s": (n_layers, slots, n_heads // r, d_state, r * head_dim),
            "tail": (n_layers, slots, taps - 1,
                     n_heads * head_dim + 2 * d_state)}


def _to_heads(s, head_dim: int):
    """[.., G, N, W] rows of heads -> [.., H, P, N]."""
    import jax.numpy as jnp

    *lead, g, n, w = s.shape
    r = w // head_dim
    s = jnp.reshape(s, (*lead, g, n, r, head_dim))
    s = jnp.moveaxis(s, -3, -1)                            # [.., G, r, P, N]
    return jnp.reshape(s, (*lead, g * r, head_dim, n))


def _to_rows(s, heads_in_row: int):
    """[.., H, P, N] -> [.., G, N, W] rows of `heads_in_row` heads."""
    import jax.numpy as jnp

    *lead, h, p, n = s.shape
    s = jnp.reshape(s, (*lead, h // heads_in_row, heads_in_row, p, n))
    s = jnp.moveaxis(s, -1, -3)                            # [.., G, N, r, P]
    return jnp.reshape(s, (*lead, h // heads_in_row, n, heads_in_row * p))


def in_proj(lp: dict, x, n_heads: int, eps: float):
    """The stream [N, h] through the layer's norm and `W_in` -> (z
    [N, H P], xBC [N, C] before the convolution, dt [N, H])."""
    import jax
    import jax.numpy as jnp

    inner = lp["norm_y"].shape[0]
    with jax.named_scope("ssd/in_proj"):
        zxd = mm(rms_norm(x, lp["norm_in"], eps), lp["w_in"])
        z, xbc, dt = jnp.split(zxd, [inner, zxd.shape[-1] - n_heads],
                               axis=-1)
    return z, xbc, dt


def _taps(lp: dict):
    import jax.numpy as jnp

    return lp["conv_w"].astype(jnp.float32)                  # [K, C]


def _split(xbc, n_heads: int, d_state: int):
    """Convolved channels [.., C] -> x [.., H, P], B [.., N], C [..,
    N]."""
    import jax.numpy as jnp

    inner = xbc.shape[-1] - 2 * d_state
    x, b, c = jnp.split(xbc, [inner, inner + d_state], axis=-1)
    return jnp.reshape(x, x.shape[:-1] + (n_heads, -1)), b, c


def conv_step(lp: dict, xbc, tail, active):
    """One token a row: xbc [S, C], tail [S, K-1, C] -> (silu(taps +
    bias) [S, C], tail); a row `active` does not mark keeps its tail."""
    import jax
    import jax.numpy as jnp

    pad = jnp.concatenate([tail, xbc[:, None]], axis=1)      # [S, K, C]
    y = jnp.sum(pad * _taps(lp), axis=1) + lp["conv_b"]
    return jax.nn.silu(y), jnp.where(active[:, None, None], pad[:, 1:],
                                     tail)


def conv_chunk(lp: dict, xbc, tail, n_state):
    """T tokens of one slot: xbc [T, C], tail [K-1, C] as the chunk
    found it -> (silu(taps + bias) [T, C], the tail after the first
    `n_state` rows)."""
    import jax
    import jax.numpy as jnp

    w = _taps(lp)
    t, k = xbc.shape[0], w.shape[0]
    pad = jnp.concatenate([tail, xbc], axis=0)               # [K-1 + T, C]
    y = sum(w[j] * pad[j:j + t] for j in range(k)) + lp["conv_b"]
    return jax.nn.silu(y), jax.lax.dynamic_slice_in_dim(pad, n_state,
                                                        k - 1, 0)


def _delta(lp: dict, dt):
    """(delta [.., H], a = delta A [.., H]): the step and its log
    decay."""
    import jax
    import jax.numpy as jnp

    delta = jax.nn.softplus(dt + lp["dt_bias"])
    return delta, delta * -jnp.exp(lp["A_log"])


def scan_step(lp: dict, x, b, c, dt, state, si: int, active):
    """The recurrence over one token a row: x [S, H, P], b, c [S, N],
    dt [S, H], `state` [L, S, G, N, W] float32 of which this is layer
    `si` -> (y [S, H, P], state). No operation mixes rows; the decay and
    delta x go to the kernel as rows of heads, each head's number over
    its P lanes."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.helpers.pallas_ssd import ssd_step

    slots, heads, p = x.shape
    rows = state.shape[2:3] + state.shape[4:]              # (G, W)
    delta, a = _delta(lp, dt)
    decay = jnp.reshape(jnp.repeat(jnp.exp(a), p, axis=-1), (slots, *rows))
    dx = jnp.reshape(delta[..., None] * x, (slots, *rows))
    y, state = ssd_step(state, si, decay, dx, b, c, active)
    return jnp.reshape(y, x.shape) + lp["D"][:, None] * x, state


def scan_chunk(lp: dict, x, b, c, dt, s0, n_state):
    """The same recurrence over T tokens of one slot from `s0`, in the
    chunked form (module docstring): x [T, H, P], b, c [T, N], dt
    [T, H], s0 [G, N, W] rows of heads -> (y [T, H, P], the matrix
    after the first `n_state` rows, as rows of heads)."""
    import jax
    import jax.numpy as jnp

    hp = jax.lax.Precision.HIGHEST
    t, p = x.shape[0], x.shape[-1]
    r = s0.shape[-1] // p
    s0 = _to_heads(s0, p)
    delta, a = _delta(lp, dt)                                 # [T, H]
    big_l = jnp.cumsum(a, axis=0)
    rows = jnp.arange(t)
    causal = (rows[:, None] >= rows[None, :])[..., None]      # [t, s, 1]
    decay = jnp.where(causal, jnp.exp(jnp.where(
        causal, big_l[:, None] - big_l[None], 0.0)), 0.0)     # [t, s, H]
    cb = jnp.einsum("tn,sn->ts", c, b, precision=hp)
    mix = decay * (cb[..., None] * delta[None])               # [t, s, H]
    y = jnp.einsum("tsh,shp->thp", mix, x, precision=hp) \
        + jnp.exp(big_l)[..., None] * jnp.einsum(
            "hpn,tn->thp", s0, c, precision=hp) \
        + lp["D"][:, None] * x
    # the matrix after `n_state` rows: their decays and their terms only
    keep = (rows < n_state)[:, None]
    a_kept = jnp.where(keep, a, 0.0)
    end = jnp.sum(a_kept, axis=0)                             # [H]
    carry = jnp.where(keep, jnp.exp(end - jnp.cumsum(a_kept, axis=0)),
                      0.0) * delta                            # [T, H]
    s1 = jnp.exp(end)[:, None, None] * s0 + jnp.einsum(
        "sh,shp,sn->hpn", carry, x, b, precision=hp)
    return y, _to_rows(s1, r)


def out_proj(lp: dict, y, z, eps: float):
    """Head outputs [N, H, P] -> [N, h]: gated by silu(z), one norm over
    all H P channels with its gain, `W_out`."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("ssd/out"):
        g = jnp.reshape(y, z.shape) * jax.nn.silu(z)
        return mm(rms_norm(g, lp["norm_y"], eps), lp["w_out"])


def decode_mix(lp: dict, x, state: dict, si: int, active, n_heads: int,
               d_state: int, eps: float):
    """The layer over one token a row: the stream [S, h] and the whole
    `state` {"s" [L, S, G, N, W], "tail" [L, S, K-1, C]}, of which this
    is layer `si` -> (out [S, h], state). The slices and their
    write-back are under the scopes of the work they belong to."""
    import jax

    z, xbc, dt = in_proj(lp, x, n_heads, eps)
    with jax.named_scope("ssd/conv"):
        xbc, tail = conv_step(lp, xbc, state["tail"][si], active)
        tails = state["tail"].at[si].set(tail)
    with jax.named_scope("ssd/scan"):
        xs, b, c = _split(xbc, n_heads, d_state)
        y, s = scan_step(lp, xs, b, c, dt, state["s"], si, active)
        state = {"s": s, "tail": tails}
    return out_proj(lp, y, z, eps), state


def chunk_mix(lp: dict, x, entry: dict, n_state, n_heads: int,
              d_state: int, eps: float):
    """The layer over T tokens of one slot: the stream [T, h], `entry`
    {"s" [G, N, W], "tail" [K-1, C]} -> (out [T, h], the entry after
    `n_state` rows)."""
    import jax

    z, xbc, dt = in_proj(lp, x, n_heads, eps)
    with jax.named_scope("ssd/conv"):
        xbc, tail = conv_chunk(lp, xbc, entry["tail"], n_state)
    with jax.named_scope("ssd/scan"):
        xs, b, c = _split(xbc, n_heads, d_state)
        y, s = scan_chunk(lp, xs, b, c, dt, entry["s"], n_state)
    return out_proj(lp, y, z, eps), {"s": s, "tail": tail}
