"""The gated short convolution (the `conv` layers of LFM2 and
LFM2-MoE, `Lfm2MoeShortConv`): token mixing by a depthwise causal
convolution of a few taps between two elementwise gates.

For token t, `u` the normed input (h numbers):

    [B | C | z] = u W_in             W_in [h, 3h], split in that order
    s = B * z
    c_t = sum_j w_j * s_{t-(K-1)+j}  `conv_w` [K, h]: a weight a channel
                                     a tap, tap K-1 on the token itself;
                                     s before position 0 is 0; no bias,
                                     no activation
    out = (C * c) W_out              W_out [h, h]

What a slot keeps between tokens is the last K-1 rows of `s` (`tail`,
float32): a STATE in engine/decode_program.py's sense, indexed by slot
and not by page, with no recurrence matrix and no decay: [K-1, h]
numbers a layer a slot, whatever the context. Two forms:

  step    (decode) one token a row: the row's `s` behind its tail, the
          taps over the K rows, the tail moved up by one.
  chunk   (prefill) T tokens of one slot behind the tail the chunk
          found: the taps as K shifted products over [tail ; s].

Both projections take the weights' stored dtype and sum in float32
(nn/attention.py `mm`); the gates, the taps' sum and the tail are
float32.

Rows that are not real: the step takes a mask and leaves a masked
row's tail as it was; the chunk takes `n_state`, the rows the returned
tail absorbs: it comes back as rows `n_state - (K-1) .. n_state - 1` of
`s` (the found tail's where those are before the chunk), so a pad row
never enters it. Every row of a chunk is ANSWERED from the rows before
it whatever `n_state` says (row `n_state` is a prompt's last token,
which the engine's first-token step absorbs: serving/continuous.py).
"""

from __future__ import annotations

from deeplearning4j_tpu.nn.attention import mm, rms_norm


def in_proj(lp: dict, x, eps: float):
    """The stream [N, h] through the layer's norm -> (s [N, h] the
    convolution's input `B * z`, C [N, h] the output gate)."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("conv/in_proj"):
        b, c, z = jnp.split(mm(rms_norm(x, lp["norm_in"], eps), lp["w_in"]),
                            3, axis=-1)
    with jax.named_scope("conv/mix"):
        return b * z, c


def _taps(lp: dict):
    import jax.numpy as jnp

    return lp["conv_w"].astype(jnp.float32)                  # [K, h]


def conv_step(lp: dict, s, tail, active):
    """One token a row: s [S, h], tail [S, K-1, h] -> (c [S, h],
    tail); a row `active` does not mark keeps its tail. No operation
    mixes rows."""
    import jax.numpy as jnp

    pad = jnp.concatenate([tail, s[:, None]], axis=1)        # [S, K, h]
    c = jnp.sum(pad * _taps(lp), axis=1)
    return c, jnp.where(active[:, None, None], pad[:, 1:], tail)


def conv_chunk(lp: dict, s, tail, n_state):
    """T tokens of one slot: s [T, h], tail [K-1, h] as the chunk found
    it (zero where the chunk starts at position 0) -> (c [T, h], the
    tail after the first `n_state` rows)."""
    import jax
    import jax.numpy as jnp

    w = _taps(lp)
    t, k = s.shape[0], w.shape[0]
    pad = jnp.concatenate([tail, s], axis=0)                 # [K-1 + T, h]
    c = sum(w[j] * pad[j:j + t] for j in range(k))
    return c, jax.lax.dynamic_slice_in_dim(pad, n_state, k - 1, 0)


def out_proj(lp: dict, c, gate):
    import jax

    with jax.named_scope("conv/mix"):
        y = gate * c
    with jax.named_scope("conv/out_proj"):
        return mm(y, lp["w_out"])


def state_shape(n_layers: int, slots: int, taps: int, hidden: int):
    """What `n_layers` such layers keep for `slots` slots: the last
    `taps - 1` rows of `s`, the channels innermost (whole 128-lane
    tiles at the published width)."""
    return (n_layers, slots, taps - 1, hidden)


def decode_mix(lp: dict, x, state, si: int, active, eps: float):
    """The layer over one token a row: the stream [S, h] and the whole
    `state` [L, S, K-1, h], of which this is layer `si` ->
    (out [S, h], state). The tail's slice and its write-back are under
    `conv/mix`, with the gates and the taps."""
    import jax

    s, gate = in_proj(lp, x, eps)
    with jax.named_scope("conv/mix"):
        c, tail = conv_step(lp, s, state[si], active)
        state = state.at[si].set(tail)
    return out_proj(lp, c, gate), state


def chunk_mix(lp: dict, x, tail, n_state, eps: float):
    """The layer over T tokens of one slot: the stream [T, h], `tail`
    [K-1, h] -> (out [T, h], the tail after `n_state` rows)."""
    import jax

    s, gate = in_proj(lp, x, eps)
    with jax.named_scope("conv/mix"):
        c, tail = conv_chunk(lp, s, tail, n_state)
    return out_proj(lp, c, gate), tail
