"""Sliding-window attention over a per-slot RING of K/V rows: a layer
whose query at position t reads keys t - (window - 1) .. t alone
(Hugging Face's `sliding_window` convention: a key k is seen where
k > t - window), so what a slot keeps of it never grows with the
context. Laguna's `sliding_attention` layers.

The ring is a STATE in engine/decode_program.py's sense, indexed by
slot and not by page: `[window layers, slots, 2, window, n_kv * D]` in
the pool's dtype, position p in cell p mod window, K in plane 0 and V
in plane 1, each row nn/gqa_attention.py's (n_kv * D numbers, keys
rotated before they are written). Cell c of a ring holds the newest
position congruent to c that the slot has absorbed, so the cells are
in the page pool's RING order and the decode step is
`gqa_decode_attention` over the ring with `live = min(t + 1, window)`:
the same operands in the same order whatever the slot's history.

  step    (decode) one token a row: write the row's cell t mod window
          (an inactive row's cell keeps what it held), then attend
          over the ring; a cell that holds no position yet is zeroed
          and masked.
  chunk   (prefill) T rows of one slot at start s: ONE softmax over
          [ring ; chunk], row t seeing the ring's cells whose
          positions lie in (t - window, s) and the chunk's rows in
          (t - window, t]; then the chunk's first `n_state` rows go
          into the ring (`absorb`), the newest row a cell winning, so
          a pad row, or the prompt's last token that the first-token
          step writes, never enters it.

Any chunk length against any window: a chunk longer than the window
writes only the rows the ring keeps, and a chunk boundary may fall
anywhere in the ring. Operands stay in the ring's dtype (the chunk's
own rows are rounded to it first, so a chunk sees what a later step
reads back) and sum in float32; masks, softmax and positions are
float32 / int32.
"""

from __future__ import annotations

from deeplearning4j_tpu.nn.attention import MASK_VALUE, _softmax, merge_heads
from deeplearning4j_tpu.nn.gqa_attention import (
    _group_blocks,
    _on_kv_lanes,
    _own_lanes,
    gqa_decode_attention,
)


def state_shape(n_layers: int, max_slots: int, window: int, row: int):
    return (n_layers, max_slots, 2, window, row)


def ring_write(ring, i: int, k, v, positions, active):
    """ring [L, S, 2, W, C] with each `active` row's K and V [S, C]
    written into its slot's cell `positions mod W` of layer `i`; an
    inactive row's cell is written back as it was."""
    import jax.numpy as jnp

    s = jnp.arange(ring.shape[1])
    cell = positions % ring.shape[3]
    for io, row in enumerate((k, v)):
        old = ring[i, s, io, cell]
        new = jnp.where(active[:, None], row.astype(ring.dtype), old)
        ring = ring.at[i, s, io, cell].set(new)
    return ring


def window_decode_attention(q, ring_k, ring_v, positions, n_kv: int):
    """One position a slot over its ring (the DECODE shape): `q`
    [S, H, D] rotated, `ring_k` / `ring_v` [S, W, n_kv * D] with this
    position's row written. Cells at or past min(t + 1, W) hold no
    position yet. Returns [S, H * D]."""
    import jax.numpy as jnp

    live = jnp.minimum(positions + 1, ring_k.shape[1])
    return gqa_decode_attention(q, ring_k, ring_v, live, n_kv)


def held_positions(start, window: int):
    """[W] the position cell c of a ring holds before a chunk at
    `start`: the newest p < start with p = c mod W (negative: none)."""
    import jax.numpy as jnp

    c = jnp.arange(window)
    return start - 1 - (start - 1 - c) % window


def window_chunk_attention(q, k, v, ring_k, ring_v, start, n_kv: int):
    """A chunk of T rows of one slot at positions start .. start + T - 1
    (the CHUNK-PREFILL shape): `q` [T, H, D] rotated, `k` / `v`
    [T, n_kv * D] the chunk's own rows in the ring's precision,
    `ring_k` / `ring_v` [W, n_kv * D] the ring as the chunk found it.
    Row t reads the ring's cells whose positions lie in (t - W, start)
    and its own chunk's rows u with t - W < u <= t; a cell that holds
    no position is zeroed. ONE softmax spans [ring ; chunk]. Returns
    [T, H * D]."""
    import jax.numpy as jnp

    f32 = jnp.float32
    t, h, d = q.shape
    w = ring_k.shape[0]
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, q.dtype))
    held = held_positions(start, w)                         # [W]
    rows = start + jnp.arange(t)                            # [T]
    valid = held >= 0
    see = valid[None, :] & (held[None, :] > rows[:, None] - w)  # [T, W]
    ring_k = jnp.where(valid[:, None], ring_k, 0.0)
    ring_v = jnp.where(valid[:, None], ring_v, 0.0)
    dt = ring_k.dtype
    e = _group_blocks(h, n_kv, d, q.dtype)
    qb = (_on_kv_lanes(q, n_kv) * e).astype(dt)             # [T, C, H]
    sp = jnp.einsum("nc,tch->htn", ring_k, qb,
                    preferred_element_type=f32) * scale     # [H, T, W]
    sp = jnp.where(see[None], sp, MASK_VALUE)
    own = lambda a: jnp.repeat(  # noqa: E731
        jnp.reshape(a, (t, n_kv, d)), h // n_kv, axis=1)
    si = jnp.einsum("thd,uhd->htu", q.astype(dt), own(k),
                    preferred_element_type=f32) * scale     # [H, T, T]
    r = jnp.arange(t)
    band = (r[None, :] <= r[:, None]) & (r[None, :] > r[:, None] - w)
    si = jnp.where(band[None], si, MASK_VALUE)
    p = _softmax(jnp.concatenate([sp, si], axis=-1)).astype(dt)
    # [T, H, C]; the heads are brought forward after the product
    # (XLA:CPU runs no bfloat16 product that writes them forward itself)
    full = jnp.swapaxes(jnp.einsum("htn,nc->htc", p[..., :w], ring_v,
                                   preferred_element_type=f32), 0, 1)
    mine = jnp.einsum("htu,uhd->thd", p[..., w:], own(v),
                      preferred_element_type=f32)
    return _own_lanes(full, n_kv) + merge_heads(mine)


def absorb(entry, k, v, start, n_state):
    """One slot's ring entry [2, W, C] after a chunk at `start` whose
    first `n_state` rows (`k`, `v` [T, C]) it absorbs: cell c takes the
    newest absorbed row at a position = c mod W, and keeps what it held
    where there is none."""
    import jax.numpy as jnp

    w, t = entry.shape[1], k.shape[0]
    last = n_state - 1
    row = last - (start + last - jnp.arange(w)) % w          # [W]
    take = (row >= 0)[:, None]
    at = jnp.clip(row, 0, t - 1)
    return jnp.stack([jnp.where(take, a.astype(entry.dtype)[at], entry[io])
                      for io, a in enumerate((k, v))])
