"""Grouped-query attention over a PAGED cache of token rows: `n_heads`
query heads on `n_kv` key/value heads, query head h reading K/V head
h // (n_heads / n_kv), with a norm a head on q and k and rotary
positions on the whole head (LFM2's and LFM2-MoE's `full_attention`
layers). What other published layers of this kind differ in is data:
no norm a head where the layer has no `q_norm`, a partial or YaRN-scaled
rotation (`rotary`'s `inv`, `factor`), and a sigmoid gate a head on the
output from the layer's normed input (`gated_project`, `gate`:
Laguna's `full_attention` and `sliding_attention` layers, the latter
over nn/window_attention.py's ring), no rotation at all (`theta=None`)
and a score scale of the model's own in place of 1/sqrt(head_dim)
(`scale`: Granite-4.0-H's NoPE layers, whose `attention_multiplier`
is 1/128).

The cache is nn/attention.py's: one row of n_kv * head_dim numbers a
cached position for K and one for V, pages flattened in ring order,
and neither cache contraction splits a row into (n_kv, head_dim) (that
module's "Layout discipline": a minor dimension of head_dim 64 fills
half a 128-lane tile). Scores are the rows times a BLOCK-DIAGONAL
query in which query head h sits on the lanes of ITS K/V head
(`_group_blocks`), values the weights times the rows with head h's
lanes of the result taken after. With one query head a K/V head both
forms below are nn/attention.py's `paged_decode_attention` and
`chunk_prefill_attention` operation for operation (pinned bitwise on
the CPU, tests/test_gqa_attention.py); those stay as they are for the
programs that run them.

What the pool holds is final: `project` norms and rotates a key in
float32 BEFORE its row is written, so a cached key is never touched
again, and the rotation takes the LOGICAL position (it grows past the
window on a ring wrap: only differences reach a score). Operands go
into both contractions in the rows' stored dtype (a bfloat16 pool is
never raised: a float32 copy of a gathered window would be twice the
window) and sum in float32; norms, rotary, softmax are float32.
Ring order, dead-cell zeroing and the one softmax over
[prior cells ; chunk] are nn/attention.py's, for the same reasons.

The decode step need not gather: `gqa_pool_attention` is the DECODE
shape read straight from the pool by a kernel
(nn/helpers/pallas_paged_gqa.py) that copies each slot's live pages and
no others, with the same block-diagonal query, the same operands in
the pool's dtype and an online softmax over the pages; its result is
`gqa_decode_attention`'s over the same cells up to the order of the
sums.
"""

from __future__ import annotations

from deeplearning4j_tpu.nn.attention import (
    MASK_VALUE,
    _softmax,
    merge_heads,
    mm,
    rms_norm,
)
from deeplearning4j_tpu.nn.latent_attention import rotary


def project(lp: dict, x, positions, n_heads: int, n_kv: int, theta: float,
            eps: float, inv=None, factor: float = 1.0):
    """x [N, h] -> (q [N, n_heads, D], (k_row, v_row) [N, n_kv * D]
    each): the stream through the layer's norm and the three
    projections, q and k through their norm a head (a gain of D) where
    the layer has one (`q_norm`, `k_norm`) and rotated by `positions`
    (`inv` and `factor` are `rotary`'s: a partial or scaled rotation;
    `theta` None with no `inv`: no rotation); k and v as the rows the
    pool stores."""
    return _project(lp, rms_norm(x, lp["norm_in"], eps), positions,
                    n_heads, n_kv, theta, eps, inv, factor)


def gated_project(lp: dict, x, positions, n_heads: int, n_kv: int,
                  theta: float, eps: float, inv=None, factor: float = 1.0):
    """`project` of a layer whose heads' outputs are gated: (q, gate
    logits [N, n_heads] float32 from the same normed input through
    `attn_gate` [h, n_heads], (k_row, v_row))."""
    u = rms_norm(x, lp["norm_in"], eps)
    q, cell = _project(lp, u, positions, n_heads, n_kv, theta, eps, inv,
                       factor)
    return q, mm(u, lp["attn_gate"]), cell


def _project(lp, u, positions, n_heads, n_kv, theta, eps, inv, factor):
    import jax.numpy as jnp

    n = u.shape[0]
    q = jnp.reshape(mm(u, lp["wq"]), (n, n_heads, -1))
    k = jnp.reshape(mm(u, lp["wk"]), (n, n_kv, -1))

    def norm(a, gain):
        return rms_norm(a, lp[gain], eps) if gain in lp else a

    def turn(a):
        if theta is None and inv is None:
            return a
        return rotary(a, positions, theta, inv, factor)

    q, k = turn(norm(q, "q_norm")), turn(norm(k, "k_norm"))
    return q, (merge_heads(k), mm(u, lp["wv"]))


def gate(att, logits):
    """att [N, H * D] (heads merged) times sigmoid(logits) [N, H], each
    head's D numbers by its own gate, float32."""
    import jax
    import jax.numpy as jnp

    n, h = logits.shape
    heads = jnp.reshape(att, (n, h, -1)) * jax.nn.sigmoid(logits)[..., None]
    return merge_heads(heads)


def _group_blocks(n_heads: int, n_kv: int, head_dim: int, dtype):
    """E [n_kv * head_dim, n_heads] of 1.0 where lane c belongs to the
    K/V head of query head h (c // head_dim == h // group)."""
    import jax.numpy as jnp

    group = n_heads // n_kv
    return (jnp.arange(n_kv * head_dim)[:, None] // head_dim
            == jnp.arange(n_heads)[None, :] // group).astype(dtype)


def _on_kv_lanes(q, n_kv: int):
    """q [.., H, D] -> [.., n_kv * D, H]: every query head repeated
    over the lanes of a row (times `_group_blocks` it is the
    block-diagonal query)."""
    import jax.numpy as jnp

    return jnp.swapaxes(jnp.tile(q, n_kv), -1, -2)


def _own_lanes(full, n_kv: int):
    """full [.., H, n_kv * D] -> [.., H * D]: of each query head's
    weighted rows the lanes of its own K/V head, heads merged."""
    import jax.numpy as jnp

    h = full.shape[-2]
    d = full.shape[-1] // n_kv
    own = (jnp.arange(n_kv)[None, :]
           == jnp.arange(h)[:, None] // (h // n_kv)).astype(full.dtype)
    parts = jnp.reshape(full, full.shape[:-1] + (n_kv, d))
    return merge_heads(jnp.sum(parts * own[:, :, None], axis=-2))


def _scale(q, scale):
    """The scores' factor: `scale` where the model gives one (a Python
    number), 1/sqrt(head_dim) where it does not."""
    import jax.numpy as jnp

    if scale is None:
        return 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
    return float(scale)


def gqa_decode_attention(q, k_rows, v_rows, live, n_kv: int, scale=None):
    """One position a slot against its gathered window (the DECODE
    shape): `q` [S, H, D] normed and rotated, `k_rows` / `v_rows`
    [S, cells, n_kv * D] in ring order with the new position's row
    already written, `live[s]` readable cells (the rest zeroed before
    the score contraction and masked after). Returns [S, H * D], heads
    merged; `scale` is `_scale`'s."""
    import jax.numpy as jnp

    f32 = jnp.float32
    n = k_rows.shape[1]
    scale = _scale(q, scale)
    mask = jnp.arange(n)[None, :] < live[:, None]          # [S, N]
    k_rows = jnp.where(mask[:, :, None], k_rows, 0.0)
    v_rows = jnp.where(mask[:, :, None], v_rows, 0.0)
    e = _group_blocks(q.shape[-2], n_kv, q.shape[-1], q.dtype)
    qb = (_on_kv_lanes(q, n_kv) * e).astype(k_rows.dtype)  # [S, C, H]
    scores = jnp.einsum("snc,sch->shn", k_rows, qb,
                        preferred_element_type=f32) * scale
    scores = jnp.where(mask[:, None], scores, MASK_VALUE)
    w = _softmax(scores).astype(v_rows.dtype)
    full = jnp.einsum("shn,snc->shc", w, v_rows,
                      preferred_element_type=f32)          # [S, H, C]
    return _own_lanes(full, n_kv)


def gqa_pool_attention(q, pool, li: int, page_ids, live, active,
                       n_kv: int, scale=None):
    """`gqa_decode_attention` of each `active` row over its window
    read straight from the page pool (`pool` as stored, `li` the page
    layer, `page_ids` [S, W] in ring order, `live[s]` readable cells):
    the kernel copies a row's first ceil(live / page) pages and nothing
    else, under the `kv_read` scope, since it is the cache read. An
    inactive row reads nothing and gives 0. Returns [S, H * D], heads
    merged; `scale` is `_scale`'s."""
    import math

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.helpers.pallas_paged_gqa import (
        paged_gqa_decode,
    )

    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    with jax.named_scope("kv_read"):
        full = paged_gqa_decode(q, pool, li, page_ids,
                                jnp.where(active, live, 0), n_kv, scale)
    return _own_lanes(full, n_kv)


def gqa_chunk_attention(q, k, v, k_rows, v_rows, n_prior, n_kv: int,
                        scale=None):
    """One prompt chunk attending to its prior context and to itself
    (the CHUNK-PREFILL shape): `q` [T, H, D] of positions
    n_prior..n_prior+T-1, `k` / `v` [T, n_kv * D] the chunk's own rows
    in the pool's precision, `k_rows` / `v_rows` [cells, n_kv * D] the
    prior positions in ring order (cells >= n_prior are scratch:
    zeroed and masked). ONE softmax spans [prior cells ; chunk].
    Returns [T, H * D], heads merged; `scale` is `_scale`'s."""
    import jax.numpy as jnp

    f32 = jnp.float32
    t, h, d = q.shape
    n = k_rows.shape[0]
    scale = _scale(q, scale)
    prior = jnp.arange(n) < n_prior                        # [N]
    k_rows = jnp.where(prior[:, None], k_rows, 0.0)
    v_rows = jnp.where(prior[:, None], v_rows, 0.0)
    dt = k_rows.dtype
    e = _group_blocks(h, n_kv, d, q.dtype)
    qb = (_on_kv_lanes(q, n_kv) * e).astype(dt)            # [T, C, H]
    sp = jnp.einsum("nc,tch->htn", k_rows, qb,
                    preferred_element_type=f32) * scale    # [H, T, N]
    sp = jnp.where(prior[None, None], sp, MASK_VALUE)
    # the chunk's own keys and values, a copy a query head of the group
    # (T rows: nothing beside the window)
    own = lambda a: jnp.repeat(  # noqa: E731
        jnp.reshape(a, (t, n_kv, d)), h // n_kv, axis=1)
    si = jnp.einsum("thd,uhd->htu", q.astype(dt), own(k),
                    preferred_element_type=f32) * scale    # [H, T, T]
    causal = jnp.tril(jnp.ones((t, t), bool))
    si = jnp.where(causal[None, :, :], si, MASK_VALUE)
    w = _softmax(jnp.concatenate([sp, si], axis=-1)).astype(dt)
    # [T, H, C]; the heads are brought forward after the product
    # (XLA:CPU runs no bfloat16 product that writes them forward itself)
    full = jnp.swapaxes(jnp.einsum("htn,nc->htc", w[..., :n], v_rows,
                                   preferred_element_type=f32), 0, 1)
    mine = jnp.einsum("htu,uhd->thd", w[..., n:], own(v),
                      preferred_element_type=f32)
    return _own_lanes(full, n_kv) + merge_heads(mine)
