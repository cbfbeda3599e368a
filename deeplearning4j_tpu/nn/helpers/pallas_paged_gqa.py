"""Grouped-query decode attention read straight from the page pool (TPU).

A gathered window costs what its width costs: every slot's page ids,
live or not, are copied out of the pool, the dead cells zeroed in a
pass of their own, and both cache contractions run over all of them.
This kernel reads each slot's LIVE pages and nothing else: the grid is
the slots, and slot s's loop runs over its first ceil(live[s] / page)
page ids by blocks of pages (`_pages_a_block`: a megabyte of K rows),
copying each page's K rows and V rows from the pool in HBM into VMEM
(two buffers each: block j + 1's copies are in flight while block j is
attended, and a slot's last block starts the next slot's first
copies). Softmax is online over the blocks, in ring order, in float32:
a running max, sum and accumulator a query head.

The pool is read in the layout it has, `[page layers, 2, pages, page,
n_kv * head_dim]`, one row of whole 128-lane tiles a token
(nn/attention.py's "Layout discipline"). A row is never split into
heads: as in nn/gqa_attention.py, the scores are the rows times a
BLOCK-DIAGONAL query (query head h on the lanes of its own K/V head)
and the values the weights times the rows, of which the caller takes
each head's own lanes. Operands enter both products in the pool's
dtype and sum in float32.

    m, l, acc = -inf, 0, 0                          a query head
    for block j of the live pages:
        s   = scale * (Q_blockdiag . K_j^T)        [H, cells of a block]
        s   = where(cell < live, s, MASK_VALUE)
        m'  = max(m, max s);  p = exp(s - m')
        l   = exp(m - m') l + sum p
        acc = exp(m - m') acc + p . where(cell < live, V_j, 0)
        m   = m'
    out = acc / l                                   [H, n_kv * head_dim]

Only a slot's last block holds dead cells (the live cells are the first
`live` of the ring; a page of the block past the slot's last is not
copied, and its buffer holds what it held), so only it masks: the
scores with MASK_VALUE and the value rows with 0, in registers (a dead
cell may hold anything, NaN included, and 0 x NaN is NaN). A slot with
`live` 0 reads nothing and gives 0. A row's result is a function of its own page ids and `live`
alone, whatever the width of the ids or the rows beside it. Runs in
interpret mode off-TPU so the same tests drive both.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.nn.attention import MASK_VALUE
from deeplearning4j_tpu.nn.helpers.pallas_conv import _interpret


# the K rows (and the V rows) of one block of pages, single-buffered:
# the kernel holds two of each
_BLOCK_BUDGET = 1 * 2**20


def _pages_a_block(page: int, row_bytes: int, width: int) -> int:
    """Pages a block holds: as many as the budget takes, at least one,
    never more than the window's."""
    return max(1, min(width, _BLOCK_BUDGET // (page * row_bytes)))


def _kernel(ids_ref, live_ref, q_ref, pool_ref, o_ref, k_buf, v_buf, sems,
            m_ref, l_ref, acc_ref, turn_ref, *, li, width, scale):
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    _, pb, ps, c = k_buf.shape
    f32 = jnp.float32

    def pages(slot):
        return (live_ref[slot] + ps - 1) // ps

    def copies(slot, blk, b, go):
        """`go` each copy of the block's pages the slot reads: its K
        and V rows into buffer b (a page past the slot's last is not
        copied; its cells are dead)."""
        first = blk * pb

        def one(j, carry):
            page = ids_ref[slot * width + first + j]
            for io, buf in ((0, k_buf), (1, v_buf)):
                go(pltpu.make_async_copy(pool_ref.at[li, io, page],
                                         buf.at[b, j], sems.at[io, b]))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(pages(slot) - first, pb), one, 0)

    def start(slot, blk, b):
        copies(slot, blk, b, lambda c: c.start())

    # turn_ref: [0] the buffer of this slot's first block, [1] 1 where
    # the slot before already started its copies
    @pl.when(s == 0)
    def _():
        turn_ref[0] = 0
        turn_ref[1] = 0

    n_blocks = (pages(s) + pb - 1) // pb
    b0 = turn_ref[0]

    @pl.when((n_blocks > 0) & (turn_ref[1] == 0))
    def _():
        start(s, 0, b0)

    turn_ref[1] = 0
    m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, f32)
    l_ref[...] = jnp.zeros(l_ref.shape, f32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, f32)
    live = live_ref[s]

    def attend(b, blk, tail):
        k = k_buf[b].reshape(pb * ps, c)
        v = v_buf[b].reshape(pb * ps, c)
        sc = jax.lax.dot_general(
            q_ref[...], k, (((1,), (1,)), ((), ())),
            preferred_element_type=f32) * scale          # [H, pb * ps]
        if tail:
            # the cells at or past `live` (the block's pages past the
            # slot's last were not copied: what the buffer holds there
            # is dead too)
            rest = live - blk * pb * ps
            sc = jnp.where(
                jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1) < rest,
                sc, MASK_VALUE)
            v = jnp.where(
                jax.lax.broadcasted_iota(jnp.int32, (pb * ps, 1), 0) < rest,
                v, jnp.zeros_like(v))
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=f32)
        m_ref[...] = m_new

    def body(blk, carry):
        b = (b0 + blk) % 2
        last = blk + 1 == n_blocks

        @pl.when(~last)
        def _():
            start(s, blk + 1, 1 - b)

        nxt = jnp.minimum(s + 1, n_slots - 1)

        @pl.when(last & (s + 1 < n_slots) & (pages(nxt) > 0))
        def _():
            start(nxt, 0, 1 - b)
            turn_ref[1] = 1

        copies(s, blk, b, lambda c: c.wait())

        @pl.when(~last)
        def _():
            attend(b, blk, False)

        @pl.when(last)
        def _():
            attend(b, blk, True)

        return carry

    jax.lax.fori_loop(0, n_blocks, body, 0)
    turn_ref[0] = (b0 + n_blocks) % 2
    l = l_ref[...]
    o_ref[...] = jnp.where(l > 0, acc_ref[...] / l, 0.0)


def paged_gqa_decode(q, pool, li: int, page_ids, live, n_kv: int,
                     scale: float):
    """q [S, H, D] (normed, rotated), the pool [page layers, 2, pages,
    page, n_kv * D] as stored, `li` the page layer, page_ids [S, W]
    int32 in ring order, live [S] int32 (0: the slot reads nothing) ->
    [S, H, n_kv * D] float32: each query head's weighted value rows, of
    which its own K/V head's D lanes are its output. `scale` is the
    scores' factor, a Python number."""
    slots, heads, d = q.shape
    _, _, _, ps, c = pool.shape
    width = page_ids.shape[1]
    group = heads // n_kv
    dt = pool.dtype
    # the block-diagonal query: head h's D numbers on the lanes of its
    # own K/V head, 0 on the others
    own = (jnp.arange(c)[None, :] // d
           == jnp.arange(heads)[:, None] // group)
    qb = jnp.where(own, jnp.tile(q, (1, 1, n_kv)), 0).astype(dt)
    f32 = jnp.float32
    isz = jnp.dtype(dt).itemsize
    pb = _pages_a_block(ps, c * isz, width)
    at_slot = lambda s, ids, live: (s, 0, 0)            # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(slots,),
        in_specs=[pl.BlockSpec((None, heads, c), at_slot),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, heads, c), at_slot),
        scratch_shapes=[pltpu.VMEM((2, pb, ps, c), dt),
                        pltpu.VMEM((2, pb, ps, c), dt),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.VMEM((heads, 1), f32),
                        pltpu.VMEM((heads, 1), f32),
                        pltpu.VMEM((heads, c), f32),
                        pltpu.SMEM((2,), jnp.int32)])
    return pl.pallas_call(
        functools.partial(_kernel, li=li, width=width, scale=float(scale)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, heads, c), f32),
        interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # four blocks of rows, the block's scores and weights, and
            # the query, accumulator and output a head (two of each
            # block the pipeline holds)
            vmem_limit_bytes=4 * pb * ps * c * isz
            + 2 * heads * pb * ps * 4 + 6 * heads * c * 4 + 8 * 2**20),
        # at most: every slot's every page live
        cost_estimate=pl.CostEstimate(
            flops=4 * slots * heads * c * width * ps,
            bytes_accessed=2 * slots * width * ps * c * isz,
            transcendentals=slots * heads * width * ps),
    )(page_ids.reshape(-1), live, qb, pool)
