"""The paged GQA decode kernel (nn/helpers/pallas_paged_gqa.py, in
interpret mode here) against `gqa_decode_attention` over the same pool
gathered at the whole window's width.

Each case holds one slot of each kind the engine hands the step: one
live cell, a page boundary, mid-page, the whole window after a ring
wrap (every cell live, the ids rotated), and an empty slot (its first
id the scratch page, inactive). Dead cells, the pages past a slot's
live ones and scratch page 0 hold NaN, which a cell read or summed
would carry into the output. Shapes are the served models' groups and
head sizes on two K/V heads: LFM2's 4 of 64 (a row of one 128-lane
tile, a head half a tile), Laguna's full layers' 6 of 128, Granite's 4
of 128 with its score scale of 1/128. A float32 pool holds the kernel
to float32 rounding (the page-wise softmax reorders the sums); a
bfloat16 pool to the rounding of the weights to bfloat16 before the
value product, which the kernel does before normalising and the
reference after.
"""

import numpy as np
import pytest

PAGE = 16
WIDTH = 6                   # pages a slot's window holds
N_KV = 2
SHAPES = {"lfm2": (4, 64, None), "laguna": (6, 128, None),
          "granite": (4, 128, 1 / 128)}
# live cells of the slots; the last slot is empty
LIVE = (1, PAGE, PAGE + 5, WIDTH * PAGE, 0)


def _case(shape, dtype, seed=0):
    """(q, pool, page ids [S, WIDTH], live, active): each slot's pages
    drawn from the pool in ring order, every id past a slot's live
    pages a page of NaN, scratch page 0 NaN, a live slot's dead cells
    NaN."""
    import jax.numpy as jnp

    group, d, _ = SHAPES[shape]
    rng = np.random.default_rng(seed)
    s = len(LIVE)
    n_pages = 2 + s * WIDTH
    pool = rng.standard_normal((2, 2, n_pages, PAGE, N_KV * d))
    pool[:, :, 0] = np.nan                       # scratch
    pool[:, :, 1] = np.nan                       # past the live pages
    ids = np.ones((s, WIDTH), np.int32)
    order = rng.permutation(np.arange(2, n_pages))
    for i, live in enumerate(LIVE):
        n = -(-live // PAGE)
        ids[i, :n] = order[i * WIDTH:i * WIDTH + n]
        if n:
            pool[:, :, ids[i, n - 1], live - (n - 1) * PAGE:] = np.nan
    ids[-1] = 0                                  # the empty slot
    q = rng.standard_normal((s, group * N_KV, d))
    live = np.maximum(np.asarray(LIVE, np.int32), 1)
    return (jnp.asarray(q, jnp.float32), jnp.asarray(pool, dtype),
            jnp.asarray(ids), jnp.asarray(live),
            jnp.asarray(np.asarray(LIVE) > 0))


_JITTED = {}


def _kernel(q, pool, ids, live, active, scale):
    """The kernel through `gqa_pool_attention`, jitted once a scale and
    block budget (the budget is read as the kernel is traced)."""
    import jax

    from deeplearning4j_tpu.nn.gqa_attention import gqa_pool_attention
    from deeplearning4j_tpu.nn.helpers import pallas_paged_gqa

    key = (scale, pallas_paged_gqa._BLOCK_BUDGET)
    if key not in _JITTED:
        _JITTED[key] = jax.jit(lambda *a: gqa_pool_attention(
            *a[:2], 1, *a[2:], N_KV, scale))
    return np.asarray(_JITTED[key](q, pool, ids, live, active))


def _pages_a_block(monkeypatch, pages, shape, dtype):
    """Blocks of `pages` pages: the kernel's budget set to hold so
    many of this shape's."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.helpers import pallas_paged_gqa

    row = N_KV * SHAPES[shape][1] * jnp.dtype(dtype).itemsize
    monkeypatch.setattr(pallas_paged_gqa, "_BLOCK_BUDGET",
                        pages * PAGE * row)
    assert pallas_paged_gqa._pages_a_block(PAGE, row, WIDTH) == pages


@pytest.mark.parametrize("pages", [1, 4])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_kernel_is_the_gathered_window_attention(shape, dtype, pages,
                                                     monkeypatch):
    """At a page a block, and at four (the window's six pages in a
    block of four and one of two, of which a slot may read fewer)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.gqa_attention import gqa_decode_attention

    _pages_a_block(monkeypatch, pages, shape, dtype)
    scale = SHAPES[shape][2]
    q, pool, ids, live, active = _case(shape, jnp.dtype(dtype))
    got = _kernel(q, pool, ids, live, active, scale)
    assert np.isfinite(got).all()
    # the empty slot reads nothing
    assert not got[-1].any()
    # the reference over the whole window gathered, the ids past the
    # live pages pointed at scratch as the engine hands them
    ref_ids = jnp.where(jnp.arange(WIDTH)[None, :]
                        < -(-live[:, None] // PAGE), ids, 0)
    rows = [jnp.reshape(pool[1, io][ref_ids], (len(LIVE), WIDTH * PAGE, -1))
            for io in (0, 1)]
    want = np.asarray(jax.jit(lambda *a: gqa_decode_attention(
        *a, N_KV, scale))(q, *rows, live))
    assert np.isfinite(want[:-1]).all()
    if dtype == "float32":
        tol = 1e-5
    else:
        # a weight rounded to bfloat16 on either side: 2 ** -8 of it
        # each, over values of this size
        tol = 2 * 2.0 ** -8 * float(np.nanmax(np.abs(
            np.asarray(pool, np.float32))))
    np.testing.assert_allclose(got[:-1], want[:-1], atol=tol, rtol=0)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_a_row_reads_its_live_pages_whatever_the_width_and_its_neighbours(
        shape, monkeypatch):
    """A row's result is a function of its own ids and `live` alone,
    to the bit: the ids handed at twice the width (more pages past the
    live ones, all NaN), its neighbours' live cells changed, give the
    same numbers (in blocks of four pages: what a block's buffer holds
    past a slot's last page is another slot's)."""
    import jax.numpy as jnp

    _pages_a_block(monkeypatch, 4, shape, jnp.bfloat16)
    scale = SHAPES[shape][2]
    q, pool, ids, live, active = _case(shape, jnp.bfloat16)
    got = _kernel(q, pool, ids, live, active, scale)
    wide = jnp.concatenate([ids, jnp.ones_like(ids)], axis=1)
    np.testing.assert_array_equal(
        _kernel(q, pool, wide, live, active, scale), got)
    other = jnp.asarray([PAGE + 5, 1, 3, WIDTH * PAGE, 1], jnp.int32)
    mixed = _kernel(q, pool, ids, jnp.where(jnp.arange(5) == 3, live, other),
                    active, scale)
    np.testing.assert_array_equal(mixed[3], got[3])
