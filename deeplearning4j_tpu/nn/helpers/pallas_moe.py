"""The held experts' product over the experts some row chose (TPU).

A product of every held expert over every row (einsums over the
stacked experts) reads every held expert's weights whatever the
routing. This kernel reads only those on a hit list: the held experts
some row chose, compacted in ascending id order, with their count as
scalar prefetch. The grid is (slot of the list, tile of the expert's
intermediate width); slot e reads expert `ids[e]`. A slot past the
list's end asks for the block the last real step fetched, so the
pipeline issues no copy for it and an expert no row chose costs no
HBM bytes; its body does not run.

    y[n] = sum over e < n_hit, ascending, of
           where(w[n, ids[e]] > 0, w[n, ids[e]] * E_ids[e](x[n]), 0)

A row's result is a function of that row alone: every row of a tile is
its own dot product, the experts are added in ascending id order, and
an expert the row did not choose adds an exact 0 (a `where`, not a
product with 0), so the rows beside it change only which zeros are
added. Runs in interpret mode off-TPU so the same tests drive both.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.nn.helpers.pallas_conv import _interpret

# the gate, up and down blocks of one grid step, single-buffered: the
# pipeline holds two of each
_BLOCK_BUDGET = 12 * 2**20


def _pick_tf(h: int, f: int, itemsize: int) -> int:
    """The widest tile of the intermediate width f (a multiple of 128
    that divides it) whose three blocks fit the budget; f itself where
    it is no multiple of 128."""
    if f % 128:
        return f
    tf = 128
    for t in range(128, f + 1, 128):
        if f % t == 0 and 3 * h * t * itemsize <= _BLOCK_BUDGET:
            tf = t
    return tf


def _block(e, f, ids, n, nf):
    """(expert, tile) of grid step (e, f): slots past the list repeat
    the last real step's block, so no copy is issued for them."""
    n = n[0]
    real = e < n
    expert = ids[jnp.where(real, e, jnp.maximum(n - 1, 0))]
    tile = jnp.where(real, f, jnp.where(n > 0, nf - 1, 0))
    return expert, tile


def _kernel(ids_ref, n_ref, x_ref, w_ref, g_ref, u_ref, d_ref, o_ref,
            *acc_ref, nf):
    e, f = pl.program_id(0), pl.program_id(1)
    f32 = jnp.float32

    @pl.when((e == 0) & (f == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    def emit(ye):
        w = w_ref[...]                                   # [N, 1]
        o_ref[...] += jnp.where(w > 0, ye * w, 0.0)

    @pl.when(e < n_ref[0])
    def _():
        x = x_ref[...]
        g = jnp.dot(x, g_ref[...], preferred_element_type=f32)
        u = jnp.dot(x, u_ref[...], preferred_element_type=f32)
        act = (jax.nn.silu(g) * u).astype(d_ref.dtype)
        part = jnp.dot(act, d_ref[...], preferred_element_type=f32)
        if nf == 1:
            emit(part)
            return
        acc, = acc_ref

        @pl.when(f == 0)
        def _():
            acc[...] = part

        @pl.when((f > 0) & (f < nf - 1))
        def _():
            acc[...] += part

        @pl.when(f == nf - 1)
        def _():
            emit(acc[...] + part)


def hit_list(w):
    """(ids [E] int32, n_hit [1] int32): the held experts (columns of
    `w` [N, E]) that some row chose (weight above 0), ascending,
    first."""
    hit = jnp.any(w > 0, axis=0)
    ids = jnp.argsort(~hit, stable=True).astype(jnp.int32)
    return ids, jnp.sum(hit, dtype=jnp.int32)[None]


def grouped_experts(x, w, ids, n_hit, eg, eu, ed):
    """x [N, h] (the experts' dtype), w [N, E] float32 (0 where a row
    did not choose the held expert), the hit list (`hit_list`), the
    held experts `eg`, `eu` [E, h, f], `ed` [E, f, h] -> y [N, h]
    float32: the weighted sum of the hit experts' outputs."""
    n_rows, h = x.shape
    n_held, _, f = eg.shape
    isz = eg.dtype.itemsize
    tf = _pick_tf(h, f, isz)
    nf = f // tf
    block = functools.partial(_block, nf=nf)

    def gate_up(e, j, ids, n):
        expert, tile = block(e, j, ids, n)
        return expert, 0, tile

    def down(e, j, ids, n):
        expert, tile = block(e, j, ids, n)
        return expert, tile, 0

    def weight(e, j, ids, n):
        return block(e, j, ids, n)[0], 0, 0

    const = lambda e, j, ids, n: (0, 0)                 # noqa: E731
    f32 = jnp.float32
    blocks = 3 * h * tf * isz
    resident = n_rows * h * (2 * x.dtype.itemsize + 3 * 4)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(n_held, nf),
        in_specs=[pl.BlockSpec((n_rows, h), const),
                  pl.BlockSpec((None, n_rows, 1), weight),
                  pl.BlockSpec((None, h, tf), gate_up),
                  pl.BlockSpec((None, h, tf), gate_up),
                  pl.BlockSpec((None, tf, h), down)],
        out_specs=pl.BlockSpec((n_rows, h), const),
        scratch_shapes=[pltpu.VMEM((n_rows, h), f32)] if nf > 1 else [])
    return pl.pallas_call(
        functools.partial(_kernel, nf=nf), grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows, h), f32),
        interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(2 * blocks + resident + 8 * 2**20,
                                 100 * 2**20)),
        # at most: every held expert on the list
        cost_estimate=pl.CostEstimate(
            flops=6 * n_rows * h * f * n_held,
            bytes_accessed=3 * h * f * isz * n_held,
            transcendentals=n_rows * f * n_held),
    )(ids, n_hit, x, jnp.transpose(w)[:, :, None], eg, eu, ed)
