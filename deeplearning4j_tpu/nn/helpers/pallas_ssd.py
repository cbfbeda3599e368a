"""The Mamba-2 decode step's state update and read in one pass (TPU).

XLA fuses the recurrence's one-token update into the in-place write of
the state and its read against C into a reduction of its own, and each
of the two reads the whole matrix: three passes over the state a layer
where two (one read, one write) are the least. This kernel makes the
two: a grid step holds one slot's matrices of a block of head rows in
VMEM, computes the decayed matrix plus the row's outer product, reads it
against C, and writes it back in place (the state is an aliased
operand, so only the layer's blocks move).

The state is laid out [layers, slots, G, N, W]: W = r P lanes hold the
P channels of r heads side by side (r = 128 / P where the heads divide
into such rows: 2 at the published P of 64), G = H / r rows of heads,
N = d_state on the sublanes. Every operand of the update is then a row
or a column of the tile: the decay and delta x are rows [1, W] (a
head's number repeated over its P lanes), B and C columns [N, 1], and
`y = sum_n new[n] C[n]` a reduction over the sublanes, so nothing is
transposed.

    new = where(active, decay * S + B (x) dx, S)     [N, W] a row of heads
    y   = sum over N of new * C                      [1, W]

A row's result and its matrices are a function of that row alone.
Runs in interpret mode off-TPU so the same tests drive both.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.nn.helpers.pallas_conv import _interpret

# the state block of one grid step (single-buffered: the pipeline holds
# two of each of the input and the aliased output)
_BLOCK_BUDGET = 2 * 2**20


def heads_per_row(n_heads: int, head_dim: int) -> int:
    """How many heads' P channels share a row of lanes: the most, up to
    128 // P, by halves, that divide the heads."""
    r = max(1, 128 // head_dim)
    while n_heads % r:
        r //= 2
    return r


def _kernel(active_ref, decay_ref, dx_ref, b_ref, c_ref, s_ref, y_ref,
            out_ref, *, rows: int):
    keep = active_ref[pl.program_id(0)] != 0
    b, c = b_ref[0], c_ref[0]                                # [N, 1]
    for g in range(rows):
        s = s_ref[0, g]                                      # [N, W]
        new = decay_ref[0, g:g + 1] * s + b * dx_ref[0, g:g + 1]
        y_ref[0, g:g + 1] = jnp.sum(new * c, axis=0, keepdims=True)
        out_ref[0, g] = jnp.where(keep, new, s)


def ssd_step(state, layer: int, decay, dx, b, c, active):
    """state [L, S, G, N, W] float32 (donated through: the result is the
    same buffer with layer `layer` advanced), decay, dx [S, G, W], b, c
    [S, N], active [S] bool -> (y [S, G, W], state)."""
    _, slots, groups, n, w = state.shape
    rows = groups
    while rows > 1 and (rows * n * w * 4 > _BLOCK_BUDGET or groups % rows):
        rows //= 2
    f32 = jnp.float32

    def at_slot(i, j, act):
        return i, j, 0

    def col(i, j, act):
        return i, 0, 0

    def state_block(i, j, act):
        return layer, i, j, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(slots, groups // rows),
        in_specs=[pl.BlockSpec((1, rows, w), at_slot),
                  pl.BlockSpec((1, rows, w), at_slot),
                  pl.BlockSpec((1, n, 1), col),
                  pl.BlockSpec((1, n, 1), col),
                  pl.BlockSpec((None, 1, rows, n, w), state_block)],
        out_specs=[pl.BlockSpec((1, rows, w), at_slot),
                   pl.BlockSpec((None, 1, rows, n, w), state_block)])
    block = rows * n * w * 4
    y, state = pl.pallas_call(
        functools.partial(_kernel, rows=rows), grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((slots, groups, w), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 5 (after the scalar prefetch): the state, in place
        input_output_aliases={5: 1},
        interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=4 * block + 8 * 2**20),
        cost_estimate=pl.CostEstimate(
            flops=5 * slots * groups * n * w,
            bytes_accessed=2 * slots * groups * n * w * 4,
            transcendentals=0),
    )(active.astype(jnp.int32), decay, dx, b[..., None].astype(f32),
      c[..., None].astype(f32), state)
    return y, state
