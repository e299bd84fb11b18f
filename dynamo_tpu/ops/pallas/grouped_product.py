"""Grouped matrix product: rows sorted by group, each against its group's matrix.

``grouped_product(parts [P, M, K], w [G, K, N], schedule)`` is, for the rows of
group ``g`` (a run of the ``M`` sorted rows), ``sum over p of parts[p] @ w[g]``
in float32: the product ``ops/moe.py:dropless_experts`` takes three times. It
is the megablox kernel of ``jax.experimental.pallas.ops.tpu`` and its schedule
of (row tile, group) visits, with three things changed for a layer that
streams its weights: the parts of a row come stacked on a leading axis
and are summed in the kernel, so an expert's block is fetched once for all of
them and the output is ``[M, N]`` and not ``[P * M, N]``; the contracted
dimension is never cut, so the block of ``w`` under a run's tiles has ONE index
and the pipeline, which fetches a block only when its index moves, reads a
run's matrix once however many tiles it spans; and what the layer never uses
(an offset of groups, an existing output, a transposed ``w``) is left out.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# where each group's rows start [G + 1]; the group [V] and the row tile [V] of
# each visit, V the most there can be; the number of visits
Schedule = Tuple[jax.Array, jax.Array, jax.Array, jax.Array]


def make_schedule(group_sizes: jax.Array, rows: int, rows_per_tile: int) -> Schedule:
    """The visits of a product over ``rows`` sorted rows (whole tiles) in
    groups of ``group_sizes`` (int32), in order: every (row tile, group) with
    a row of the group in the tile, a group's visits one after another. Rows
    past the groups' are in no visit."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // rows_per_tile
    tiles_of = jnp.where(group_sizes > 0, (ends - 1) // rows_per_tile - first + 1, 0)
    until = jnp.cumsum(tiles_of)  # visits up to and with each group
    visit = jnp.arange(rows // rows_per_tile + group_sizes.shape[0] - 1, dtype=jnp.int32)
    group = jnp.sum(until[None, :] <= visit[:, None], axis=1).clip(0, group_sizes.shape[0] - 1)
    tile = first[group] + visit - (until - tiles_of)[group]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group, tile.clip(0, rows // rows_per_tile - 1), until[-1]


def columns_per_block(rows: int, columns: int, itemsize: int) -> int:
    """Columns of one block of a group's ``[rows, columns]`` matrix, whole in
    its rows: the most, in 128s and a divisor of ``columns``, that keep a block
    at 2.5 MB (two are in flight beside the tile); all, where none divides."""
    fit = [n for n in range(128, columns + 1, 128)
           if columns % n == 0 and rows * n * itemsize <= 5 << 19]
    return max(fit) if fit else columns


@functools.partial(jax.jit, static_argnames=("rows_per_tile", "interpret"))
def grouped_product(
    parts: jax.Array,  # [P, M, K]: M sorted rows in whole tiles, P parts of each
    w: jax.Array,  # [G, K, N]
    schedule: Schedule,
    *,
    rows_per_tile: int,
    interpret: bool = False,
) -> jax.Array:
    """``[M, N]`` float32; a row in no visit holds anything (not a number, in
    the interpreter), so the caller reads the rows it sorted there and no
    others."""
    p, m, k = parts.shape
    n = w.shape[2]
    tm, tn = rows_per_tile, columns_per_block(k, n, w.dtype.itemsize)
    offsets, groups, tiles, visits = schedule

    def kernel(offsets, groups, tiles, parts, w, out):
        i = pl.program_id(1)
        products = jnp.dot(parts[...].reshape(p * tm, k), w[...],
                           preferred_element_type=jnp.float32).reshape(p, tm, tn)
        total = products[p - 1]  # the smallest part first: the sum loses least
        for j in reversed(range(p - 1)):
            total = total + products[j]
        row = tiles[i] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
        mine = (row >= offsets[groups[i]]) & (row < offsets[groups[i] + 1])
        out[...] = jnp.where(mine, total, out[...])

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((p, tm, k), lambda j, i, offsets, groups, tiles: (0, tiles[i], 0)),
                pl.BlockSpec((None, k, tn), lambda j, i, offsets, groups, tiles: (groups[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, i, offsets, groups, tiles: (tiles[i], j)),
            grid=(n // tn, visits),
        ),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="grouped_product",
    )(offsets, groups, tiles, parts, w)
