"""A chunk of Mamba's selective scan with the state held on the chip.

``selective_scan(delta, x, b, c, a, s0, n_valid, continues)`` advances every
row's ``[N, D]`` float32 state over the first ``n_valid[row]`` tokens of a
``[B, T, D]`` chunk by ``models/jamba.py:_scan_tokens``'s recurrence, in its
operations, all float32 on the vector unit (nothing goes to the MXU, which
would round to bfloat16):

    s = exp(delta_t * A) * s + (delta_t * x_t) * B_t
    y_t = sum over N of s * C_t          (n = 0 first, in order)

A ``lax.scan`` over the tokens carries the rows' whole state through HBM once a
token; here a grid step holds its part of a row's state in registers from the
row's first token to its last valid one, reads it once and writes it once.
Valid tokens are a prefix of a row; positions past them are never computed and
their outputs are zeros, and a row without a valid token gets its state back
bit for bit. ``exp(delta A)`` is made a token at a time: ``[B, T, N, D]`` never
exists.

**A sequence may fill several consecutive rows**, an earlier piece of it in
an earlier row (a lane's rows of one chunk dispatch:
``models/jamba.py:forward_chunk``). A row that ``continues`` the row above it
starts from the state that row ends with, and not from ``s0[row]``: the grid
walks the rows IN ORDER for each block of channels, and the state's block is
indexed by the sequence's FIRST row (a prefetched scalar beside ``n_valid``),
so the block stays on the chip from that row's first token to the last valid
token of the sequence's last row. ``s0`` is loaded where a row starts a
sequence, the state goes to HBM once a SEQUENCE, at its first row, and the rows
that continue have no entry of their own in the result. A row that continues
nothing (a padding row between two sequences too) takes nothing and hands
nothing on; where no row continues, every row is a sequence and the results are
what a call a row gives, bit for bit: the token's body knows nothing of rows.

**The layout is the kernel.** The only vectors along N are ``B_t`` and ``C_t``
(16 numbers a token) and the only reduction is the sum over N. So a grid step
takes a row and ``GROUPS * LANES`` channels, its state is N registers of
``[GROUPS, LANES]`` channels, ``delta_t`` and ``x_t`` are one register each,
``B_t[n]`` and ``C_t[n]`` are scalars read from SMEM and splat, and the sum
over N is N - 1 vector adds. Nothing touches the cross-lane unit.

**And nothing is laid out around it.** The mixer's arrays have their tokens
(``delta``, ``x``, ``y``) or their state rows (``a``, the state) on the
sublanes: under the chip's (8, 128) tiling ``[R, D]`` lies in HBM as
``[R / 8, D / 128, 8, 128]``, so a token's channels are one sublane of each of
``D / 128`` tiles. :func:`_by_sublane` names that order as an array
``[R / 8, D / 128 * 8, 128]`` (a bitcast for the chip's compiler, which a
reshape to ``[R, D / 128, 128]`` is not: that one costs a copy of the array),
and the kernel reads row ``r`` of a block with ONE strided load: sublanes ``r,
r + 8, ...`` of the block, ``GROUPS`` of them, and writes it back the same
way. So the chunk's arrays go through HBM once each, as the mixer left them.

On the TPU the kernel tiles channels by ``GROUPS * LANES``; any other width
there is a ``ValueError`` that says so (interpreted on the CPU, as the tests
run it, every width goes: one block holds a row's channels whole). State rows
come in eights everywhere. A chunk is padded to whole sublane tiles of 8
tokens, and one longer than ``TILE`` tokens to whole tiles of ``TILE``, taken
one a grid step.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
GROUPS = 8  # a grid step's channels are GROUPS x LANES: one register a state row
TILE = 128  # tokens of a row a grid step takes


def _by_sublane(v: jax.Array, lanes: int) -> jax.Array:
    """``[..., R, D]`` as ``[..., R / 8, D / lanes * 8, lanes]``: row ``r`` of
    channel group ``g`` at ``[r // 8, g * 8 + r % 8]``, the order the tiled
    array has in HBM."""
    *lead, r, d = v.shape
    v = v.reshape(*lead, r // SUBLANES, SUBLANES, d // lanes, lanes)
    return jnp.swapaxes(v, -3, -2).reshape(*lead, r // SUBLANES, d // lanes * SUBLANES, lanes)


def _by_row(v: jax.Array) -> jax.Array:
    """:func:`_by_sublane`'s inverse."""
    *lead, octets, rows, lanes = v.shape
    v = v.reshape(*lead, octets, rows // SUBLANES, SUBLANES, lanes)
    return jnp.swapaxes(v, -3, -2).reshape(*lead, octets * SUBLANES, rows // SUBLANES * lanes)


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan(
    delta: jax.Array,  # [B, T, D] float32: the step size after its softplus
    x: jax.Array,  # [B, T, D] float32
    b: jax.Array,  # [B, T, N] float32
    c: jax.Array,  # [B, T, N] float32
    a: jax.Array,  # [N, D] float32: -exp(a_log)
    s0: jax.Array,  # [B, N, D] float32: the rows' state before the chunk
    n_valid: jax.Array,  # [B] int32: a row's valid tokens, a prefix of it
    continues: Optional[jax.Array] = None,  # [B] bool: the row goes on where the row above it ends
    *,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """(``y`` ``[B, T, D]``, zeros past a row's valid tokens; the state
    ``[B, N, D]`` after the last valid token of each SEQUENCE, at the
    sequence's first row: a row that ``continues`` the row above it, a full
    row, belongs to that row's sequence, and its own entry of the state is
    never written. ``None``: no row continues, a row a sequence.)"""
    rows, t_in, d = delta.shape
    n = a.shape[0]
    if n % SUBLANES:
        raise ValueError(f"selective_scan takes state rows in eights, not {n}")
    if not interpret and d % (GROUPS * LANES):
        raise ValueError(
            f"selective_scan on the TPU takes channels in blocks of {GROUPS * LANES} "
            f"({GROUPS} sublanes x {LANES} lanes), not a width of {d}")
    lanes = LANES if d % LANES == 0 else d
    groups = GROUPS if d % (GROUPS * lanes) == 0 else d // lanes
    tile = min(TILE, -(-t_in // SUBLANES) * SUBLANES)
    t = -(-t_in // tile) * tile

    def row(r):
        """Where row ``r`` of a block in :func:`_by_sublane`'s order lies: ``[groups, lanes]``.
        (A shift and a mask: ``//`` and ``%`` of a signed token index are a dozen scalar
        operations a load.)"""
        return r >> 3, pl.ds(r & (SUBLANES - 1), groups, stride=SUBLANES), slice(None)

    def kernel(n_valid, first, delta, x, b, c, a, s0, y, s, a_rows):
        r, at = pl.program_id(1), pl.program_id(2)

        # a row that continues finds its sequence's state where the row above left it: in ``s``
        @pl.when((at == 0) & (first[r] == r))
        def _():
            s[...] = s0[...]

        y[...] = jnp.zeros_like(y)
        for j in range(n):  # gathered once a grid step: a token then reads A's rows with plain loads
            a_rows[j] = a[row(j)]

        def token(i, state):
            d_i = delta[row(i)]
            dx = d_i * x[row(i)]
            new, out = [], None
            for j in range(n):
                s_j = jnp.exp(d_i * a_rows[j]) * state[j] + dx * b[i * n + j]
                term = s_j * c[i * n + j]
                out = term if out is None else out + term
                new.append(s_j)
            y[row(i)] = out
            return tuple(new)

        tokens = jnp.clip(n_valid[r] - at * tile, 0, tile)
        state = tuple(s[row(j)] for j in range(n))
        # two tokens a trip: the loop's copies of the carried registers are paid once for both
        state = jax.lax.fori_loop(0, tokens // 2, lambda i, st: token(2 * i + 1, token(2 * i, st)), state)
        state = jax.lax.fori_loop(tokens // 2 * 2, tokens, token, state)
        for j in range(n):
            s[row(j)] = state[j]

    pad = ((0, 0), (0, t - t_in), (0, 0))
    by_token = lambda v: jnp.pad(v, pad).reshape(rows, t // tile, tile * n)  # noqa: E731
    width, whole = groups * SUBLANES, d // lanes * SUBLANES  # sublanes of a block, of a row's channels

    # a sequence's first row: the row itself, or where it continues, that of the row above it
    first = jnp.arange(rows, dtype=jnp.int32)
    if continues is not None:
        first = jax.lax.cummax(jnp.where(continues, 0, first))

    # the grid: (channel block j, row i, token tile k), the rows of a channel block in order
    chunk = pl.BlockSpec((None, tile // SUBLANES, width, lanes), lambda j, i, k, n_valid, first: (i, k, j, 0))
    scalars = pl.BlockSpec((None, None, tile * n), lambda j, i, k, n_valid, first: (i, k, 0),
                           memory_space=pltpu.SMEM)
    # one block a sequence: it stays where it is while the rows that follow continue it
    state = pl.BlockSpec((None, n // SUBLANES, width, lanes), lambda j, i, k, n_valid, first: (first[i], 0, j, 0))
    y, s = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((rows, t // SUBLANES, whole, lanes), jnp.float32),
                   jax.ShapeDtypeStruct((rows, n // SUBLANES, whole, lanes), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[chunk, chunk, scalars, scalars,
                      pl.BlockSpec((n // SUBLANES, width, lanes), lambda j, i, k, n_valid, first: (0, j, 0)),
                      state],
            out_specs=(chunk, state),
            grid=(d // (groups * lanes), rows, t // tile),
            scratch_shapes=[pltpu.VMEM((n, groups, lanes), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="selective_scan",
    )(n_valid.astype(jnp.int32), first, _by_sublane(jnp.pad(delta, pad), lanes),
      _by_sublane(jnp.pad(x, pad), lanes), by_token(b), by_token(c),
      _by_sublane(a, lanes), _by_sublane(s0, lanes))
    return _by_row(y)[:, :t_in], _by_row(s)


ROWS = 16  # slots of a channel block a grid step of the step kernel holds


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_step(
    delta: jax.Array,  # [S, D] float32: every slot's one token, the step size after its softplus
    x: jax.Array,  # [S, D] float32
    b: jax.Array,  # [S, N] float32
    c: jax.Array,  # [S, N] float32
    a: jax.Array,  # [N, D] float32: -exp(a_log)
    s: jax.Array,  # [L, S, N, D] float32: the state of every slot, a run of layers whole
    layer: jax.Array,  # int32: the layer of the run this call advances
    valid: jax.Array,  # [S] bool: the slots that take the token
    *,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """A decode step of the same recurrence: ONE token of every slot, the
    chunk kernel's token body (the same products and sums in the same order, so
    a slot's state after a step is, bit for bit, what the chunk kernel leaves
    after a chunk of that one token), a slot's state read from HBM once and
    written once. (``y`` ``[S, D]``, zeros where a slot is not valid; ``s``
    with layer ``layer`` advanced, a slot that is not valid bit for bit as it
    was.)

    The run's whole state is the operand, in :func:`_by_sublane`'s order (a
    bitcast), ALIASED to the output, and the layer's index rides in the block
    index map: the kernel updates layer ``layer`` in place and touches no
    other, so a loop over the layers carries the run's state and nothing is
    sliced out of it or stacked back around the call (either would be a pass
    of its own). A grid step takes ``ROWS`` slots of ``GROUPS * LANES``
    channels, 1 MB of state in and 1 MB out: under it the step's overhead
    shows, over it nothing is gained and the first load and the last store,
    which nothing hides, grow. The slots' tokens lie on the sublanes as a
    chunk's tokens do, so slot ``r`` of a block is the same strided load.
    Slots past a multiple of ``ROWS`` are a part block of the state; the token
    arrays are padded to it."""
    slots, d = delta.shape
    n = a.shape[0]
    if n % SUBLANES:
        raise ValueError(f"selective_step takes state rows in eights, not {n}")
    if not interpret and d % (GROUPS * LANES):
        raise ValueError(
            f"selective_step on the TPU takes channels in blocks of {GROUPS * LANES} "
            f"({GROUPS} sublanes x {LANES} lanes), not a width of {d}")
    lanes = LANES if d % LANES == 0 else d
    groups = GROUPS if d % (GROUPS * lanes) == 0 else d // lanes
    blocks = -(-slots // ROWS)

    def row(r):
        return r >> 3, pl.ds(r & (SUBLANES - 1), groups, stride=SUBLANES), slice(None)

    def kernel(layer, valid, delta, x, b, c, a, s0, y, s, a_rows):
        first = pl.program_id(1) * ROWS
        for j in range(n):
            a_rows[j] = a[row(j)]

        def slot(r, _):
            live = valid[first + r] != 0

            @pl.when(live)
            def _():
                d_r = delta[row(r)]
                dx = d_r * x[row(r)]
                out = None
                for j in range(n):
                    s_j = jnp.exp(d_r * a_rows[j]) * s0[(r, *row(j))] + dx * b[r * n + j]
                    term = s_j * c[r * n + j]
                    out = term if out is None else out + term
                    s[(r, *row(j))] = s_j
                y[row(r)] = out

            @pl.when(jnp.logical_not(live))
            def _():
                s[r] = s0[r]
                y[row(r)] = jnp.zeros((groups, lanes), jnp.float32)

        jax.lax.fori_loop(0, ROWS, slot, None)

    pad = ((0, blocks * ROWS - slots), (0, 0))
    by_slot = lambda v: jnp.pad(v, pad).reshape(blocks, 1, ROWS * n)  # noqa: E731
    width, whole = groups * SUBLANES, d // lanes * SUBLANES  # sublanes of a block, of a slot's channels

    tokens = pl.BlockSpec((ROWS // SUBLANES, width, lanes), lambda j, i, layer, valid: (i, j, 0))
    scalars = pl.BlockSpec((None, None, ROWS * n), lambda j, i, layer, valid: (i, 0, 0),
                           memory_space=pltpu.SMEM)
    state = pl.BlockSpec((None, ROWS, n // SUBLANES, width, lanes),
                         lambda j, i, layer, valid: (layer[0], i, 0, j, 0))
    y, s = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((blocks * ROWS // SUBLANES, whole, lanes), jnp.float32),
                   jax.ShapeDtypeStruct((*s.shape[:2], n // SUBLANES, whole, lanes), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[tokens, tokens, scalars, scalars,
                      pl.BlockSpec((n // SUBLANES, width, lanes), lambda j, i, layer, valid: (0, j, 0)),
                      state],
            out_specs=(tokens, state),
            grid=(d // (groups * lanes), blocks),  # a channel block's slots in turn: A's rows stay
            scratch_shapes=[pltpu.VMEM((n, groups, lanes), jnp.float32)],
        ),
        input_output_aliases={7: 1},  # the state, after the two prefetched scalars and five arrays
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="selective_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), jnp.pad(valid.astype(jnp.int32), pad[0]),
      _by_sublane(jnp.pad(delta, pad), lanes), _by_sublane(jnp.pad(x, pad), lanes),
      by_slot(b), by_slot(c), _by_sublane(a, lanes), _by_sublane(s, lanes))
    return _by_row(y)[:slots], _by_row(s)
