"""A chunk of the KDA delta rule with the state held on the chip.

``kda_scan(q, k, v, log_decay, beta, s0, n_valid, continues)`` advances every (row, head)
pair's ``[d, d]`` float32 state over the first ``n_valid[row]`` tokens of a
``[B, T, H, d]`` chunk by :func:`kda_step`'s recurrence (this file's last
function: a decode step's one token, in ``jax.numpy``), in that function's
operations and order, all float32 on the vector unit (no
product on the MXU, which would round to bfloat16):

    S = S * exp(log_decay)[:, None]           decay, a factor a key channel
    u = v - k^T S
    S = S + (beta k)[:, None] * u[None, :]    the rank-one update
    o = S^T q

A ``lax.scan`` over the tokens carries the rows' whole state through HBM once a
token; here a grid step holds its pairs' state in the chip's fast memory, reads
it once and writes it once after the row's last valid token. Valid tokens are a
prefix of a row; positions past them are never computed and their outputs are
zeros, and a row without a valid token gets its state back bit for bit.

**A sequence may fill several consecutive rows**, an earlier piece of it in
an earlier row (a lane's rows of one chunk dispatch:
``models/kimi_linear.py``, ``models/qwen3_next.py``), as in
``ops/pallas/selective_scan.py``. A row that ``continues`` the row above it
starts from the state that row ends with, and not from ``s0[row]``: the grid
walks the rows IN ORDER for each group of heads, and the state's block is
indexed by the sequence's FIRST row (a prefetched scalar beside ``n_valid``),
so the block stays on the chip from that row's first token to the last valid
token of the sequence's last row. ``s0`` is loaded where a row starts a
sequence, the state goes to HBM once a SEQUENCE, at its first row, and the
rows that continue have no entry of their own in the result. A row that
continues nothing (a padding row between two sequences too) takes nothing and
hands nothing on: the token's body knows nothing of rows. Told nothing
(``continues=None``) every row is a sequence, in the same one call: the
kernel with no row continuing.

**The layout is the kernel.** The state lies key channel by sublane and value
channel by lane, so both sums over the key channels are plain vector adds. What
a token costs beside them is the spread of its decay, k and q, vectors along the
key channels, along the lanes: one permute a register of 8 key channels on the
cross-lane unit, and a head alone needs 48 a token, which take longer than its
arithmetic (PERF.md, PR 40). So a grid step takes ``HEADS`` heads of a row
together: a register's 128 lanes are ``HEADS`` heads x a slice of ``d / HEADS``
value channels, the state of the group is ``HEADS`` such parts (one a slice),
and ONE permute spreads a token's coefficient of all ``HEADS`` heads for all the
parts (its source holds, head by head, ``d / HEADS`` tokens a head along the
lanes). The parts are independent, which is also what keeps the vector unit
busy while a part's sums finish. Bringing q, k, v, beta and the state into that
layout and the outputs and the state back, and the exponential, are XLA's,
around the kernel.

On the TPU the kernel tiles heads of 128 channels and chunks of whole tiles of
128 tokens; any other shape there is a ``ValueError`` that says so (interpreted
on the CPU, as the tests run it, every shape goes: a chunk is padded to whole
source registers).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
HEADS = 4  # heads of a row a grid step takes together, where the heads divide by it


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_scan(
    q: jax.Array,  # [B, T, H, d] float32, as is k, v and log_decay
    k: jax.Array,
    v: jax.Array,
    log_decay: jax.Array,
    beta: jax.Array,  # [B, T, H]
    s0: jax.Array,  # [B, H, d, d] float32: the rows' state before the chunk
    n_valid: jax.Array,  # [B] int32: a row's valid tokens, a prefix of it
    continues: Optional[jax.Array] = None,  # [B] bool: the row goes on where the row above it ends
    *,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """(outputs ``[B, T, H, d]``, zeros past a row's valid tokens; the state
    ``[B, H, d, d]`` after the last valid token of each SEQUENCE, at the
    sequence's first row: a row that ``continues`` the row above it, a full
    row, belongs to that row's sequence, and its own entry of the state is
    never written. ``None``: no row continues, a row a sequence.)"""
    b, t_in, h, d = q.shape
    p = max(n for n in (HEADS, 2, 1) if h % n == 0 and d % n == 0)  # heads a step, parts of a state
    seg = d // p  # value channels of a part, and tokens of a head in a source register
    if not interpret and (d != LANES or t_in % LANES):
        raise ValueError(
            f"kda_scan on the TPU takes heads of {LANES} channels and chunks of whole tiles "
            f"of {LANES} tokens, not a head of {d} and a chunk of {t_in}")
    t = -(-t_in // seg) * seg
    tile = t if interpret else LANES
    groups = h // p

    # a sequence's first row: the row itself, or where it continues, that of the row above it
    first = jnp.arange(b, dtype=jnp.int32)
    if continues is not None:
        first = jax.lax.cummax(jnp.where(continues, 0, first))

    def kernel(n, first, q, k, decay, v, beta, s0, o, s):
        r, at = pl.program_id(1), pl.program_id(2)

        # a row that continues finds its sequence's state where the row above left it: in ``s``
        @pl.when((at == 0) & (first[r] == r))
        def _():
            s[...] = s0[...]

        o[...] = jnp.zeros_like(o)
        head = jax.lax.broadcasted_iota(jnp.int32, (d, d), 1) // seg * seg

        def token(i, carry):
            row = pl.ds(i, 1)
            lane = head + i % seg  # where token i of each head lies in its source register
            decay_i, k_i, q_i = (jnp.take_along_axis(ref[i // seg], lane, axis=1)
                                 for ref in (decay, k, q))  # [d, d]: key channel x (head, any)
            bk = beta[row, :] * k_i
            for part in range(p):
                state = s[part] * decay_i
                ks = jnp.sum(k_i * state, axis=0, keepdims=True)  # k^T S: [1, d]
                state = state + bk * (v[part, row, :] - ks)
                o[part, row, :] = jnp.sum(q_i * state, axis=0, keepdims=True)
                s[part] = state
            return carry

        tokens = jnp.clip(n[r] - at * tile, 0, tile)
        # two tokens a trip: the second's permutes run under the first's arithmetic
        jax.lax.fori_loop(0, tokens // 2, lambda i, c: token(2 * i + 1, token(2 * i, c)), 0)
        jax.lax.fori_loop(tokens // 2 * 2, tokens, token, 0)

    def by_lane(a):
        """``[B, T, H, d]`` as ``[B, groups, T / seg, d, (head of the group, token)]``."""
        a = a.reshape(b, t // seg, seg, groups, p, d)
        return jnp.transpose(a, (0, 3, 1, 5, 4, 2)).reshape(b, groups, t // seg, d, d)

    def by_row(a):
        """``[B, T, H, d]`` as ``[B, groups, part, T, (head of the group, channel of the part)]``."""
        a = a.reshape(b, t, groups, p, p, seg)
        return jnp.transpose(a, (0, 2, 4, 1, 3, 5)).reshape(b, groups, p, t, d)

    def parts_of(s):
        """``[B, H, d, d]`` as ``[B, groups, part, d, (head, channel)]``, and back."""
        s = s.reshape(b, groups, p, d, p, seg)
        return jnp.transpose(s, (0, 1, 4, 3, 2, 5)).reshape(b, groups, p, d, d)

    pad = ((0, 0), (0, t - t_in)) + ((0, 0),) * 2
    q, k, v, decay = (jnp.pad(a, pad) for a in (q, k, v, jnp.exp(log_decay)))
    beta = jnp.repeat(jnp.pad(beta, pad[:3]).reshape(b, t, groups, p), seg, axis=-1)  # [B, T, groups, d]

    # the grid: (head group j, row i, token tile a), the rows of a head group in order
    source = pl.BlockSpec((None, None, tile // seg, d, d), lambda j, i, a, n, first: (i, j, a, 0, 0))
    rows = pl.BlockSpec((None, None, p, tile, d), lambda j, i, a, n, first: (i, j, 0, a, 0))
    # one block a sequence: it stays where it is while the rows that follow continue it
    state = pl.BlockSpec((None, None, p, d, d), lambda j, i, a, n, first: (first[i], j, 0, 0, 0))
    o, s = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((b, groups, p, t, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, groups, p, d, d), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[source, source, source, rows,
                      pl.BlockSpec((None, None, tile, d), lambda j, i, a, n, first: (i, j, a, 0)), state],
            out_specs=(rows, state),
            grid=(groups, b, t // tile),
        ),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="kda_scan",
    )(n_valid.astype(jnp.int32), first, by_lane(q), by_lane(k), by_lane(decay), by_row(v),
      jnp.transpose(beta, (0, 2, 1, 3)), parts_of(s0))
    o = jnp.transpose(o.reshape(b, groups, p, t, p, seg), (0, 3, 1, 4, 2, 5)).reshape(b, t, h, d)
    return o[:, :t_in], parts_of(s).reshape(b, h, d, d)


def kda_step(s, q, k, v, log_decay, beta):
    """One token of the delta rule on ``s`` ``[B, H, d_k, d_v]``, all float32
    and elementwise (the MXU would round a float32 product to bfloat16):
    ``S = (I - beta k k^T) Diag(alpha) S + beta k v^T``, ``o = S^T q``: what
    :func:`kda_scan` does a token, for the one token of a decode step.
    ``log_decay`` ``[B, H, d_k]`` is a factor a key channel (Kimi-Linear's
    KDA) or ``[B, H, 1]``, one a head (Qwen3-Next's Gated DeltaNet). Written
    under the kernel and not over it: a Mosaic payload holds its callers'
    lines."""
    s = s * jnp.exp(log_decay)[..., None]
    ks = jnp.sum(k[..., None] * s, axis=-2)  # k^T S: [B, H, d_v]
    s = s + (beta[..., None] * k)[..., None] * (v - ks)[..., None, :]
    return s, jnp.sum(q[..., None] * s, axis=-2)
