"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
Hyper-Connections, arXiv:2409.19606): the residual path of
``models/xing4.py``.

A token's state is ``n`` residual streams of ``C`` values. Around every
sublayer ``F`` three maps are computed FROM the state (``x̂`` = the state's
``n C`` values over their root mean square, no weight; ``[p, q, r] = x̂ φ``):

    H_pre  = sigmoid(α_pre p + b_pre)                      [n]     what F sees of each stream
    H_post = 2 sigmoid(α_post q + b_post)                  [n]     what each stream takes of F's output
    H_res  = sinkhorn(exp(clip(α_res mat(r) + b_res)))     [n, n]  how the streams mix: doubly stochastic
    u = Σ_j H_pre[j] X_j;   y = F(u);   X_i <- Σ_j H_res[i, j] X_j + H_post[i] y

All of it float32: the maps decide how every later layer is fed, as a router
decides which expert computes, so ``x̂ φ`` is ``ops/latent.py:mm`` (three
bfloat16 parts against the bfloat16 ``φ``). The streams are a TUPLE of ``n``
arrays ``[..., C]`` (an ``[n, C]``-minor array would be padded to eight
sublanes by the chip's tiling), and ``φ`` is held transposed, ``[2n + n², n
C]``, its minor axis whole registers. The Sinkhorn sweeps (``iters`` x a
division by the column sums, then by the row sums: 2 x ``iters`` DEPENDENT
normalisations) run with the token rows in the minor axis, ``[n, n, rows]``:
a sum over columns or rows is then ``n`` - 1 additions of whole registers, no
reduction across lanes, and the sweeps are elementwise work that fuses.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.ops.latent import wdot

Streams = Tuple[jax.Array, ...]  # n arrays [..., C] float32


# sweeps unrolled into one trip of the loop: the chip's compiler makes 9 kernels of five sweeps (36
# a call with its four trips; 60 at one sweep a trip, 30 with all twenty unrolled, which is 1,280
# operations a sublayer to compile: the decode program's compile went from 17 to 40 s)
SWEEPS_A_TRIP = 5


def sinkhorn(m: jax.Array, iters: int, eps: float) -> jax.Array:
    """``iters`` sweeps over the positive ``m`` ``[n, n, ...]`` (row, column,
    then anything): every column divided by its sum + ``eps``, then every row
    by its sum + ``eps``. The ``n²`` entries are ``n²`` arrays over the token
    rows and a sum is ``n - 1`` additions of them: nothing but elementwise
    work, which the chip's compiler fuses (the same sweeps as ``m.sum(axis)``
    over one array are two kernels a normalisation, 81 a call)."""
    n = m.shape[0]

    def sweep(_, e):
        cols = [sum(e[i][j] for i in range(n)) + eps for j in range(n)]
        e = [[e[i][j] / cols[j] for j in range(n)] for i in range(n)]
        rows = [sum(e[i][j] for j in range(n)) + eps for i in range(n)]
        return tuple(tuple(e[i][j] / rows[i] for j in range(n)) for i in range(n))

    e = jax.lax.fori_loop(0, iters, sweep, tuple(tuple(m[i, j] for j in range(n)) for i in range(n)),
                          unroll=SWEEPS_A_TRIP)
    return jnp.stack([jnp.stack(row) for row in e])


def mhc_maps(streams: Streams, phi: jax.Array, b: jax.Array, alpha: jax.Array, iters: int,
             hc_eps: float, rms_eps: float, clamp: Tuple[float, float]):
    """The three maps of one sublayer for the tokens of ``streams`` (``n``
    arrays ``[..., C]``): (``H_pre`` ``[..., n]``, ``H_post`` ``[..., n]``,
    ``H_res`` ``[..., n, n]``), float32. ``phi`` ``[2n + n², n C]`` (held
    transposed), ``b`` ``[2n + n²]``, ``alpha`` ``[3]`` (pre, post, res)."""
    n = len(streams)
    flat = jnp.concatenate(streams, axis=-1).astype(jnp.float32)  # vec(X), stream-major
    x_hat = flat * jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + rms_eps)
    pqr = wdot("...e,fe->...f", x_hat, phi)
    # the token rows into the minor axis: [2n + n², ...]
    pqr = jnp.moveaxis(pqr, -1, 0)
    alpha, b = alpha.astype(jnp.float32), b.astype(jnp.float32)
    bias = b.reshape(-1, *([1] * (pqr.ndim - 1)))
    h_pre = jax.nn.sigmoid(alpha[0] * pqr[:n] + bias[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * pqr[n:2 * n] + bias[n:2 * n])
    logits = jnp.clip(alpha[2] * pqr[2 * n:] + bias[2 * n:], *clamp)
    h_res = sinkhorn(jnp.exp(logits).reshape(n, n, *pqr.shape[1:]), iters, hc_eps)
    return jnp.moveaxis(h_pre, 0, -1), jnp.moveaxis(h_post, 0, -1), jnp.moveaxis(h_res, (0, 1), (-2, -1))


def mix_in(streams: Streams, h_pre: jax.Array) -> jax.Array:
    """``u = Σ_j H_pre[j] X_j``: what the sublayer sees, ``[..., C]``."""
    return sum(h_pre[..., j, None] * x for j, x in enumerate(streams))


def mix_out(streams: Streams, h_res: jax.Array, h_post: jax.Array, y: jax.Array) -> Streams:
    """``X_i <- Σ_j H_res[i, j] X_j + H_post[i] y``."""
    return tuple(
        sum(h_res[..., i, j, None] * x for j, x in enumerate(streams)) + h_post[..., i, None] * y
        for i in range(len(streams)))


def spread(x: jax.Array, n: int) -> Streams:
    """The streams start as ``n`` copies of the embedding."""
    return (x,) * n


def collapse(streams: Sequence[jax.Array]) -> jax.Array:
    """... and end as their sum."""
    return sum(streams)
