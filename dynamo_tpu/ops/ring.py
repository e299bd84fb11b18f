"""Attention over a ring of positions a slot: a window layer's cache.

A layer whose queries never look further back than ``W`` positions needs no
page of a lane that lies further back, however long the lane grows. Its cache
here is ONE array ``[S, KVH, P, D]``: slot ``s`` owns ``ring[s]``, and position
``p`` of the lane in that slot lies at entry ``p % P`` of every KV head's row
(``P >= W``: a step program reads the ring before it writes it, so what a
dispatch writes may overwrite only what lies ``P`` or more positions behind it,
which no query of that dispatch or a later one sees). The heads are the major
axis of a slot's ring, the positions the next: the layout in which a product
over ``(slot, head)`` pairs reads the ring where it lies, so a decode dispatch
attends it IN PLACE (no gather: the ring is the lane's dense history), and a
chunk's rows take their lanes' rings a tile of entries a trip.

Keys are stored rotated, so an entry needs no position beside it but the mask:
entry ``e`` of a lane whose next position is ``n`` holds position ``n - 1 -
((n - 1 - e) mod P)`` (:func:`held_positions`), live iff that is ``>= 0`` and
inside the query's window. Nothing resets a ring: a slot's new request starts
at position 0, and what the last one left is then further back than any mask
lets through.

The partials are in ``models/llama.py``'s form (numerator ``[B, T, H, D]``,
row maximum and denominator ``[B, H, T]``), so its flash merge folds them with
the partials over a dispatch's fresh keys.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.models.llama import _merge_partials

Partial = Tuple[jax.Array, jax.Array, jax.Array]

# entries of a ring a trip of `chunk_ring_partial` reads, at most: 48 heads' scores of a group of
# 8 rows of 128 queries against them are 100 MB of float32, and a tile is whole registers wide
RING_TILE = 512


def ring_tile(window: int) -> int:
    """Entries a trip reads: the largest power of two up to ``RING_TILE`` that
    divides the window, so that the window's entries are whole tiles (what a
    ring holds past them, one block, is read as one last short tile)."""
    return math.gcd(window, RING_TILE)


def held_positions(next_pos: jax.Array, ring_len: int) -> jax.Array:
    """``[..., P]``: the position each ring entry holds for a lane whose next
    position is ``next_pos`` ``[...]`` (what the lane has written is the
    positions under it); < 0 where the entry holds nothing of this lane (a
    lane that starts, a padding row's -1)."""
    last = next_pos[..., None] - 1
    return last - jnp.mod(last - jnp.arange(ring_len), ring_len)


def ring_trips(next_pos: jax.Array, window: int):
    """Trips of :func:`chunk_ring_partial` over the window's whole tiles for
    rows whose lanes' next positions are ``next_pos`` ``[B]``: a ring fills from
    entry 0, so a lane under ``window`` positions holds history in its first
    tiles alone, and one at or past it in all of them; padding rows (< 0) and
    lanes that start (0) ask for none."""
    tile = ring_tile(window)
    return ((jnp.clip(next_pos, 0, window) + tile - 1) // tile).max()


def masked_partial(q: jax.Array, k: jax.Array, v: jax.Array, sees: jax.Array,
                   scale: float) -> Partial:
    """Flash partial of queries ``q`` ``[B, T, H, D]`` against keys and values
    ``[B, KVH, E, D]`` (the ring's layout: heads major), query ``t`` of row
    ``b`` seeing entry ``e`` iff ``sees[b, t, e]``. A query that sees nothing
    leaves the empty partial."""
    b, t, h, d = q.shape
    kvh = k.shape[1]
    qg = q.reshape(b, t, kvh, h // kvh, d)
    scores = jnp.einsum(
        "btngd,bnsd->bngts", qg, k, preferred_element_type=jnp.float32) * scale
    scores = jnp.where(sees[:, None, None], scores, -jnp.inf)
    m = jnp.maximum(scores.max(axis=-1), -1e30)
    p = jnp.exp(scores - m[..., None])
    num = jnp.einsum("bngts,bnsd->btngd", p, v.astype(jnp.float32))
    return num.reshape(b, t, h, d), m.reshape(b, h, t), p.sum(axis=-1).reshape(b, h, t)


def in_window(held: jax.Array, q_pos: jax.Array, window: int) -> jax.Array:
    """``[B, T, E]``: query at ``q_pos`` ``[B, T]`` (< 0: padding) sees the
    entry that holds position ``held`` ``[B, E]`` (< 0: nothing): it is
    history, and fewer than ``window`` positions behind the query. (What a
    ring holds lies under the dispatch's first query, so it is causal as it
    is.)"""
    return ((held >= 0)[:, None, :] & (q_pos >= 0)[:, :, None]
            & (q_pos[:, :, None] - held[:, None, :] < window))


def empty_partial(q: jax.Array) -> Partial:
    b, t, h, d = q.shape
    return (jnp.zeros((b, t, h, d), jnp.float32), jnp.full((b, h, t), -1e30, jnp.float32),
            jnp.zeros((b, h, t), jnp.float32))


def chunk_ring_partial(
    q: jax.Array,  # [B, T, H, D] one window layer's chunk queries (rotated)
    ring_k: jax.Array,  # [S, KVH, P, D] the layer's ring, read and not written
    ring_v: jax.Array,
    lanes: jax.Array,  # [B] each row's slot, clipped into the ring
    next_pos: jax.Array,  # [B] where the row's lane's FIRST row of the dispatch starts
    n_trips,  # :func:`ring_trips` of these rows
    q_pos: jax.Array,  # [B, T]; < 0 = padding
    window: int,
    scale: float,
) -> Partial:
    """Flash partial of a chunk's rows against their lanes' rings: the
    window's entries a tile of :func:`ring_tile` a trip in the ring's own order
    (a tile is a slice of the ring where it lies, taken for the rows' lanes
    alone) and ``n_trips`` trips, then the entries past the window (one block)
    as one short tile. No trip leaves that last tile's partial alone, which is
    empty for a lane under ``window`` positions. A row's ring history ends
    where its lane's first row of the dispatch starts; the rows between are
    fresh keys in the caller's hands."""
    ring_len = ring_k.shape[2]
    tile = ring_tile(window)
    held = held_positions(next_pos, ring_len)  # [B, P]

    def against(start, size, acc):
        def taken(ring):
            return jnp.take(jax.lax.dynamic_slice_in_dim(ring, start, size, axis=2), lanes, axis=0)

        sees = in_window(jax.lax.dynamic_slice_in_dim(held, start, size, axis=1), q_pos, window)
        return _merge_partials(acc, masked_partial(q, taken(ring_k), taken(ring_v), sees, scale))

    acc = jax.lax.fori_loop(0, n_trips, lambda i, acc: against(i * tile, tile, acc), empty_partial(q))
    return against(window, ring_len - window, acc) if ring_len > window else acc


def attended(part: Partial) -> jax.Array:
    """``[B, T, H, D]``: a partial normalised; zeros where a query saw nothing."""
    num, _, den = part
    den = den.transpose(0, 2, 1)[..., None]
    return jnp.where(den > 0.0, num / jnp.maximum(den, 1e-30), 0.0)


def ring_write(ring: jax.Array, new: jax.Array, positions: jax.Array, lanes: jax.Array) -> jax.Array:
    """``ring`` ``[S, KVH, P, D]`` with ``new`` ``[B, KVH, T, D]`` written at
    ``positions`` ``[B, T]`` of the slots ``lanes`` ``[B]``: ONE flat index
    over ``(slot, head, entry)``, which on a donated ring is an update in place
    (``ops/attention.py:write_kv_to_pool`` says why one index). A position < 0
    and a lane past the ring (a padding row) write nowhere. The caller sees to
    it that one call's positions of a lane are ``P`` at most."""
    s, kvh, ring_len, d = ring.shape
    live = (positions >= 0) & (lanes < s)[:, None]  # [B, T]
    at = ((lanes[:, None, None] * kvh + jnp.arange(kvh)[None, :, None]) * ring_len
          + jnp.mod(positions, ring_len)[:, None, :])  # [B, KVH, T]
    at = jnp.where(live[:, None, :], at, s * kvh * ring_len)
    flat = ring.reshape(s * kvh * ring_len, d)
    flat = flat.at[at.reshape(-1)].set(new.reshape(-1, d).astype(ring.dtype), mode="drop")
    return flat.reshape(ring.shape)
