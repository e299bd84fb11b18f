"""Latent attention (MLA) over a paged pool of latents: what
``models/kimi_linear.py`` and ``models/openpangu.py`` share.

A token's cache entry is ``[RMSNorm(c) ; k^r]``: the normed latent of
``kv_lora_rank`` values and ONE key part of ``qk_rope_head_dim`` that every
head shares (rotated where the model rotates). The pool holds them in one
member, ``latent`` ``[L, N, bs, W]`` with ``W >= rank + rope`` (a module may
pad a row to whole registers of 128 lanes: the columns past ``rank + rope``
hold zeros and a query meets them with zeros). Attention is taken in the
absorbed form: the query's no-position part goes through ``W_kvb``'s key half
into the latent space, scores and the weighted sum are taken against the
latents as they lie, and ``W_kvb``'s value half comes after; the same
mathematics as expanding keys and values from the latent at every step.
Three callers' forms share those two ends: :func:`attend_absorbed` over a
gathered ``[B, P, W]`` history under a mask (Kimi's chunk, every row's whole
table), :func:`attend_absorbed_tiled` for a chunk, which reads the rows' block
tables out of the pool a tile of positions a trip and stops at the tile that
holds the rows' last position (``models/openpangu.py``'s chunk programs), and
:func:`attend_absorbed_live` for a decode step of the three latent modules
(``models/openpangu.py``, ``models/xing4.py``, ``models/kimi_linear.py``),
over the tiles that hold history of the lanes' tables, which a dispatch
gathers once (:func:`live_latents`), and the dispatch's own steps in a small
buffer beside them.

The arithmetic is the modules' own (:func:`wdot`): float32 activations
against bfloat16 weights in ``PASSES`` bfloat16 parts, float32 against float32
(a score against the float32 latents) at the highest precision.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models.llama import (
    _merge_partials, apply_rope, history_tile, history_tiles_full, rms_norm,
)
from dynamo_tpu.ops.parts import HIGHEST, operand_parts

# -- products of float32 activations against bfloat16 weights ------------------
#
# The MXU multiplies bfloat16. A float32 activation handed to it is rounded to
# 8 bits of mantissa first (a relative error of up to 2^-9), and that is the
# noise a bfloat16 program carries from layer to layer. A dense model's answer
# moves with it smoothly. An expert model's does not: the router keeps the
# ``top_k`` largest of several hundred scores, the ninth lies a few percent of
# their spread below the eighth, and noise of a few tenths of a percent on the
# router's input swaps the two in several token-layers of a hundred: another
# expert computes, and the token's hidden state moves by a tenth (PERF.md: the
# model's section). So these models keep their activations in float32 on the whole
# path to their routers: the activation is split into ``PASSES`` bfloat16 parts
# (what is left of it after the parts before, rounded again), every part is
# multiplied exactly as bfloat16 against the weight (which IS bfloat16, so it
# needs no parts) and the products are added in float32. Three parts carry 24
# bits: float32's own.
#
# The weights are read once where it matters: a small activation (a decode
# step, which streams the weights and computes little) has its parts stacked
# into one product; a large one (a chunk of prompt, where the products are the
# work) takes one product a part, so that only one part's output is held.

PASSES = 3
# activations of at most this many elements are stacked into one product
STACK_UP_TO = 1 << 24


def wdot(spec: str, x: jax.Array, w: jax.Array) -> jax.Array:
    """``einsum(spec, x, w)`` in float32 for a float32 ``x``: the sum of its
    :func:`operand_parts`' products, at the highest precision where they are
    float32."""
    parts = operand_parts(x.astype(jnp.float32), w.dtype, PASSES)
    w = w.astype(parts[0].dtype)
    precision = HIGHEST if w.dtype == jnp.float32 else None
    if x.size <= STACK_UP_TO:
        ins, out = spec.split("->")
        both = jnp.einsum(f"Z{ins}->Z{out}", jnp.stack(parts), w, precision=precision,
                          preferred_element_type=jnp.float32)
        return both.sum(axis=0)
    # the smallest part first: the sum loses least
    return sum(jnp.einsum(spec, part, w, precision=precision, preferred_element_type=jnp.float32)
               for part in reversed(parts))


def mm(x: jax.Array, w: jax.Array) -> jax.Array:
    """``x @ w`` in float32: the activation against a weight matrix."""
    return wdot("...e,ef->...f", x, w)


# -- what the cache holds, and attention over it --------------------------------

def cached_latent(x: jax.Array, w_kva: jax.Array, kv_norm: jax.Array, rank: int, eps: float,
                  positions: Optional[jax.Array] = None, theta: Optional[float] = None,
                  width: Optional[int] = None, inv_freq=None) -> jax.Array:
    """What the cache holds of each token of ``x`` ``[B, T, E]``: ``[RMSNorm(c)
    ; k^r]``, the key part rotated at ``positions`` ``[B, T]`` where ``theta``
    is given (one head, shared by all; by ``inv_freq`` where the model scales
    its frequencies: ``models/llama.py:apply_rope``), and zeros up to ``width``
    where the pool's rows are wider."""
    kv = mm(x, w_kva)
    lat = rms_norm(kv[..., :rank], kv_norm, eps)
    k_r = kv[..., rank:]
    if theta is not None:
        k_r = apply_rope(k_r[:, :, None, :], positions, theta, inv_freq)[:, :, 0]
    held = [lat, k_r]
    if width is not None and width > kv.shape[-1]:
        held.append(jnp.zeros((*kv.shape[:-1], width - kv.shape[-1]), kv.dtype))
    return jnp.concatenate(held, axis=-1)


def _into_latent_space(q: jax.Array, w_kvb: jax.Array, rank: int, nope: int, v_dim: int, width: int):
    """The absorbed form's first end, which no key moves: (the queries ``q``
    ``[B, T, H, nope + rope]`` as they meet a cached row of ``width`` values,
    ``[B, T, H, width]``: the no-position part taken into the latent space by
    ``W_kvb``'s key half, the rotated part as it is, zeros where the row is
    padded; ``W_kvb`` as ``[rank, H, nope + v_dim]``)."""
    b, t, h, _ = q.shape
    w_kvb = w_kvb.reshape(rank, h, nope + v_dim)
    q_lat = wdot("bthd,rhd->bthr", q[..., :nope], w_kvb[..., :nope])
    held = [q_lat, q[..., nope:]]
    if width > rank + q.shape[-1] - nope:  # a padded row: zeros meet its padding
        held.append(jnp.zeros((b, t, h, width - rank - q.shape[-1] + nope), q.dtype))
    return jnp.concatenate(held, axis=-1), w_kvb


def _out_of_latent_space(out_lat: jax.Array, w_kvb: jax.Array, wo: jax.Array, nope: int) -> jax.Array:
    """The other end: the weighted sum of latents ``out_lat`` ``[B, T, H,
    rank]`` through ``W_kvb``'s value half (``w_kvb`` as the first end hands it
    back) and ``W_o``."""
    b, t, h, _ = out_lat.shape
    out = wdot("bthr,rhd->bthd", out_lat, w_kvb[..., nope:])
    return mm(out.reshape(b, t, -1), wo)


def attend_absorbed(q: jax.Array, w_kvb: jax.Array, wo: jax.Array, latent: jax.Array,
                    mask: jax.Array, rank: int, nope: int, v_dim: int, scale: float) -> jax.Array:
    """Absorbed latent attention: the queries ``q`` ``[B, T, H, nope + rope]``
    (the rotated part already rotated) against the cached ``latent`` ``[B, P,
    W]`` under ``mask`` ``[B, T, P]``, through ``W_kvb`` ``[rank, H * (nope +
    v_dim)]`` and ``W_o``; scores times ``scale``."""
    q_all, w_kvb = _into_latent_space(q, w_kvb, rank, nope, v_dim, latent.shape[-1])  # [B, T, H, W]
    scores = wdot("bthc,bpc->bhtp", q_all, latent) * scale
    scores = jnp.where(mask[:, None], scores, -jnp.inf)
    top = jnp.maximum(scores.max(axis=-1, keepdims=True), -1e30)
    p = jnp.exp(scores - top)
    p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    out_lat = wdot("bhtp,bpr->bthr", p, latent[..., :rank])
    return _out_of_latent_space(out_lat, w_kvb, wo, nope)


def _scored(q_all: jax.Array, latent: jax.Array, mask: jax.Array, rank: int, scale: float):
    """One flash partial: the queries as they meet a cached row, ``q_all``
    ``[B, T, H, W]``, against ``latent`` ``[B, P, W]`` under ``mask`` ``[B, T,
    P]``: (the weighted sum IN THE LATENT SPACE ``[B, T, H, rank]``, the row
    maximum ``[B, H, T]``, the denominator), what ``models/llama.py:
    _merge_partials`` folds."""
    scores = wdot("bthc,bpc->bhtp", q_all, latent) * scale
    scores = jnp.where(mask[:, None], scores, -jnp.inf)
    top = jnp.maximum(scores.max(axis=-1), -1e30)
    p = jnp.exp(scores - top[..., None])
    return wdot("bhtp,bpr->bthr", p, latent[..., :rank]), top, p.sum(axis=-1)


def _empty_partial(b: int, t: int, h: int, rank: int):
    """The partial over no key: what a merge leaves as it finds it."""
    return (jnp.zeros((b, t, h, rank), jnp.float32), jnp.full((b, h, t), -1e30, jnp.float32),
            jnp.zeros((b, h, t), jnp.float32))


def _normalised(partial) -> jax.Array:
    """A merged partial's weighted sum ``[B, T, H, rank]``; zeros where it met no key."""
    num, _, den = partial
    return num / jnp.maximum(den, 1e-30).transpose(0, 2, 1)[..., None]


def attend_absorbed_tiled(q: jax.Array, w_kvb: jax.Array, wo: jax.Array, pool: jax.Array, layer: int,
                          block_tables: jax.Array, positions: jax.Array, n_tiles, tile_blocks: int,
                          rank: int, nope: int, v_dim: int, scale: float) -> jax.Array:
    """:func:`attend_absorbed` for a chunk whose tokens' latents are in the
    pool already: the queries ``q`` ``[B, T, H, nope + rope]`` at ``positions``
    ``[B, T]`` (< 0: padding) against MLA layer ``layer`` of ``pool`` ``[L, N,
    bs, W]`` through the rows' ``block_tables`` ``[B, MB]``, a query seeing the
    keys up to its own position. The tables are read ``tile_blocks`` pages a
    trip, ``n_tiles`` trips (the caller's: the tiles up to the rows' last
    position; a traced scalar): trip ``i`` gathers the rows' pages of tile
    ``i``, scores them as the full form does, and folds (the weighted sum IN
    THE LATENT SPACE ``[B, T, H, rank]``, the row max, the denominator) into a
    running partial by the flash merge (``models/llama.py:
    chunk_history_partial`` is the form). A tile past the trips is never read,
    whatever it holds; no trip leaves the empty partial, and zeros. Both ends
    are :func:`attend_absorbed`'s, done once, outside the loop; every product
    keeps its precision, and only the order of the float32 sums differs."""
    b, t, h, _ = q.shape
    q_all, w_kvb = _into_latent_space(q, w_kvb, rank, nope, v_dim, pool.shape[-1])  # [B, T, H, W]
    tile = tile_blocks * pool.shape[2]
    # whole tiles: the columns added point at page 0 and lie past every position
    tables = jnp.pad(block_tables, ((0, 0), (0, -block_tables.shape[1] % tile_blocks)))

    def trip(i, acc):
        cols = jax.lax.dynamic_slice_in_dim(tables, i * tile_blocks, tile_blocks, axis=1)
        latent = gather_latent(pool, layer, cols)  # [B, tile, W]
        # key p of tile i is position i * tile + p: a query sees keys up to its own (padding, < 0, none)
        key_pos = i * tile + jnp.arange(tile)
        mask = key_pos[None, None, :] <= positions[:, :, None]
        return _merge_partials(acc, _scored(q_all, latent, mask, rank, scale))

    out_lat = _normalised(jax.lax.fori_loop(0, n_tiles, trip, _empty_partial(b, t, h, rank)))
    return _out_of_latent_space(out_lat, w_kvb, wo, nope)


# -- a decode dispatch's live history -------------------------------------------
#
# A lane's block table is as wide as ``max_model_len`` and holds a fraction of
# that, so a decode step cuts every table into tiles of
# ``models/llama.py:history_tile`` positions and attends the tiles that hold
# history. The lanes are taken LONGEST FIRST and in blocks of
# :func:`lanes_at_once`: a block walks as many tiles as its longest lane holds
# (its first), which after the sort is what nearly every lane of it holds, and
# a block of lanes that do not decode walks none. Lanes stay the major axis of
# every product: a lane's queries (128 heads x 640 float32, 328 KB) are half of
# one of its tiles (256 x 640, 655 KB), so sending them out to (lane, tile)
# slots, as ``models/llama.py:_live_window_attention`` does for four KV heads,
# costs half of what it saves. The program is ONE text whatever the lanes hold:
# the blocks are a ``lax.map`` and a block's tiles a ``fori_loop`` whose trip
# count is traced, so no width is compiled twice and the width follows the
# traffic; no conditional stands inside the step loop, where the chip's
# compiler answers one with a copy of what it reads (PERF.md 6, PR 69: a
# ``lax.switch`` over static widths around the attention's core alone read
# 3.29 ms a layer where the core at that width reads 1.24).
# :func:`live_history_tiles` is the count, for the program and the host alike.

# Settled on the v5e at the batch cells' occupancy (64 lanes, 58 decoding at contexts 63 ... 1,134):
# 2, 4, 8 and 16 lanes a block read 1.51, 1.49, 1.66 and 1.73 ms a layer at 128 heads (0.84, 0.79, 0.80,
# 0.89 at 32), and a block's tile laid out in one piece (the tile before the lane in the buffer) 1.37
# where a piece a lane reads 1.49 (0.63 / 0.76): PERF.md 6, PR 69
LANES_AT_ONCE = 4


def lanes_at_once(lanes: int) -> int:
    """Lanes in a block of the live form: the largest divisor of ``lanes`` up
    to ``LANES_AT_ONCE``, so that the blocks are whole."""
    return max(d for d in range(1, LANES_AT_ONCE + 1) if lanes % d == 0)


def _live_blocks(base, block_size: int, table_blocks: int):
    """(``order`` ``[B]``: the lanes longest history first; the history each
    holds in that order ``[B / lb, lb]``; the tiles each block walks ``[B /
    lb]``: its longest lane's). For a traced ``base`` and a numpy one alike."""
    xp = np if isinstance(base, np.ndarray) else jnp
    tile = history_tile(block_size, table_blocks)
    held = base.clip(0, table_blocks * block_size)
    order = xp.argsort(-held, stable=True)
    held = held[order].reshape(-1, lanes_at_once(base.shape[0]))
    return order, held, (held[:, 0] + tile - 1) // tile


def live_history_tiles(base, block_size: int, table_blocks: int):
    """(lane, tile) pairs a decode step of the live form attends for ``base``
    ``[B]`` (a lane's history is the positions < base; a lane that does not
    decode has -1 and none): every block of :func:`lanes_at_once` lanes,
    longest first, the tiles its longest lane holds. Written for a traced
    array (the program's own trips) and a numpy one (the host's count of what
    the program will read) alike."""
    _, held, trips = _live_blocks(base, block_size, table_blocks)
    return trips.sum() * held.shape[1]


class LiveLatents(NamedTuple):
    """A decode dispatch's history as :func:`attend_absorbed_live` reads it."""

    latent: Tuple[jax.Array, ...]  # a layer: [B / lb, tiles, lb, tile, W], the sorted lanes' tables
    order: jax.Array  # [B] the lane in sorted row r
    back: jax.Array  # [B] the sorted row of lane b
    held: jax.Array  # [B / lb, lb] positions of history a sorted row holds
    trips: jax.Array  # [B / lb] tiles a block walks


def live_latents(pool: jax.Array, layers: int, block_tables: jax.Array, base: jax.Array) -> LiveLatents:
    """The first ``layers`` MLA layers of ``pool`` ``[L, N, bs, W]`` through
    ``block_tables`` ``[B, MB]`` for lanes whose history is the positions <
    ``base`` ``[B]`` (-1: the lane does not decode), gathered ONCE a dispatch:
    the lanes longest first, in blocks, every table whole in ONE gather a
    layer; what a step then reads of it is the tiles that hold history. (A
    gather of those tiles alone wants a buffer zeroed first: of five layers'
    6.4 ms the zeros alone are 3.7, a (block, tile) pair a trip behind them
    5.3 in all at the batch cells' fill and more than the whole gather from
    half-full tables on: PERF.md 6, PR 69.)"""
    bs, w = pool.shape[2:]
    mb = block_tables.shape[1]
    tile = history_tile(bs, mb)
    tile_blocks, tiles = tile // bs, history_tiles_full(bs, mb)
    order, held, trips = _live_blocks(base, bs, mb)
    n_blocks, lb = held.shape
    # whole tiles: the columns added point at page 0 and lie past every history
    tables = jnp.pad(block_tables, ((0, 0), (0, tiles * tile_blocks - mb)))[order]
    # a block's lanes side by side under each tile: a trip reads one piece of the buffer
    tables = tables.reshape(n_blocks, lb, tiles, tile_blocks).transpose(0, 2, 1, 3).reshape(-1, tile_blocks)
    latent = tuple(gather_latent(pool, layer, tables).reshape(n_blocks, tiles, lb, tile, w)
                   for layer in range(layers))
    return LiveLatents(latent, order, jnp.argsort(order), held, trips)


def live_positions_attended(live: LiveLatents, steps: int) -> jax.Array:
    """``[B]``: the positions a lane's row is scored against in every layer and
    step of the dispatch: its block's tiles and the steps' buffer."""
    tile = live.latent[0].shape[3]
    return (jnp.repeat(live.trips, live.held.shape[1]) * tile + steps)[live.back]


def recent_latents(pool: jax.Array, layers: int, lanes: int, steps: int) -> Tuple[jax.Array, ...]:
    """The buffers a dispatch's steps write their latents to, a layer: ``[B,
    steps, W]`` as the pool holds a row."""
    return (jnp.zeros((lanes, steps, pool.shape[-1]), pool.dtype),) * layers


def attend_absorbed_live(q: jax.Array, w_kvb: jax.Array, wo: jax.Array, live: LiveLatents, layer: int,
                         recent: jax.Array, fresh: jax.Array, step, fed: jax.Array, rank: int, nope: int,
                         v_dim: int, scale: float):
    """:func:`attend_absorbed` for decode step ``step`` of a dispatch: the
    lanes' queries ``q`` ``[B, 1, H, nope + rope]`` against MLA layer ``layer``
    of the dispatch's history ``live`` (:func:`live_latents`: a lane sees all
    of its history there) and against the latents of the dispatch's own steps,
    ``recent`` ``[B, steps, W]`` with this step's ``fresh`` ``[B, 1, W]``
    written in: a lane that decodes (``fed`` ``[B]``) sees the steps up to this
    one. Returns (the attention's output ``[B, 1, E]``, ``recent`` with the
    step's row). A block of lanes walks the tiles it holds, each trip
    :func:`attend_absorbed_tiled`'s; the steps' partial is folded in by the
    same merge; both ends are done once, outside the loops. Every product
    keeps its precision, and only the order of the float32 sums differs."""
    b, t, h, _ = q.shape
    history = live.latent[layer]
    n_blocks, lb = live.held.shape
    tile, width = history.shape[3:]
    q_all, w_kvb = _into_latent_space(q[live.order], w_kvb, rank, nope, v_dim, width)  # [B, 1, H, W]
    key_pos = jnp.arange(tile)

    def block(xs):
        blk, q_blk, held, trips = xs  # (), [lb, 1, H, W], [lb], ()

        def trip(i, acc):
            latent = jax.lax.dynamic_slice(history, (blk, i, 0, 0, 0), (1, 1, lb, tile, width))
            mask = i * tile + key_pos[None, None, :] < held[:, None, None]
            return _merge_partials(acc, _scored(q_blk, latent.reshape(lb, tile, width), mask, rank, scale))

        return jax.lax.fori_loop(0, trips, trip, _empty_partial(lb, t, h, rank))

    past = jax.lax.map(block, (jnp.arange(n_blocks), q_all.reshape(n_blocks, lb, t, h, width),
                               live.held, live.trips))
    past = tuple(x.reshape(b, *x.shape[2:]) for x in past)
    recent = jax.lax.dynamic_update_slice_in_dim(recent, fresh.astype(recent.dtype), step, axis=1)
    seen = (jnp.arange(recent.shape[1])[None, :] <= step) & fed[:, None]  # [B, steps]
    own = _scored(q_all, recent[live.order], seen[live.order][:, None, :], rank, scale)
    out = _out_of_latent_space(_normalised(_merge_partials(past, own)), w_kvb, wo, nope)
    return out[live.back], recent


# -- the pool's rows -------------------------------------------------------------

def page_rows(positions, block_tables, num_blocks: int, block_size: int, layer: int, layers: int):
    """Row of each position in the ``[L * N * bs, ...]`` view of the pool for
    MLA layer ``layer``; padding gets the row past the pool (dropped)."""
    from dynamo_tpu.ops.attention import _page_rows as rows_of

    rows = rows_of(positions, block_tables, num_blocks, block_size)
    per_layer = num_blocks * block_size
    return jnp.where(rows < per_layer, layer * per_layer + rows, layers * per_layer)


def write_latent(pool: jax.Array, layer: int, new: jax.Array, positions, block_tables):
    """Scatter ``new`` ``[B, T, D]`` into MLA layer ``layer`` of the pool under
    ONE flat row index (ops/attention.py ``write_kv_to_pool`` says why)."""
    l, n, bs, d = pool.shape
    rows = page_rows(positions, block_tables, n, bs, layer, l).reshape(-1)
    flat = pool.reshape(l * n * bs, d).at[rows].set(
        new.reshape(-1, d).astype(pool.dtype), mode="drop")
    return flat.reshape(pool.shape)


def gather_latent(pool: jax.Array, layer: int, block_tables) -> jax.Array:
    """A lane's pages of MLA layer ``layer`` as ``[B, MB * bs, D]``."""
    l, n, bs, d = pool.shape
    pages = pool.reshape(l * n, bs, d)[layer * n + block_tables]
    return pages.reshape(block_tables.shape[0], -1, d)
