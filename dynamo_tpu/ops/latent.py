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
Two callers' forms share those two ends: :func:`attend_absorbed` over a
gathered ``[B, P, W]`` history under a mask (a decode step's dense buffer;
Kimi's chunk, every row's whole table), and :func:`attend_absorbed_tiled` for
a chunk, which reads the rows' block tables out of the pool a tile of
positions a trip and stops at the tile that holds the rows' last position
(``models/openpangu.py``'s chunk programs).

The arithmetic is the two modules' own (:func:`wdot`): float32 activations
against bfloat16 weights in ``PASSES`` bfloat16 parts, float32 against float32
(a score against the float32 latents) at the highest precision.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from dynamo_tpu.models.llama import _merge_partials, apply_rope, rms_norm
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
        scores = wdot("bthc,bpc->bhtp", q_all, latent) * scale
        scores = jnp.where(mask[:, None], scores, -jnp.inf)
        top = jnp.maximum(scores.max(axis=-1), -1e30)
        p = jnp.exp(scores - top[..., None])
        part = wdot("bhtp,bpr->bthr", p, latent[..., :rank])
        return _merge_partials(acc, (part, top, p.sum(axis=-1)))

    empty = (jnp.zeros((b, t, h, rank), jnp.float32), jnp.full((b, h, t), -1e30, jnp.float32),
             jnp.zeros((b, h, t), jnp.float32))
    num, _, den = jax.lax.fori_loop(0, n_tiles, trip, empty)
    out_lat = num / jnp.maximum(den, 1e-30).transpose(0, 2, 1)[..., None]
    return _out_of_latent_space(out_lat, w_kvb, wo, nope)


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
