"""Trinity decoder (``model_type: afmoe``): window attention layers beside full
attention layers, three to one, both with a norm on every head of q and k and
a sigmoid gate on the attended values; four RMS norms a layer; a dense gated
feed-forward in the first layers and sigmoid-routed experts beside one shared
expert in the rest; embeddings scaled by ``sqrt(hidden)``; an untied head.

Two kinds of attention live in one model, with two caches and two lifetimes:

- a FULL layer (``full_attention``) uses NO positional encoding and sees every
  position under the query. Its keys and values are the pool's pages, the
  Llama layout ``{"k", "v"}`` ``[L_full, N, bs, KVH, D]``: allocated by the
  block, handed out and taken back by the engine's allocator exactly as any
  model's, written, gathered and attended by ``models/llama.py``'s own
  functions (the chunk's history a tile at a time, a decode dispatch's through
  ``with_live_history``), as ``models/lfm2.py`` sends its pages.
- a WINDOW layer (``sliding_attention``) rotates q and k and sees the
  ``sliding_window`` positions that end with the query's own. Its keys and
  values are a RING a slot (:class:`SlotState`, owned here; ``ops/ring.py``):
  ``[S, KVH, P, D]`` a layer with ``P = sliding_window + RING_BLOCK``
  positions, position ``p`` of a slot's lane at entry ``p % P``. A lane holds
  ``P`` positions there however long it grows, where one lifetime for every
  layer would hold the lane whole: at 8 slots x 8,192 positions 1.08 GB of
  rings in four window layers against 3.22 GB of pages. No table, no
  allocator, nothing freed: the bound holds by construction. A step program
  reads the rings and writes them once, after its layers; nothing resets one
  (a slot's new request starts at position 0, and the mask lets nothing older
  through), which is also why preemption by recompute works as it is. Nothing
  outside this module indexes the rings, and ``pages.take`` / ``put`` never
  see them: what hands pages over without them is refused or declined by name
  (``docs/kv_cache_manager.md``, "State per slot").

A layer on ``x``: ``a = N_in(x)``; ``x += N_post_attn(Attn(a))``; ``m =
N_pre_mlp(x)``; ``x += N_post_mlp(FF(m))``, as ``models/openpangu.py`` places
its four norms, and ``FF`` IS that module's ``feed_forward`` (a dense gated
feed-forward, or the experts held here of the ``num_experts_published`` the
router scores beside one shared expert: ``ops/moe.py``). The layers differ in
kind twice over, so they are a tuple of per-layer trees walked in Python (an
expert layer's ``[X, E, F]`` matrices go to the grouped product as they lie:
``models/lfm2.py`` says what a ``lax.scan`` over a stack of them costs).

The arithmetic is ``models/lfm2.py``'s, for its reason (a router that keeps 4
of 256 is the same discontinuity): bfloat16 weights, float32 activations from
the embedding to the head, every product that a later router sees in THREE
bfloat16 parts (``ops/latent.py:mm``), float32 pages and rings with attention's
own products at float32's precision, the head in one part. The configuration's
file keeps the readings on the chip.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.models.llama import (  # noqa: F401  (the two tile counts are this module's too)
    _chunk_self_partial, _live_window_attention, _merge_partials, _pool_pages, apply_rope,
    chunk_history_partial, chunk_history_tiles, chunk_layout, chunk_rows_above_partial,
    decode_history_tiles, embed_lookup, flush_window, history_tile, history_tiles_full, rms_norm,
    with_live_history,
)
from dynamo_tpu.models.openpangu import MOE_COUNTERS, feed_forward
from dynamo_tpu.ops import ring
from dynamo_tpu.ops.latent import mm
from dynamo_tpu.ops.parts import dot_parts

Params = Dict[str, Any]
KVCache = Dict[str, jax.Array]  # {"k", "v"}: [L_full, N, bs, KVH, D]
SlotState = Dict[str, Tuple[jax.Array, ...]]  # {"k", "v"}: per window layer [S, KVH, P, D]

WINDOW, FULL = "sliding_attention", "full_attention"

# sums the step programs return, in this order (engine: /debug/engine): the six
# of ops/moe.py:dropless_experts (a call is one expert layer over a decode
# step's lanes or over a group of a chunk's rows); window layers run (a group
# of a chunk's rows or a decode step each count their layers); the ring entries
# those layers' query rows scored (a decode lane its whole ring a layer and
# step, a chunk's row the tiles its group's loop walks and the block past the
# window); of those, the ones that hold history inside the window of the row's
# first query; what the same rows' WHOLE histories hold (what a full layer in
# the window layer's place would have read); full layers run
COUNTERS = ("moe_layer_calls", "moe_held_rows", "moe_experts_hit", "moe_routed_pairs",
            "moe_rows_computed", "moe_expert_reads", "swa_layer_calls",
            "swa_history_positions_read", "swa_history_positions_live",
            "swa_history_positions_whole", "full_layer_calls")
assert COUNTERS.index("swa_layer_calls") == MOE_COUNTERS  # what `feed_forward` counts comes first
# A lane may fill several rows of one chunk dispatch with successive pieces of
# its prompt (engine_jax/engine.py:chunk_rows_of). Of the four things a module
# with state per slot owes for it (models.module_for) an attention-only model
# owes the third alone: a row's history, in the pool and in the ring, ends where
# its lane's FIRST row of the dispatch starts, and it attends the rows between
# as fresh keys (in a window layer under the window's mask); nothing is handed
# from row to row, and the rings are written once, after the layers, by position
LANE_TAKES_ROWS = True
# ... and AT the full width as under it: `forward_chunk` reads `lanes` and builds
# its layout from them whatever the rows number, so the rows of a full-width
# dispatch that no lane's first piece fills (computed either way: 8 rows are one
# group) go to further pieces of the lanes' prompts. Four modules keep one row a
# lane there, each for its program's text: `llama`'s full-width program takes no
# lanes, `jamba`, `lfm2` and `qwen3_next` read theirs under `rows < slots` alone
FULL_WIDTH_TAKES_ROWS = True
# rows of a chunk computed at once: the rows are independent, and a chunk of
# more is taken in groups (8 rows of 128 positions route 4,096 pairs; more at
# once only adds temporaries)
ROWS_AT_ONCE = 8
# the pages and the rings are float32 under any weights, and attention's own
# products over them are taken at float32's precision (models/llama.py's
# einsums and ops/ring.py's name none: they take the one in force where traced)
ATTENTION_PRECISION = "highest"
# a ring is the window's positions and this many more: one block of the pool's pages
RING_BLOCK = 16


@dataclass(frozen=True)
class TrinityConfig:
    vocab_size: int = 200192
    hidden_size: int = 3072
    intermediate_size: int = 12288  # the dense feed-forward of the first layers
    num_layers: int = 60
    num_heads: int = 48
    num_kv_heads: int = 8
    head_dim: int = 128
    # "sliding_attention" or "full_attention" for each layer, as published
    layer_types: Tuple[str, ...] = ()
    sliding_window: int = 4096
    rope_theta: float = 10000.0  # the window layers'; a full layer rotates nothing
    num_dense_layers: int = 6
    moe_intermediate_size: int = 3072
    num_experts: int = 256  # held here, ids first_expert ...
    num_experts_published: int = 256  # the router's width
    first_expert: int = 0
    num_experts_per_tok: int = 4
    moe_renormalize: bool = True  # route_norm
    routed_scaling_factor: float = 2.448  # route_scale
    mup_enabled: bool = True
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if len(self.layer_types) != self.num_layers or set(self.layer_types) - {WINDOW, FULL}:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers of kinds "
                f"{sorted(set(self.layer_types))}: {self.num_layers} of {WINDOW!r} / {FULL!r} wanted")
        if self.sliding_window % RING_BLOCK or self.sliding_window <= 0:
            raise ValueError(
                f"sliding_window {self.sliding_window} is no whole number of blocks of {RING_BLOCK}")

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def ring_positions(self) -> int:
        """Positions a slot holds in a window layer: the window's and one block more."""
        return self.sliding_window + RING_BLOCK


def lane_rows_most(config: TrinityConfig, width: int) -> int:
    """The rows of ``width`` positions that one lane may fill of ONE chunk
    dispatch, at any rung: what a window layer's ring holds, since the rings
    are written once a dispatch and each position to its own entry. The
    engine deals a lane no more (``engine_jax/engine.py:chunk_rows_of``), and
    ``forward_chunk`` holds the number against the ring where it is traced
    (one row at the least: a row wider than a ring is refused there)."""
    return max(1, config.ring_positions // width)


# -- parameters ---------------------------------------------------------------

def init_params(rng: jax.Array, config: TrinityConfig) -> Params:
    """Random init with fan-in scaling; the routers and every norm float32; the
    selection bias (published as a trained buffer) small seeded values, so that
    it moves some choices (as ``models/lfm2.py``'s)."""
    c = config
    e = c.hidden_size

    def dense(key, shape, fan_in, dtype=None):
        w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
        return w.astype(dtype or c.dtype)

    def layer(key, experts: bool) -> Params:
        k = jax.random.split(key, 13)
        lp = {
            "in_norm": jnp.ones((e,), jnp.float32), "post_attn_norm": jnp.ones((e,), jnp.float32),
            "pre_mlp_norm": jnp.ones((e,), jnp.float32), "post_mlp_norm": jnp.ones((e,), jnp.float32),
            "wq": dense(k[0], (e, c.q_dim), e), "wk": dense(k[1], (e, c.kv_dim), e),
            "wv": dense(k[2], (e, c.kv_dim), e), "wg": dense(k[3], (e, c.q_dim), e),
            "wo": dense(k[4], (c.q_dim, e), c.q_dim),
            "q_norm": jnp.ones((c.head_dim,), jnp.float32),
            "k_norm": jnp.ones((c.head_dim,), jnp.float32),
        }
        if not experts:
            f = c.intermediate_size
            lp.update(w_gate=dense(k[5], (e, f), e), w_up=dense(k[6], (e, f), e),
                      w_down=dense(k[7], (f, e), f))
            return lp
        x, f = c.num_experts, c.moe_intermediate_size
        lp.update(
            router=dense(k[5], (e, c.num_experts_published), e, jnp.float32),
            e_bias=0.02 * jax.random.normal(k[6], (c.num_experts_published,), jnp.float32),
            w_gate=dense(k[7], (x, e, f), e), w_up=dense(k[8], (x, e, f), e),
            w_down=dense(k[9], (x, f, e), f),
            ws_gate=dense(k[10], (e, f), e), ws_up=dense(k[11], (e, f), e),
            ws_down=dense(k[12], (f, e), f),
        )
        return lp

    return {
        "embed": dense(jax.random.fold_in(rng, 1000), (c.vocab_size, e), e),
        "final_norm": jnp.ones((e,), jnp.float32),
        "layers": tuple(layer(jax.random.fold_in(rng, i), i >= c.num_dense_layers)
                        for i in range(c.num_layers)),
        "lm_head": dense(jax.random.fold_in(rng, 1001), (e, c.vocab_size), e),
    }


def param_shardings(config: TrinityConfig, mesh):
    raise NotImplementedError(
        "trinity runs on one device: experts over the chips of a host are "
        "ROADMAP M1's remainder"
    )


# -- the two caches -----------------------------------------------------------

def make_kv_cache(
    config: TrinityConfig, num_blocks: int, block_size: int, dtype: Any = None,
    quantized: bool = False,
) -> KVCache:
    """The FULL layers' page pool, in the Llama layout."""
    if quantized:
        raise ValueError("trinity has no int8 page layout")
    c = config
    shape = (c.layer_types.count(FULL), num_blocks, block_size, c.num_kv_heads, c.head_dim)
    return {"k": jnp.zeros(shape, dtype or jnp.float32), "v": jnp.zeros(shape, dtype or jnp.float32)}


def make_slot_state(config: TrinityConfig, slots: int) -> SlotState:
    """The WINDOW layers' rings of every slot (``ops/ring.py``): an array a
    layer, a layer's array replaced whole by the program that writes it.
    ``sliding_window + RING_BLOCK`` positions a slot, whatever the server's
    ``--max-model-len``."""
    c = config
    shape = (slots, c.num_kv_heads, c.ring_positions, c.head_dim)
    return {name: tuple(jnp.zeros(shape, jnp.float32) for _ in range(c.layer_types.count(WINDOW)))
            for name in ("k", "v")}


# -- the layers ---------------------------------------------------------------

def final_norm(params: Params, config: TrinityConfig, x: jax.Array) -> jax.Array:
    return rms_norm(x, params["final_norm"], config.rms_norm_eps)


def lm_head(params: Params, config: TrinityConfig, h: jax.Array) -> jax.Array:
    """Final hidden states to float32 logits (the head is untied; no router
    follows it: one part)."""
    return dot_parts(h, params["lm_head"])


def embed(params: Params, c: TrinityConfig, tokens: jax.Array) -> jax.Array:
    """Float32 embeddings, times ``sqrt(hidden)`` where ``mup_enabled``."""
    x = embed_lookup(params, tokens, c.dtype).astype(jnp.float32)
    return x * math.sqrt(c.hidden_size) if c.mup_enabled else x


def _project(lp: Params, c: TrinityConfig, kind: str, a: jax.Array, positions: jax.Array):
    """q, k, v and the gate of normed inputs ``a`` ``[B, T, E]``: q and k split
    into heads and normed over each head's ``D``, then rotated in a WINDOW
    layer and left as they are in a FULL one; float32, as the pages and the
    rings are. No bias."""
    b, t, _ = a.shape
    q = rms_norm(mm(a, lp["wq"]).reshape(b, t, c.num_heads, c.head_dim), lp["q_norm"], c.rms_norm_eps)
    k = rms_norm(mm(a, lp["wk"]).reshape(b, t, c.num_kv_heads, c.head_dim), lp["k_norm"], c.rms_norm_eps)
    v = mm(a, lp["wv"]).reshape(b, t, c.num_kv_heads, c.head_dim)
    if kind == WINDOW:
        q, k = apply_rope(q, positions, c.rope_theta), apply_rope(k, positions, c.rope_theta)
    return q, k, v, jax.nn.sigmoid(mm(a, lp["wg"]))


def _layer(lp: Params, c: TrinityConfig, kind: str, x: jax.Array, positions: jax.Array, attend):
    """One decoder layer over ``x`` ``[B, T, E]`` at ``positions`` ``[B, T]``
    (< 0: padding). ``attend(q, k, v) -> [B, T, H, D]`` attends what the
    queries see and keeps the fresh keys and values. Returns (x, the expert
    counters)."""
    eps = c.rms_norm_eps
    b, t, _ = x.shape
    a = rms_norm(x, lp["in_norm"], eps)
    with jax.named_scope("swa" if kind == WINDOW else "full_attn"):
        q, k, v, gate = _project(lp, c, kind, a, positions)
        with jax.default_matmul_precision(ATTENTION_PRECISION):
            out = attend(q, k, v)
        y = mm(out.astype(jnp.float32).reshape(b, t, c.q_dim) * gate, lp["wo"])
    x = x + rms_norm(y, lp["post_attn_norm"], eps)
    y, stats = feed_forward(lp, c, rms_norm(x, lp["pre_mlp_norm"], eps), positions >= 0)
    return x + rms_norm(y, lp["post_mlp_norm"], eps), stats


def _swa_counts(c: TrinityConfig, fed: jax.Array, read, first_query: jax.Array,
                held: jax.Array) -> jax.Array:
    """``swa_layer_calls`` and the three ``swa_history_positions_*`` of the
    window layers over rows of which ``fed`` ``[B]`` hold a token: each such
    row scored ``read`` ring entries a layer; its lane holds ``held`` ``[B]``
    positions under the dispatch's first query of the lane, of which the
    row's query at ``first_query`` ``[B]`` sees those inside its window."""
    layers = c.layer_types.count(WINDOW)
    live = jnp.clip(held - jnp.clip(first_query - (c.sliding_window - 1), 0), 0)
    return jnp.stack([jnp.int32(layers), layers * fed.sum() * read,
                      layers * jnp.sum(jnp.where(fed, live, 0)),
                      layers * jnp.sum(jnp.where(fed, held, 0))]).astype(jnp.int32)


def _kinds(c: TrinityConfig):
    """(layer, kind, the layer's index among its kind) for every layer."""
    seen = {WINDOW: 0, FULL: 0}
    for i, kind in enumerate(c.layer_types):
        yield i, kind, seen[kind]
        seen[kind] += 1


# -- the step programs --------------------------------------------------------

class _Left(NamedTuple):
    """What the groups of a dispatch so far leave the next one."""

    at: jax.Array  # the dispatch's row that is the next group's first
    k: jax.Array  # [L, N, C, KVH, D] the dispatch's fresh keys so far, zeros from `at` on
    v: jax.Array


def forward_chunk(
    params: Params, config: TrinityConfig, tokens: jax.Array, positions: jax.Array,
    kv_cache: KVCache, block_tables: jax.Array, state: SlotState, lanes: jax.Array,
):
    """A ``[R, C]`` block of prompt tokens (``lanes`` ``[R]``: the row's slot;
    ``max_slots`` and above = a padding row), valid tokens (position >= 0) a
    prefix of each row. A lane may fill several CONSECUTIVE rows with
    successive pieces of its prompt, in order, each full but the last, at any
    number of rows (``FULL_WIDTH_TAKES_ROWS``).

    Returns (hidden ``[R, C, E]`` after the final norm, the pool with the full
    layers' K and V written, the slot state with the window layers' K and V
    written into the rows' lanes' rings, the counters ``[len(COUNTERS)]``).
    More than ``ROWS_AT_ONCE`` rows are taken in groups of that many, one after
    another and only as far as the last row that holds a token; the pool and
    the rings are only read inside the loop, and what the rows made is written
    after it: one scatter a pool array and one a ring.

    A row attends, in any layer, (a) the cached history of its lane, which
    ends where the lane's FIRST row of the dispatch starts (``llama.ChunkLayout``):
    a full layer the pool's pages a tile a trip (``chunk_history_partial``), a
    window layer its lane's ring a tile a trip under the window's mask
    (``ops/ring.py:chunk_ring_partial``); (b) the fresh keys of its lane's rows
    above it (``chunk_rows_above_partial``) and (c) its own
    (``_chunk_self_partial``), both causal and in a window layer inside the
    window, folded by the flash merge. A lane's rows of one dispatch hold a
    ring's positions at most (each is written to its own entry): the engine
    deals a lane ``lane_rows_most`` rows at most, and that number is held
    against the ring here, where the program is traced."""
    from dynamo_tpu.ops.attention import write_kv_to_pool

    c = config
    rows, width = tokens.shape
    slots = state["k"][0].shape[0]
    a_lane = min(rows, lane_rows_most(c, width))  # the rows one lane may fill of this dispatch
    if a_lane * width > c.ring_positions:
        raise ValueError(
            f"a lane's {a_lane} rows of {width} positions pass the {c.ring_positions} positions a "
            f"window layer keeps of it: two of them would be written to one entry of its ring")
    layout = chunk_layout(positions, lanes, slots)
    # the dispatch's fresh keys and values, every layer's: a group's rows go in where they stand
    none_yet = jnp.zeros((c.num_layers, *tokens.shape, c.num_kv_heads, c.head_dim), jnp.float32)
    group = partial(_chunk_rows, params, c, _pool_pages(kv_cache), kv_cache["k"].shape[1], state,
                    layout)
    if rows <= ROWS_AT_ONCE:
        h, k, v, counters = group(_Left(jnp.int32(0), none_yet, none_yet), tokens, positions,
                                  block_tables, lanes)
    else:
        if rows % ROWS_AT_ONCE:
            raise ValueError(f"{rows} rows are no whole number of groups of {ROWS_AT_ONCE}")
        # the groups as far as the last row that holds a token (the engine packs its rows to the
        # front): a group of padding rows alone is not computed, and its hidden states stay zeros
        fed = (lanes < slots) & (positions[:, 0] >= 0)
        last = jnp.max(jnp.where(fed, jnp.arange(rows) + 1, 0))

        def step(g, carry):
            sums, k, v, h = carry
            at = g * ROWS_AT_ONCE
            hg, k, v, more = group(_Left(at, k, v), *(
                jax.lax.dynamic_slice_in_dim(a, at, ROWS_AT_ONCE)
                for a in (tokens, positions, block_tables, lanes)))
            return sums + more, k, v, jax.lax.dynamic_update_slice_in_dim(h, hg, at, 0)

        counters, k, v, h = jax.lax.fori_loop(
            0, (last + ROWS_AT_ONCE - 1) // ROWS_AT_ONCE, step,
            (jnp.zeros((len(COUNTERS),), jnp.int32), none_yet, none_yet,
             jnp.zeros((*tokens.shape, c.hidden_size), jnp.float32)))
    full = jnp.asarray([i for i, kind, _ in _kinds(c) if kind == FULL], jnp.int32)
    cache = {"k": write_kv_to_pool(kv_cache["k"], k[full], positions, block_tables),
             "v": write_kv_to_pool(kv_cache["v"], v[full], positions, block_tables)}
    new_state = {
        name: tuple(ring.ring_write(state[name][j], new[i].transpose(0, 2, 1, 3), positions, lanes)
                    for i, kind, j in _kinds(c) if kind == WINDOW)
        for name, new in (("k", k), ("v", v))}
    return h, cache, new_state, counters


def _chunk_rows(params, c, pages, num_blocks, state, layout, left, tokens, positions,
                block_tables, lanes):
    """The layers over the rows given, all at once, the pool (its
    ``_pool_pages`` views) and the rings read and not written: (hidden after
    the final norm, every layer's fresh K and V, the counters). The rows are
    ``left.at`` onwards of the dispatch, and the K and V returned are the
    DISPATCH's so far, ``[L, N, C, KVH, D]``: a lane may fill several rows,
    which may straddle two groups."""
    n = tokens.shape[0]
    slots = state["k"][0].shape[0]
    lane = jnp.clip(lanes, 0, slots - 1)
    fed = (lanes < slots) & (positions[:, 0] >= 0)

    scale = c.head_dim ** -0.5
    block_size = pages["k"].shape[1]
    table_blocks = block_tables.shape[1]
    tile_blocks = history_tile(block_size, table_blocks) // block_size
    # where each row's cached history ends: at its lane's first row of the dispatch (the rows
    # between: their keys in hand)
    ends, _ = layout.rows(left.at, n)
    starts = ends[:, 0]
    history_len = jnp.clip(starts, 0, table_blocks * block_size)
    n_tiles = chunk_history_tiles(ends, block_size, table_blocks)
    tables = jnp.pad(block_tables, (
        (0, 0), (0, history_tiles_full(block_size, table_blocks) * tile_blocks - table_blocks)))
    n_trips = ring.ring_trips(jnp.where(fed, starts, 0), c.sliding_window)

    x = embed(params, c, tokens)
    fresh_k, fresh_v = [], []
    stats = jnp.zeros((MOE_COUNTERS,), jnp.int32)
    for i, kind, j in _kinds(c):
        window = c.sliding_window if kind == WINDOW else None

        def attend(q, k, v, i=i, kind=kind, j=j, window=window):
            if kind == FULL:
                hist = chunk_history_partial(
                    c, q, pages, j * num_blocks + tables, history_len, n_tiles, positions,
                    scale, tile_blocks, block_size, jnp.float32)
            else:
                hist = ring.chunk_ring_partial(
                    q, state["k"][j], state["v"][j], lane, starts, n_trips, positions,
                    window, scale)
            part = _merge_partials(hist, _chunk_self_partial(c, q, k, v, positions, scale, window))
            k, v = (jax.lax.dynamic_update_slice_in_dim(all_rows[i], mine, left.at, 0)
                    for all_rows, mine in ((left.k, k), (left.v, v)))
            fresh_k.append(k)
            fresh_v.append(v)
            return ring.attended(chunk_rows_above_partial(
                c, q, k, v, layout.positions, layout.lanes, left.at, layout.n_back, scale, part,
                window))

        x, more = _layer(params["layers"][i], c, kind, x, positions, attend)
        stats = stats + more
    held = jnp.clip(starts, 0)
    own = _swa_counts(
        c, fed, n_trips * ring.ring_tile(c.sliding_window) + RING_BLOCK, positions[:, 0], held)
    counters = jnp.concatenate([stats, own, jnp.full((1,), c.layer_types.count(FULL), jnp.int32)])
    return final_norm(params, c, x), jnp.stack(fresh_k), jnp.stack(fresh_v), counters


def decode(
    params: Params, config: TrinityConfig, tokens: jax.Array, positions: jax.Array,
    kv_cache: KVCache, block_tables: jax.Array, state: SlotState, steps: int, max_pos: int,
    sample, carry,
):
    """``steps`` tokens of every slot (``tokens``, ``positions`` ``[S]``;
    position < 0 = the slot does not decode; a lane that passes ``max_pos``
    stops there).

    The ``steps`` (a handful) are unrolled. Pool and rings are read-only inside
    the dispatch: a step's K and V go to a buffer a layer, and after the steps
    the pool takes the full layers' buffers in one scatter a pool array
    (``flush_window``) and each ring its layer's (``ops/ring.py:ring_write``).
    A FULL layer is the dense tier of the Llama decode program (the pool's live
    (lane, tile) pairs gathered once: ``with_live_history``); a WINDOW layer
    reads the lanes' rings IN PLACE, every entry of every slot under the mask
    of what it holds (``ops/ring.py:held_positions``) and the window, plus the
    buffer. ``sample(logits [S, V], positions, carry, k) -> (next tokens [S],
    carry, outputs)`` is the engine's. Returns (tokens, positions, carry, the
    stacked outputs, pool, state, counters ``[len(COUNTERS)]``)."""
    c = config
    base = positions
    n_slots = tokens.shape[0]
    n_full, n_win = c.layer_types.count(FULL), c.layer_types.count(WINDOW)
    full_buffer = jnp.zeros((n_slots, steps, c.num_kv_heads, c.head_dim), jnp.float32)
    ring_buffer = jnp.zeros((n_slots, c.num_kv_heads, steps, c.head_dim), jnp.float32)
    scale = c.head_dim ** -0.5
    held = ring.held_positions(base, c.ring_positions)  # [S, P]
    in_cache = jnp.clip(base, 0)

    def run(history):
        live = history[1]

        def step(loop, k):
            toks, pos, carry, fk, fv, wk, wv, counters = loop
            pos2 = pos[:, None]
            in_buffer = (jnp.arange(steps)[None, :] <= k) & (base[:, None] >= 0)  # [S, W]
            fk, fv, wk, wv = list(fk), list(fv), list(wk), list(wv)
            sees_ring = ring.in_window(held, pos2, c.sliding_window)  # [S, 1, P], every window layer's
            sees_buffer = in_buffer[:, None, :] & (pos2 >= 0)[..., None]
            x = embed(params, c, toks)[:, None]  # [S, 1, E]
            for i, kind, j in _kinds(c):

                def attend(q, kk, vv, kind=kind, j=j):
                    if kind == FULL:
                        fk[j] = jax.lax.dynamic_update_slice(fk[j], kk, (0, k, 0, 0))
                        fv[j] = jax.lax.dynamic_update_slice(fv[j], vv, (0, k, 0, 0))
                        return _live_window_attention(
                            c, q, live, live.k[j], live.v[j], fk[j], fv[j], in_buffer, None)
                    wk[j] = jax.lax.dynamic_update_slice(wk[j], kk.transpose(0, 2, 1, 3), (0, 0, k, 0))
                    wv[j] = jax.lax.dynamic_update_slice(wv[j], vv.transpose(0, 2, 1, 3), (0, 0, k, 0))
                    return ring.attended(_merge_partials(
                        ring.masked_partial(q, state["k"][j], state["v"][j], sees_ring, scale),
                        ring.masked_partial(q, wk[j], wv[j], sees_buffer, scale)))

                x, more = _layer(params["layers"][i], c, kind, x, pos2, attend)
                counters = counters.at[:MOE_COUNTERS].add(more)
            nxt, carry, out = sample(lm_head(params, c, final_norm(params, c, x))[:, 0], pos, carry, k)
            own = _swa_counts(c, pos >= 0, c.ring_positions, pos, in_cache)
            counters = counters.at[MOE_COUNTERS:].add(
                jnp.concatenate([own, jnp.full((1,), n_full, jnp.int32)]))
            new_pos = jnp.where((pos >= 0) & (pos < max_pos), pos + 1, -1)
            return (nxt, new_pos, carry, tuple(fk), tuple(fv), tuple(wk), tuple(wv), counters), out

        loop = (tokens, positions, carry, (full_buffer,) * n_full, (full_buffer,) * n_full,
                (ring_buffer,) * n_win, (ring_buffer,) * n_win,
                jnp.zeros((len(COUNTERS),), jnp.int32))
        outs = []
        for k in range(steps):
            loop, out = step(loop, jnp.int32(k))
            outs.append(out)
        return loop, jax.tree.map(lambda *a: jnp.stack(a), *outs)

    (toks, pos, carry, fk, fv, wk, wv, counters), out = with_live_history(
        kv_cache, block_tables, base, run, out_dtype=jnp.float32)
    cache = flush_window(kv_cache, block_tables, base, jnp.stack(fk), jnp.stack(fv), max_pos)
    # the positions the steps wrote, as `flush_window` takes them: a lane that was padding or
    # ran past `max_pos` writes nowhere
    at = base[:, None] + jnp.arange(steps)[None, :]
    at = jnp.where((base[:, None] >= 0) & (at <= max_pos), at, -1)
    lanes = jnp.arange(n_slots)
    new_state = {name: tuple(ring.ring_write(was, new, at, lanes) for was, new in zip(state[name], buf))
                 for name, buf in (("k", wk), ("v", wv))}
    return toks, pos, carry, out, cache, new_state, counters
