"""openPangu-Ultra-MoE decoder (``model_type: pangu_ultra_moe``): latent
attention (MLA) in EVERY layer, with a compressed query and a rotated key part,
four RMS norms a layer (the sandwich), dense feed-forwards in the first layers
and sigmoid-routed experts beside one shared expert after them, and ONE
multi-token-prediction module behind the last layer.

The module brings its own step programs (``forward_chunk``, ``decode``,
``COUNTERS``: ``models.module_for``) and keeps NOTHING per slot: its cache is
the pool's one member ``latent`` and nothing else, ``[L, N, bs, W]`` float32
(``ops/latent.py``: the normed latent and the rotated key part of a token, 576
values, in a row of ``W`` = 640 = five registers of 128 lanes; a 576-wide row
is 4.5 registers, and the chip's compiler then pads the pool AND copies it
whole in front of every dispatch's gather: ``tests/test_aot_compile_tpu.py``).
A chunk's rows (``forward_chunk``, ``draft_chunk``, so ``verify`` too) read
their block tables a tile of positions a trip and only as far as the group's
last position (``ops/latent.py:attend_absorbed_tiled``); a decode dispatch
gathers the lanes' tables once, and a step attends the tiles of them that hold
history (``ops/latent.py:attend_absorbed_live``).
So it has no ``make_slot_state``, the engine hands its programs ``state =
None`` and takes None back, and everything that hands pages over (a prefix hit,
``verify``, preemption, the host tier, a transfer) is open to it as to
``models/llama.py`` (docs/kv_cache_manager.md, "State per slot": the two facts
and the table). A lane may fill several rows of one chunk dispatch with
successive pieces of its prompt (``LANE_TAKES_ROWS``, below: the rows meet
through the pool, so the chunk program is the one it was at every rung).

A layer on ``x``: ``a = N_in(x)``; ``x += N_post_attn(MLA(a))``; ``m =
N_pre_mlp(x)``; ``x += N_post_mlp(FF(m))``. The layers differ in kind (dense,
experts), so they are a tuple of per-layer trees walked in Python (an expert
layer's ``[X, E, F]`` matrices go to the grouped product as they lie:
``models/lfm2.py`` says what a ``lax.scan`` over a stack of them costs). The
expert layer holds ``num_experts`` experts from ``first_expert`` on, of the
``num_experts_published`` the router scores (``ops/moe.py``); the arithmetic
is ``ops/latent.py``'s (float32 activations in three bfloat16 parts against
bfloat16 weights), as ``models/kimi_linear.py``'s, for the same reason: a
router picks 8 of 256.

Multi-token prediction (the DeepSeek-V3 form; ``benchmark/configs`` lists what
is assumed): for position ``i`` with the main stack's output ``x_i`` BEFORE the
final norm and the token ``t_{i+1}`` that follows it, ``u_i = W_eh [N_e(Emb(
t_{i+1})) ; N_h(x_i)]``, one expert layer over ``u`` with its OWN latent pages
(the pool's last layer, allocated only where the engine drafts), ``N_mtp`` and
the main model's head: it scores ``t_{i+2}``. :func:`draft_chunk` runs it over
the positions a chunk or verify dispatch computed, ``decode(..., draft=True)``
inside every step; with neither asked for none of it is traced. It keeps no
state per slot either: a position's latent is written by the dispatch that
computed the position, as the main layers' is.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from dynamo_tpu.models.llama import (
    apply_rope, embed_lookup, history_tile, rms_norm,
)
from dynamo_tpu.ops import moe
from dynamo_tpu.ops.latent import (
    PASSES, attend_absorbed_live, attend_absorbed_tiled, cached_latent, live_history_tiles, live_latents,
    live_positions_attended, mm, recent_latents, write_latent,
)
from dynamo_tpu.ops.parts import operand_parts

Params = Dict[str, Any]
KVCache = Dict[str, jax.Array]  # {"latent": [L (+ 1 where the engine drafts), N, bs, W]} float32

# sums the step programs return, in this order (engine: /debug/engine)
COUNTERS = ("moe_layer_calls", "moe_held_rows", "moe_experts_hit", "moe_routed_pairs",
            "moe_rows_computed", "moe_expert_reads",
            # latent attention, summed over the layers' calls: the cached positions a call's
            # rows attended (a decode lane the tiles its block of lanes reads and the dispatch's
            # steps, a chunk's row the tiles its group reads; and every table whole once a decode
            # dispatch, which its gather reads) and, of those, the ones that held history
            "mla_layer_calls", "mla_history_positions_read", "mla_history_positions_live",
            "mtp_layer_calls")
MOE_COUNTERS = COUNTERS.index("mla_layer_calls")  # the first: what ops/moe.py:dropless_experts counts
# positions of a chunk computed at once: the rows are independent, and more are taken in groups,
# which bounds what the program holds beside its arguments (128 heads' scores against a tile of
# 256 keys are 128 KB of float32 a position)
TOKENS_AT_ONCE = 512
LANES = 128  # of a register: the pool's rows are whole registers wide
# A lane may fill several rows of one chunk dispatch with successive pieces of
# its prompt (engine_jax/engine.py:chunk_rows_of), each row with the lane's
# block table. What that rests on: inside a group of rows a layer writes EVERY
# row's latents into the pool before any row attends (`_paged`), a row reads
# its table out of the pool under a causal mask by position, and the groups run
# one after another over the pool the last one left (`_in_groups`); so a later
# row meets its lane's earlier rows' fresh keys where they lie, in its own
# group or an earlier one, and the prediction module's own layer is written and
# read the same way (`draft_chunk`). Of `models.module_for`'s four conditions
# (1) and (2) are empty (nothing is kept by lane: no row starts from a slot's
# state and none writes one back), (3) holds through the pool (a row's history
# and its lane's rows between are ONE read of the table), and (4) holds because
# the program's text is the same at every rung: `forward_chunk` takes ``lanes``
# and reads it nowhere.
LANE_TAKES_ROWS = True

_expert_parts = partial(operand_parts, parts=PASSES)  # ops/moe.py:dropless_experts' ``parts_of``


@dataclass(frozen=True)
class OpenPanguConfig:
    vocab_size: int = 153600
    hidden_size: int = 7680
    intermediate_size: int = 18432  # the dense feed-forward of the first layers
    num_layers: int = 61
    # MLA
    num_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 25600000.0
    # experts
    first_k_dense: int = 3
    moe_intermediate_size: int = 2048
    num_experts: int = 256  # held here, ids first_expert ...
    num_experts_published: int = 256  # the router's width
    first_expert: int = 0
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    moe_renormalize: bool = True
    num_mtp_layers: int = 1  # held (0: a card without the module); the engine drafts or not
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """A row of the pool: ``latent_dim`` rounded up to whole registers."""
        return -(-self.latent_dim // LANES) * LANES

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    # what a module on this one's mixer may state otherwise (models/xing4.py: YaRN)
    @property
    def score_scale(self) -> float:
        return self.qk_head_dim ** -0.5

    @property
    def rope_inv_freq(self):
        """A table of rotary frequencies in ``rope_theta``'s place, or None."""
        return None


def is_expert_layer(c: OpenPanguConfig, layer: int) -> bool:
    return layer >= c.first_k_dense


# -- parameters ---------------------------------------------------------------

def _dense(key, shape, fan_in: int, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)


def _init_layer(key, c: OpenPanguConfig, experts: bool) -> Params:
    e, h = c.hidden_size, c.num_heads

    def dense(key, shape, fan_in, dtype=None):
        return _dense(key, shape, fan_in, dtype or c.dtype)

    k = jax.random.split(key, 12)
    lp = {
        "in_norm": jnp.ones((e,), jnp.float32), "post_attn_norm": jnp.ones((e,), jnp.float32),
        "pre_mlp_norm": jnp.ones((e,), jnp.float32), "post_mlp_norm": jnp.ones((e,), jnp.float32),
        "w_qa": dense(k[0], (e, c.q_lora_rank), e),
        "q_norm": jnp.ones((c.q_lora_rank,), jnp.float32),
        "w_qb": dense(k[1], (c.q_lora_rank, h * c.qk_head_dim), c.q_lora_rank),
        "w_kva": dense(k[2], (e, c.latent_dim), e),
        "kv_norm": jnp.ones((c.kv_lora_rank,), jnp.float32),
        "w_kvb": dense(k[3], (c.kv_lora_rank, h * (c.qk_nope_head_dim + c.v_head_dim)), c.kv_lora_rank),
        "wo": dense(k[4], (h * c.v_head_dim, e), h * c.v_head_dim),
    }
    if not experts:
        f = c.intermediate_size
        lp.update(w_gate=dense(k[5], (e, f), e), w_up=dense(k[6], (e, f), e),
                  w_down=dense(k[7], (f, e), f))
        return lp
    x, f = c.num_experts, c.moe_intermediate_size
    lp.update(
        router=dense(k[5], (e, c.num_experts_published), e, jnp.float32),
        w_gate=dense(k[6], (x, e, f), e), w_up=dense(k[7], (x, e, f), e),
        w_down=dense(k[8], (x, f, e), f),
        ws_gate=dense(k[9], (e, f), e), ws_up=dense(k[10], (e, f), e),
        ws_down=dense(k[11], (f, e), f),
    )
    return lp


def init_params(rng: jax.Array, config: OpenPanguConfig, init_layer=_init_layer) -> Params:
    """Random init with fan-in scaling; every norm weight one. ``init_layer``
    makes a layer's tree (a module on this one's block brings its own:
    ``models/xing4.py``)."""
    c = config
    e = c.hidden_size

    def dense(key, shape, fan_in):
        return _dense(key, shape, fan_in, c.dtype)

    params = {
        "embed": dense(jax.random.fold_in(rng, 1000), (c.vocab_size, e), e),
        "final_norm": jnp.ones((e,), jnp.float32),
        "layers": tuple(init_layer(jax.random.fold_in(rng, i), c, is_expert_layer(c, i))
                        for i in range(c.num_layers)),
        "lm_head": dense(jax.random.fold_in(rng, 1001), (e, c.vocab_size), e),
    }
    if c.num_mtp_layers:
        key = jax.random.fold_in(rng, 2000)
        params["mtp"] = {
            "e_norm": jnp.ones((e,), jnp.float32), "h_norm": jnp.ones((e,), jnp.float32),
            "w_eh": dense(jax.random.fold_in(key, 0), (2 * e, e), 2 * e),
            "layer": init_layer(jax.random.fold_in(key, 1), c, True),
            "norm": jnp.ones((e,), jnp.float32),
        }
    return params


def param_shardings(config: OpenPanguConfig, mesh):
    raise NotImplementedError(
        "openpangu runs on one device: experts over the chips of a host are "
        "ROADMAP M1's remainder"
    )


def make_kv_cache(
    config: OpenPanguConfig, num_blocks: int, block_size: int, dtype: Any = None,
    quantized: bool = False, drafting: bool = False,
) -> KVCache:
    """The page pool: one ``latent`` member, a layer a decoder layer and
    (``drafting``: the engine runs the prediction module) one more for the
    module's own; float32 as the activations are unless the caller names a
    ``dtype``."""
    if quantized:
        raise ValueError("openpangu has no int8 page layout")
    if drafting and not config.num_mtp_layers:
        raise ValueError("this card holds no prediction module to draft with")
    return {"latent": jnp.zeros(
        (config.num_layers + bool(drafting), num_blocks, block_size, config.latent_width),
        dtype or jnp.float32)}


def chunk_history_tiles(positions, block_size: int, table_blocks: int, lanes=None):
    """Trips of the chunk programs' loop over a block table (``ops/latent.py:
    attend_absorbed_tiled``) for rows at ``positions`` ``[B, C]``: the tiles of
    ``models/llama.py:history_tile`` positions up to the one that holds the
    rows' last position (the rows' fresh latents are in the pool before the
    loop, so it reads them there), none for padding rows alone, and no more
    than cover a table. A group of rows makes its own trips; over a whole
    dispatch this is its longest row's, which is the host's count. ``lanes``
    ``[B]`` is the engine's call form where a lane may fill several rows
    (``models/llama.py``'s reads it); it is taken and NOT read: the count is the
    dispatch's longest reach, whichever lane a row belongs to. Written for a
    traced array and a numpy one alike, as ``models/llama.py``'s."""
    tile = history_tile(block_size, table_blocks)
    reach = (positions.max() + 1).clip(0, table_blocks * block_size)
    return (reach + tile - 1) // tile


def decode_history_tiles(base, block_size: int, table_blocks: int):
    """(lane, tile) pairs a decode step attends for ``base`` ``[B]`` (a lane's
    history is the positions < base; -1: the lane does not decode): the tiles
    that hold history, a block of lanes as far as its longest
    (``ops/latent.py:live_history_tiles``, for a traced array and a numpy one
    alike)."""
    return live_history_tiles(base, block_size, table_blocks)


def final_norm(params: Params, config: OpenPanguConfig, x: jax.Array) -> jax.Array:
    """``N_f`` over the main stack's output (what ``forward_chunk(..., raw=True)``
    leaves to its caller, who also hands the raw output to :func:`draft_chunk`)."""
    return rms_norm(x, params["final_norm"], config.rms_norm_eps)


def lm_head(params: Params, config: OpenPanguConfig, h: jax.Array) -> jax.Array:
    """Final hidden states to float32 logits (the head is untied)."""
    return mm(h.astype(jnp.float32), params["lm_head"])


# -- a layer ------------------------------------------------------------------

def _swiglu(x, w_gate, w_up, w_down):
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def feed_forward(lp: Params, c: OpenPanguConfig, x: jax.Array, valid: jax.Array):
    """(output ``[B, T, E]``, the expert layer's counters: the first
    ``MOE_COUNTERS`` of ``COUNTERS``). A dense layer (no router among its
    leaves) counts nothing."""
    if "router" not in lp:
        with jax.named_scope("mlp"):
            return (_swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"]),
                    jnp.zeros((MOE_COUNTERS,), jnp.int32))
    with jax.named_scope("moe"):
        b, t, e = x.shape
        flat = x.reshape(b * t, e)
        # a selection bias where the card has one (models/xing4.py: topk_method noaux_tc)
        bias = lp["e_bias"] if "e_bias" in lp else jnp.zeros((c.num_experts_published,), jnp.float32)
        ids, weights = moe.route_sigmoid_topk(
            flat, lp["router"], bias,
            c.num_experts_per_tok, c.routed_scaling_factor, c.moe_renormalize)
        y, stats = moe.dropless_experts(
            flat, ids, weights, lp["w_gate"], lp["w_up"], lp["w_down"],
            first_expert=c.first_expert, num_experts_total=c.num_experts_published,
            token_valid=valid.reshape(-1), parts_of=_expert_parts)
        with jax.named_scope("shared"):
            y = y + _swiglu(flat, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        return y.reshape(b, t, e), stats


def _absorbed(lp: Params, c: OpenPanguConfig):
    """What ``ops/latent.py``'s two forms of absorbed attention take of a layer
    around the keys: (``W_kvb``, ``W_o``) in front, (rank, no-position width,
    value width, the scores' scale) behind."""
    return ((lp["w_kvb"], lp["wo"]),
            (c.kv_lora_rank, c.qk_nope_head_dim, c.v_head_dim, c.score_scale))


def mixer(lp: Params, c: OpenPanguConfig, a: jax.Array, positions: jax.Array, width: int, attend):
    """Latent attention over the normed ``a`` ``[B, T, E]`` at ``positions``
    ``[B, T]`` (< 0: padding). ``attend(lp, q [B, T, H, nope + rope], latent
    [B, T, W]) -> [B, T, E]`` takes the tokens' cache entries where they belong
    and attends what the queries see."""
    eps = c.rms_norm_eps
    with jax.named_scope("mla"):
        b, t, _ = a.shape
        latent = cached_latent(a, lp["w_kva"], lp["kv_norm"], c.kv_lora_rank, eps,
                               positions, c.rope_theta, width, c.rope_inv_freq)
        c_q = rms_norm(mm(a, lp["w_qa"]), lp["q_norm"], eps)
        q = mm(c_q, lp["w_qb"]).reshape(b, t, c.num_heads, c.qk_head_dim)
        dn = c.qk_nope_head_dim
        q = jnp.concatenate(
            [q[..., :dn], apply_rope(q[..., dn:], positions, c.rope_theta, c.rope_inv_freq)], axis=-1)
        return attend(lp, q, latent)


def _layer(lp: Params, c: OpenPanguConfig, x: jax.Array, positions: jax.Array, width: int, attend):
    """One decoder layer over ``x`` ``[B, T, E]`` at ``positions`` ``[B, T]``
    (< 0: padding), ``attend`` :func:`mixer`'s. Returns (x, the expert
    counters)."""
    eps = c.rms_norm_eps
    valid = positions >= 0
    attn = mixer(lp, c, rms_norm(x, lp["in_norm"], eps), positions, width, attend)
    x = x + rms_norm(attn, lp["post_attn_norm"], eps)
    y, stats = feed_forward(lp, c, rms_norm(x, lp["pre_mlp_norm"], eps), valid)
    return x + rms_norm(y, lp["post_mlp_norm"], eps), stats


def _mla_counts(layers: int, positions: jax.Array, attended) -> jax.Array:
    """``mla_layer_calls``, ``..._positions_read``, ``..._positions_live`` of
    ``layers`` calls over rows at ``positions`` ``[B, T]``: a row with a token
    attends ``attended`` cached positions (one number, or one a row ``[B]``: a
    decode lane the tiles its block of lanes reads and the dispatch's steps, a
    chunk's row the tiles its group reads: trips x tile), of which the
    positions up to its last held history."""
    last = positions.max(axis=1)
    fed = last >= 0
    return jnp.stack([jnp.int32(layers), layers * jnp.sum(fed * attended),
                      layers * jnp.sum(jnp.where(fed, last + 1, 0))]).astype(jnp.int32)


def _mtp_input(params: Params, c: OpenPanguConfig, hidden: jax.Array, next_tokens: jax.Array):
    """``u = W_eh [N_e(Emb(t_{i+1})) ; N_h(x_i)]``."""
    mp = params["mtp"]
    emb = embed_lookup(params, next_tokens, c.dtype).astype(jnp.float32)
    return mm(jnp.concatenate([rms_norm(emb, mp["e_norm"], c.rms_norm_eps),
                               rms_norm(hidden, mp["h_norm"], c.rms_norm_eps)], axis=-1), mp["w_eh"])


# -- the step programs --------------------------------------------------------

def _in_groups(rows_fn, pool: jax.Array, arrays: tuple, width: int, counters: int = len(COUNTERS)):
    """``rows_fn(pool, *arrays) -> (h, pool, counters)`` over the rows of
    ``arrays`` (each ``[R, ...]``), all at once where they hold at most
    ``TOKENS_AT_ONCE`` positions and else in groups of that many, one after
    another (a row touches its own pages only)."""
    rows = arrays[0].shape[0]
    at_once = max(1, TOKENS_AT_ONCE // width)
    if rows <= at_once:
        return rows_fn(pool, *arrays)
    if rows % at_once:
        raise ValueError(f"{rows} rows are no whole number of groups of {at_once}")

    def group(carry, xs):
        pool, sums = carry
        h, pool, more = rows_fn(pool, *xs)
        return (pool, sums + more), h

    (pool, sums), h = jax.lax.scan(
        group, (pool, jnp.zeros((counters,), jnp.int32)),
        tuple(a.reshape(rows // at_once, at_once, *a.shape[1:]) for a in arrays))
    return h.reshape(rows, *h.shape[2:]), pool, sums


def _paged(c: OpenPanguConfig, pool_box: list, layer: int, positions, block_tables, n_tiles):
    """``attend`` of a chunk's rows: the tokens' latents into the pool's
    ``layer`` first, then the queries against the rows' block tables as the
    pool holds them, a tile of ``models/llama.py:history_tile`` positions a
    trip and ``n_tiles`` trips (:func:`chunk_history_tiles` of these rows): a
    query meets its own row's fresh keys, and what any earlier dispatch or row
    left in the same pages, where they lie; what lies past the rows' last
    position is not read."""
    bs = pool_box[0].shape[2]
    tile_blocks = history_tile(bs, block_tables.shape[1]) // bs

    def attend(lp, q, latent):
        pool_box[0] = write_latent(pool_box[0], layer, latent, positions, block_tables)
        ends, dims = _absorbed(lp, c)
        return attend_absorbed_tiled(q, *ends, pool_box[0], layer, block_tables, positions,
                                     n_tiles, tile_blocks, *dims)
    return attend


def _tiles_read(positions, pool: jax.Array, block_tables):
    """(trips of a chunk's rows over their tables, the cached positions a row
    attends in them)."""
    bs, mb = pool.shape[2], block_tables.shape[1]
    n_tiles = chunk_history_tiles(positions, bs, mb)
    return n_tiles, n_tiles * history_tile(bs, mb)


def forward_chunk(
    params: Params, config: OpenPanguConfig, tokens: jax.Array, positions: jax.Array,
    kv_cache: KVCache, block_tables: jax.Array, state: None, lanes: jax.Array,
    raw: bool = False,
):
    """A ``[R, C]`` block of tokens, a row a lane or (``LANE_TAKES_ROWS``)
    successive pieces of a lane's prompt in consecutive rows, in order, each
    with the lane's block table (``lanes`` is the engine's call form; nothing
    here is kept by lane and it is read nowhere), valid tokens (position >= 0)
    a prefix of each row; a row may start at any position (a prefix hit, a
    later chunk, a lane's later row, a verify dispatch): what lies before it
    is read from the pages, as its own tokens' latents are once written, and
    no page past the last position of the group of rows it is computed with.

    Returns (hidden ``[R, C, E]`` after the final norm, or before it where
    ``raw``; the pool with the rows' latents written; ``state`` as it came:
    None; the counters ``[len(COUNTERS)]``)."""
    c = config

    def rows_fn(pool, tokens, positions, block_tables):
        box = [pool]
        n_tiles, attended = _tiles_read(positions, pool, block_tables)
        x = embed_lookup(params, tokens, c.dtype).astype(jnp.float32)
        counters = jnp.zeros((MOE_COUNTERS,), jnp.int32)
        for i, lp in enumerate(params["layers"]):
            x, stats = _layer(lp, c, x, positions, pool.shape[-1],
                              _paged(c, box, i, positions, block_tables, n_tiles))
            counters = counters + stats
        own = _mla_counts(c.num_layers, positions, attended)
        h = x if raw else final_norm(params, c, x)
        return h, box[0], jnp.concatenate([counters, own, jnp.zeros((1,), jnp.int32)])

    h, pool, sums = _in_groups(rows_fn, kv_cache["latent"], (tokens, positions, block_tables),
                               tokens.shape[1])
    return h, {"latent": pool}, state, sums


def draft_chunk(
    params: Params, config: OpenPanguConfig, hidden: jax.Array, next_tokens: jax.Array,
    positions: jax.Array, kv_cache: KVCache, block_tables: jax.Array,
):
    """The prediction module over the positions a dispatch computed:
    ``hidden`` ``[R, C, E]`` the main stack's RAW output there, ``next_tokens``
    ``[R, C]`` the token that follows each. Writes the module's own latent
    pages (the pool's last layer) and returns (hidden ``[R, C, E]`` after
    ``N_mtp``, whose head logits score the token AFTER ``next_tokens``; the
    pool; the counters)."""
    c = config
    layer = c.num_layers  # the module's pages lie behind the decoder's
    if kv_cache["latent"].shape[0] <= layer:
        raise ValueError("the pool holds no pages for the prediction module (make_kv_cache(drafting=True))")

    def rows_fn(pool, hidden, next_tokens, positions, block_tables):
        box = [pool]
        n_tiles, attended = _tiles_read(positions, pool, block_tables)
        with jax.named_scope("mtp"):
            u = _mtp_input(params, c, hidden, next_tokens)
            x, stats = _layer(params["mtp"]["layer"], c, u, positions, pool.shape[-1],
                              _paged(c, box, layer, positions, block_tables, n_tiles))
            h = rms_norm(x, params["mtp"]["norm"], c.rms_norm_eps)
        own = _mla_counts(1, positions, attended)
        return h, box[0], jnp.concatenate([stats, own, jnp.ones((1,), jnp.int32)])

    h, pool, sums = _in_groups(rows_fn, kv_cache["latent"],
                               (hidden, next_tokens, positions, block_tables), hidden.shape[1])
    return h, {"latent": pool}, sums


def decode(
    params: Params, config: OpenPanguConfig, tokens: jax.Array, positions: jax.Array,
    kv_cache: KVCache, block_tables: jax.Array, state: None, steps: int, max_pos: int,
    sample, carry, draft: bool = False,
):
    """``steps`` tokens of every slot (``tokens``, ``positions`` ``[S]``;
    position < 0 = the slot does not decode).

    Every layer's history is gathered ONCE a dispatch, the lanes longest
    first in blocks (``ops/latent.py:live_latents``), and a step attends what
    of it is live: a block of lanes the tiles of
    ``models/llama.py:history_tile`` positions up to its longest lane's
    (:func:`decode_history_tiles` is the count). A step's latent goes to a
    small ``[S, steps, W]`` buffer a layer; the step attends the lane's tiles
    and the buffer's rows up to its own (``attend_absorbed_live``), and the
    pool takes the buffers after the loop in one scatter a layer.
    ``sample(logits [S, V], positions, carry, k) -> (next tokens [S], carry,
    outputs)`` is the engine's. Where
    ``draft``, every step also runs the prediction module on its raw output
    and the token it sampled (the module's own history beside the layers').
    Returns (tokens, positions, carry, the stacked outputs, pool, ``state`` as
    it came, counters ``[len(COUNTERS)]``) and, where ``draft``, the module's
    first choice for the token AFTER the last one sampled, ``[S]`` int32."""
    c = config
    pool = kv_cache["latent"]
    n_hist = c.num_layers + bool(draft)
    if pool.shape[0] < n_hist:
        raise ValueError("the pool holds no pages for the prediction module (make_kv_cache(drafting=True))")
    live = live_latents(pool, n_hist, block_tables, positions)
    attended = live_positions_attended(live, steps)
    # read ONCE a dispatch, whoever decodes: every lane's whole table, by the gather
    gathered = n_hist * block_tables.size * pool.shape[2]

    def step(loop, k):
        toks, pos, carry, recent, counters, drafts = loop
        recent = list(recent)
        pos2 = pos[:, None]

        def buffered(j):
            def attend(lp, q, latent):
                ends, dims = _absorbed(lp, c)
                out, recent[j] = attend_absorbed_live(q, *ends, live, j, recent[j], latent, k, pos >= 0, *dims)
                return out
            return attend

        x = embed_lookup(params, toks, c.dtype).astype(jnp.float32)[:, None]
        for i, lp in enumerate(params["layers"]):
            x, stats = _layer(lp, c, x, pos2, pool.shape[-1], buffered(i))
            counters = counters.at[:MOE_COUNTERS].add(stats)
        nxt, carry, out = sample(lm_head(params, c, final_norm(params, c, x))[:, 0], pos, carry, k)
        own = _mla_counts(n_hist, pos2, attended)
        if draft:
            with jax.named_scope("mtp"):
                u = _mtp_input(params, c, x, nxt[:, None])
                y, stats = _layer(params["mtp"]["layer"], c, u, pos2, pool.shape[-1],
                                  buffered(c.num_layers))
                y = rms_norm(y, params["mtp"]["norm"], c.rms_norm_eps)
                guess = jnp.argmax(lm_head(params, c, y)[:, 0], axis=-1).astype(jnp.int32)
            drafts = jnp.where(pos >= 0, guess, drafts)
            counters = counters.at[:MOE_COUNTERS].add(stats)
        counters = counters.at[MOE_COUNTERS:].add(
            jnp.concatenate([own, jnp.full((1,), int(draft), jnp.int32)]))
        new_pos = jnp.where((pos >= 0) & (pos < max_pos), pos + 1, -1)
        return (nxt, new_pos, carry, tuple(recent), counters, drafts), (out, pos)

    (toks, pos, carry, recent, counters, drafts), (out, at) = jax.lax.scan(
        step,
        (tokens, positions, carry, recent_latents(pool, n_hist, tokens.shape[0], steps),
         jnp.zeros((len(COUNTERS),), jnp.int32).at[COUNTERS.index("mla_history_positions_read")].set(gathered),
         jnp.zeros_like(tokens)),
        jnp.arange(steps))
    for j, lat in enumerate(recent):  # [S, steps, W], written at `at` [steps, S]
        pool = write_latent(pool, j, lat, at.T, block_tables)
    done = (toks, pos, carry, out, {"latent": pool}, state, counters)
    return (*done, drafts) if draft else done
