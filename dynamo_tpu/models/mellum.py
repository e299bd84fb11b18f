"""Mellum 2 decoder (``model_type: mellum``): window attention layers beside
full attention layers, three to one, BOTH rotated, each kind by a table of its
own; a norm on every head of q and k; two RMS norms a layer; softmax-routed
experts in every layer (8 of 64, renormalised; no shared expert, no dense
layer); an untied head. The first module here that serves on a MESH: 12 B
parameters are 24.3 GB of bfloat16, which no chip holds and one four-chip
host does.

The two kinds of attention, their two caches and their two lifetimes are
``models/trinity.py``'s, and what is the same is imported from there and from
``models/llama.py``: a FULL layer's keys and values are the pool's pages (the
Llama layout ``{"k", "v"}`` ``[L_full, N, bs, KVH, D]``), a WINDOW layer's a
ring a slot (``ops/ring.py``; ``sliding_window + RING_BLOCK`` positions however
long the lane grows). What differs:

- **Two rotary tables, chosen by the layer's kind.** A window layer rotates q
  and k by ``rope_theta``'s plain frequencies. A full layer rotates them by
  YaRN's blend of those (``models/xing4.py:yarn_inv_freq``) with cosine and
  sine both times ``attention_factor``, at every position (the table is
  static). Keys are stored rotated, in the pages as in the rings.
- **The layers lie in PERIODS** (window, window, window, full): every leaf of
  ``params["layers"]`` is stacked ``[periods, layers a period, ...]``, the pool
  is ``[periods, N, bs, KVH, D]`` (one full layer a period), the rings
  ``[periods, window layers a period, S, KVH, P, D]``, and both step programs
  are a ``lax.scan`` over the periods around one period's layers written out:
  a program's text does not grow with ``num_hidden_layers``. The experts'
  matrices are NOT sliced out of their stack by the scan (the chip's compiler
  copies a slice in front of a kernel): the grouped products take the stack
  whole and are told the layer (``ops/moe.py:dropless_experts(stacked_at=)``).
- **On a mesh** (``forward_chunk(..., mesh=)``, ``decode(..., mesh=)``;
  ``SERVES_ON_MESH``) a step program is ONE ``shard_map`` over the mesh's one
  axis larger than 1: a shard runs the one-device program over its heads (8 of
  32 query heads and ONE of 4 KV heads on a four-chip host, in its pages and
  in its rings), its experts (16 of 64, ids ``16 c ...`` on shard ``c``; the
  router whole on every shard) and its rows of the vocabulary, and hands the
  others what they lack in collectives that are written down here: an
  all-reduce of the out-projection's partial sums (``attn_exchange``), one of
  the experts' partial sums (``moe_exchange``: under tensor-parallel attention
  every shard holds every row, so the exchange of an expert-parallel layer IS
  that sum), one of the embedding's rows, and an all-gather of a decode step's
  logits. Eight all-reduces of ``[rows, hidden]`` float32 a period, 56 a
  program. Nothing is left to the compiler's partitioner inside a step
  program, and every Pallas call runs per shard.

A layer on ``x``: ``x += Attn(N1(x)) W_o``; ``x += Experts(N2(x))``.

The arithmetic is ``models/trinity.py``'s for its reason (a router that keeps
8 of 64 is the same discontinuity): bfloat16 weights, float32 activations,
every product that a later router sees in THREE bfloat16 parts, float32 pages
and rings with attention's own products at float32's precision, the head in
one part, float32 all-reduces. The configuration's file keeps the readings.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from dynamo_tpu.models.llama import (  # noqa: F401  (the two tile counts are this module's too)
    _chunk_self_partial, _live_window_attention, _merge_partials, _pool_pages, apply_rope,
    chunk_history_partial, chunk_history_tiles, chunk_layout, chunk_rows_above_partial,
    decode_history_tiles, embed_lookup, flush_window, history_tile, history_tiles_full, rms_norm,
    with_live_history,
)
from dynamo_tpu.models.trinity import (  # noqa: F401  (`lane_rows_most`: the engine reads it here)
    ATTENTION_PRECISION, FULL, RING_BLOCK, ROWS_AT_ONCE, WINDOW, _Left, _swa_counts, lane_rows_most,
)
from dynamo_tpu.models.xing4 import yarn_inv_freq
from dynamo_tpu.ops import moe, ring
from dynamo_tpu.ops.latent import PASSES, mm
from dynamo_tpu.ops.parts import dot_parts, operand_parts

Params = Dict[str, Any]
KVCache = Dict[str, jax.Array]  # {"k", "v"}: [periods, N, bs, KVH, D]
SlotState = Dict[str, jax.Array]  # {"k", "v"}: [periods, window layers a period, S, KVH, P, D]

# sums the step programs return, in this order (engine: /debug/engine): the six
# of ops/moe.py:dropless_experts, summed over the shards (a call is one expert
# layer over a decode step's lanes or a group of a chunk's rows; `moe_held_rows`
# is then every routed pair, each held on some shard); `models/trinity.py`'s
# four of the window layers and its count of full layers run; of the routed
# pairs, those that went to the shard that got most, summed a layer call, and
# all of them (one shard: the same number twice); the rows a shard handed to an
# all-reduce of attention's or the experts' partial sums (none on one device)
COUNTERS = ("moe_layer_calls", "moe_held_rows", "moe_experts_hit", "moe_routed_pairs",
            "moe_rows_computed", "moe_expert_reads", "swa_layer_calls",
            "swa_history_positions_read", "swa_history_positions_live",
            "swa_history_positions_whole", "full_layer_calls", "moe_pairs_fullest_shard",
            "moe_pairs_all_shards", "exchange_rows")
MOE_COUNTERS = COUNTERS.index("swa_layer_calls")  # what `dropless_experts` counts comes first
# `models/trinity.py`'s two, for its reasons: nothing is handed from row to row,
# and `forward_chunk` reads `lanes` at every width
LANE_TAKES_ROWS = True
FULL_WIDTH_TAKES_ROWS = True
# the engine hands `forward_chunk` and `decode` its mesh (`mesh=`) and makes the
# pool and the slots' state through `make_kv_cache` / `make_slot_state(mesh=)`
SERVES_ON_MESH = True

_expert_parts = partial(operand_parts, parts=PASSES)  # ops/moe.py:dropless_experts' ``parts_of``


@dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    num_layers: int = 28
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    # "sliding_attention" or "full_attention" for each layer, as published:
    # whole periods of some window layers and then one full layer
    layer_types: Tuple[str, ...] = (WINDOW, WINDOW, WINDOW, FULL) * 7
    sliding_window: int = 1024
    rope_theta: float = 500000.0  # both kinds'
    # the full layers' YaRN table
    yarn_factor: float = 16.0
    yarn_original_positions: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    attention_factor: float = 1.2772588722239782  # on cosine and sine: 0.1 ln(factor) + 1
    moe_intermediate_size: int = 896
    num_experts: int = 64  # the router's width, and what all shards hold together
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        kinds = self.layer_types
        n = kinds.index(FULL) + 1 if FULL in kinds else 0
        if (len(kinds) != self.num_layers or n < 2 or len(kinds) % n
                or kinds != ((WINDOW,) * (n - 1) + (FULL,)) * (len(kinds) // n)):
            raise ValueError(
                f"layer_types names {len(kinds)} layers {kinds[:8]}...: {self.num_layers} wanted, in "
                f"whole periods of one or more {WINDOW!r} and then one {FULL!r}")
        if self.sliding_window % RING_BLOCK or self.sliding_window <= 0:
            raise ValueError(
                f"sliding_window {self.sliding_window} is no whole number of blocks of {RING_BLOCK}")

    @property
    def period(self) -> Tuple[str, ...]:
        """One period's kinds: the window layers and then the full one."""
        return self.layer_types[:self.layer_types.index(FULL) + 1]

    @property
    def num_periods(self) -> int:
        return self.num_layers // len(self.period)

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

    @property
    def full_inv_freq(self) -> Tuple[float, ...]:
        """The full layers' ``head_dim / 2`` rotary frequencies."""
        return yarn_inv_freq(self.head_dim, self.rope_theta, self.yarn_factor,
                             self.yarn_original_positions, self.yarn_beta_fast, self.yarn_beta_slow)


# -- parameters, and where they lie on a mesh ------------------------------------

def init_params(rng: jax.Array, config: MellumConfig) -> Params:
    """Random init with fan-in scaling; the routers and every norm float32.
    A leaf of the layers is made a period at a time (``lax.map``), so that no
    float32 copy of a whole stack of experts exists beside it."""
    c = config
    e, x, f = c.hidden_size, c.num_experts, c.moe_intermediate_size
    periods, in_period = c.num_periods, len(c.period)

    def dense(key, shape, fan_in, dtype=None):
        w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
        return w.astype(dtype or c.dtype)

    def stacked(n, shape, fan_in, dtype=None):
        keys = jax.random.split(jax.random.fold_in(rng, n), periods)
        return jax.lax.map(lambda key: dense(key, (in_period, *shape), fan_in, dtype), keys)

    def ones(*shape):
        return jnp.ones((periods, in_period, *shape), jnp.float32)

    return {
        "embed": dense(jax.random.fold_in(rng, 1000), (c.vocab_size, e), e),
        "final_norm": jnp.ones((e,), jnp.float32),
        "layers": {
            "in_norm": ones(e), "mlp_norm": ones(e),
            "q_norm": ones(c.head_dim), "k_norm": ones(c.head_dim),
            "wq": stacked(0, (e, c.q_dim), e), "wk": stacked(1, (e, c.kv_dim), e),
            "wv": stacked(2, (e, c.kv_dim), e), "wo": stacked(3, (c.q_dim, e), c.q_dim),
            "router": stacked(4, (e, x), e, jnp.float32),
            "w_gate": stacked(5, (x, e, f), e), "w_up": stacked(6, (x, e, f), e),
            "w_down": stacked(7, (x, f, e), f),
        },
        "lm_head": dense(jax.random.fold_in(rng, 1001), (e, c.vocab_size), e),
    }


_EXPERTS = ("w_gate", "w_up", "w_down")


def _param_specs(axis: Optional[str]) -> Params:
    """Where each leaf lies over the mesh axis ``axis``: q, k and v by head and
    the out-projection by row, the experts by expert, embedding and head by
    vocabulary row; routers and norms on every shard."""
    in_stack = (None, None)  # a leaf of the layers leads with [periods, layers a period]
    whole = P()
    return {
        "embed": P(axis, None), "final_norm": whole, "lm_head": P(None, axis),
        "layers": {
            "in_norm": whole, "mlp_norm": whole, "q_norm": whole, "k_norm": whole, "router": whole,
            "wq": P(*in_stack, None, axis), "wk": P(*in_stack, None, axis),
            "wv": P(*in_stack, None, axis), "wo": P(*in_stack, axis, None),
            **{name: P(*in_stack, axis, None, None) for name in _EXPERTS},
        },
    }


def _cache_specs(axis: Optional[str]):
    """(the pool's arrays, the rings): both by KV head."""
    return P(None, None, None, axis, None), P(None, None, None, axis, None, None)


def _shards(config: MellumConfig, mesh) -> Tuple[str, int]:
    """(the mesh axis the model lies over, its size), held against what has to
    divide: heads, KV heads, experts and vocabulary rows."""
    from dynamo_tpu.parallel.mesh import model_axis

    axis, n = model_axis(mesh)
    c = config
    for name, size in (("num_attention_heads", c.num_heads), ("num_key_value_heads", c.num_kv_heads),
                       ("num_experts", c.num_experts), ("vocab_size", c.vocab_size)):
        if size % n:
            raise ValueError(
                f"model_type 'mellum' with {name} = {size} on a mesh axis {axis!r} of {n}: "
                f"models/mellum.py gives every shard a whole and equal number of them")
    return axis, n


def param_shardings(config: MellumConfig, mesh) -> Params:
    """NamedSharding pytree matching :func:`init_params`' structure."""
    axis, _ = _shards(config, mesh)
    return jax.tree.map(lambda spec: NamedSharding(mesh, spec), _param_specs(axis),
                        is_leaf=lambda s: isinstance(s, P))


# -- the two caches ---------------------------------------------------------------

def _made(make, mesh, spec):
    """``make()`` (a dict of arrays), every leaf created in its sharding where
    there is a mesh: it never exists whole on one device."""
    if mesh is None:
        return make()
    return jax.jit(make, out_shardings=NamedSharding(mesh, spec))()


def make_kv_cache(
    config: MellumConfig, num_blocks: int, block_size: int, dtype: Any = None,
    quantized: bool = False, mesh=None,
) -> KVCache:
    """The FULL layers' page pool, in the Llama layout (one full layer a
    period); on a mesh, by KV head."""
    if quantized:
        raise ValueError("mellum has no int8 page layout")
    c = config
    shape = (c.num_periods, num_blocks, block_size, c.num_kv_heads, c.head_dim)
    spec = _cache_specs(_shards(c, mesh)[0])[0] if mesh is not None else None
    return _made(lambda: {name: jnp.zeros(shape, dtype or jnp.float32) for name in ("k", "v")},
                 mesh, spec)


def make_slot_state(config: MellumConfig, slots: int, mesh=None) -> SlotState:
    """The WINDOW layers' rings of every slot (``ops/ring.py``), one array:
    ``sliding_window + RING_BLOCK`` positions a slot, whatever the server's
    ``--max-model-len``; on a mesh, by KV head."""
    c = config
    shape = (c.num_periods, len(c.period) - 1, slots, c.num_kv_heads, c.ring_positions, c.head_dim)
    spec = _cache_specs(_shards(c, mesh)[0])[1] if mesh is not None else None
    return _made(lambda: {name: jnp.zeros(shape, jnp.float32) for name in ("k", "v")}, mesh, spec)


# -- the layers -------------------------------------------------------------------

def final_norm(params: Params, config: MellumConfig, x: jax.Array) -> jax.Array:
    return rms_norm(x, params["final_norm"], config.rms_norm_eps)


def lm_head(params: Params, config: MellumConfig, h: jax.Array) -> jax.Array:
    """Final hidden states to float32 logits (untied; no router follows it:
    one part). Called by the engine OUTSIDE a step program: on a mesh the
    compiler partitions this one product by the head's columns."""
    return dot_parts(h, params["lm_head"])


def _all_reduce(y: jax.Array, axis: Optional[str]) -> jax.Array:
    return y if axis is None else jax.lax.psum(y, axis)


def _embed(params: Params, c: MellumConfig, tokens: jax.Array, axis: Optional[str]) -> jax.Array:
    """Float32 embeddings (no scale). A shard holds a run of the table's rows:
    it looks up the tokens that lie in it, and the shards' sum is the lookup."""
    if axis is None:
        return embed_lookup(params, tokens, c.dtype).astype(jnp.float32)
    table = params["embed"]
    at = jnp.clip(tokens, 0) - jax.lax.axis_index(axis) * table.shape[0]
    mine = (at >= 0) & (at < table.shape[0])
    rows = table[jnp.clip(at, 0, table.shape[0] - 1)].astype(jnp.float32)
    return jax.lax.psum(jnp.where(mine[..., None], rows, 0.0), axis)


def _logits(params: Params, c: MellumConfig, h: jax.Array, axis: Optional[str]) -> jax.Array:
    """:func:`lm_head` inside a step program: a shard's columns, gathered."""
    y = dot_parts(h, params["lm_head"])
    return y if axis is None else jax.lax.all_gather(y, axis, axis=-1, tiled=True)


def _rotated(c: MellumConfig, kind: str, x: jax.Array, positions: jax.Array) -> jax.Array:
    """``x`` ``[B, T, H, D]`` rotated by the table of the layer's kind."""
    if kind == WINDOW:
        return apply_rope(x, positions, c.rope_theta)
    return apply_rope(x, positions, c.rope_theta, c.full_inv_freq) * c.attention_factor


def _project(lp: Params, c: MellumConfig, kind: str, a: jax.Array, positions: jax.Array):
    """q, k and v of normed inputs ``a`` ``[B, T, E]``: q and k split into
    heads, normed over each head's ``D`` (one weight for all heads), THEN
    rotated by the kind's table; float32, as the pages and the rings are. No
    bias."""
    b, t, _ = a.shape
    eps = c.rms_norm_eps
    q = rms_norm(mm(a, lp["wq"]).reshape(b, t, c.num_heads, c.head_dim), lp["q_norm"], eps)
    k = rms_norm(mm(a, lp["wk"]).reshape(b, t, c.num_kv_heads, c.head_dim), lp["k_norm"], eps)
    v = mm(a, lp["wv"]).reshape(b, t, c.num_kv_heads, c.head_dim)
    return _rotated(c, kind, q, positions), _rotated(c, kind, k, positions), v


def _first_held(axis: Optional[str], held: int):
    """The id of the first expert this shard holds: ``held`` a shard, in order."""
    return 0 if axis is None else jax.lax.axis_index(axis) * held


def _expert_layer(lp: Params, experts: Params, c: MellumConfig, axis: Optional[str], layer,
                  m: jax.Array, valid: jax.Array):
    """The experts over normed ``m`` ``[B, T, E]``: the router whole (softmax
    over all ``num_experts``, the ``num_experts_per_tok`` largest renormalised),
    the part of the sum that the experts held here give (``experts``: the
    layers' stack ``[L * held, ...]``, this layer's at ``layer``; a shard holds
    ids ``shard * held`` onwards), and the shards' parts added up. Returns
    (the sum ``[B, T, E]``, this shard's six counters, the pairs each shard got
    ``[shards]``)."""
    b, t, e = m.shape
    flat, valid = m.reshape(b * t, e), valid.reshape(-1)
    held = experts["w_gate"].shape[0] // c.num_layers
    with jax.named_scope("moe"):
        ids, weights = moe.route_softmax_topk(flat, lp["router"], c.num_experts_per_tok, c.norm_topk_prob)
        first = _first_held(axis, held)
        y, stats = moe.dropless_experts(
            flat, ids - first, weights, *(experts[name] for name in _EXPERTS),
            num_experts_total=c.num_experts, token_valid=valid, parts_of=_expert_parts,
            stacked_at=layer, experts_a_layer=held)
    with jax.named_scope("moe_exchange"):
        y = _all_reduce(y, axis)
    return y.reshape(b, t, e), stats, moe.shard_pairs(ids, valid, held, c.num_experts // held)


def _layer(lp: Params, experts: Params, c: MellumConfig, axis: Optional[str], kind: str, layer,
           x: jax.Array, positions: jax.Array, attend):
    """One decoder layer over ``x`` ``[B, T, E]`` at ``positions`` ``[B, T]``
    (< 0: padding). ``attend(q, k, v) -> [B, T, H, D]`` attends what the
    queries see and keeps the fresh keys and values. ``c`` is the shard's:
    its heads. Returns (x, the expert layer's counters, the pairs a shard)."""
    eps = c.rms_norm_eps
    b, t, _ = x.shape
    a = rms_norm(x, lp["in_norm"], eps)
    with jax.named_scope("swa" if kind == WINDOW else "full_attn"):
        q, k, v = _project(lp, c, kind, a, positions)
        with jax.default_matmul_precision(ATTENTION_PRECISION):
            out = attend(q, k, v)
        y = mm(out.astype(jnp.float32).reshape(b, t, c.q_dim), lp["wo"])
    with jax.named_scope("attn_exchange"):
        x = x + _all_reduce(y, axis)
    y, stats, pairs = _expert_layer(lp, experts, c, axis, layer, rms_norm(x, lp["mlp_norm"], eps),
                                    positions >= 0)
    return x + y, stats, pairs


def _split(params: Params):
    """(the layers' leaves a ``lax.scan`` over periods slices, the experts'
    matrices as ONE stack ``[L * held, ...]`` that it does not)."""
    layers = params["layers"]
    experts = {name: layers[name].reshape(-1, *layers[name].shape[3:]) for name in _EXPERTS}
    return {name: a for name, a in layers.items() if name not in _EXPERTS}, experts


def _counted(c: MellumConfig, axis: Optional[str], stats, pairs, own, rows) -> jax.Array:
    """``[len(COUNTERS)]``: the expert layers' six, the window layers' four and
    the full layers' calls, the fullest shard's pairs and all shards', and the
    ``rows`` that went through each of a layer's two all-reduces."""
    sent = 0 if axis is None else 2 * c.num_layers * rows
    return jnp.concatenate([
        stats, own, jnp.stack([jnp.int32(c.layer_types.count(FULL)), *pairs, jnp.int32(sent)]),
    ]).astype(jnp.int32)


def _over_shards(counters: jax.Array, axis: Optional[str], shards: int) -> jax.Array:
    """A program's counters as the host adds them up: the expert layers' six
    are a shard's own and are summed over the shards (the calls and the routed
    pairs are every shard's alike: once); the rest every shard counts alike."""
    if axis is None:
        return counters
    stats = jax.lax.psum(counters[:MOE_COUNTERS], axis)
    alike = jnp.asarray([name in ("moe_layer_calls", "moe_routed_pairs")
                         for name in COUNTERS[:MOE_COUNTERS]])
    return counters.at[:MOE_COUNTERS].set(jnp.where(alike, stats // shards, stats))


# -- on a mesh ----------------------------------------------------------------------

def _per_shard(program, config: MellumConfig, mesh, n_replicated_out: int):
    """``program(params, pool, state, host, *, c, axis, shards) -> (replicated
    outputs, pool, state, counters)`` over the shards of ``mesh``: every shard
    runs it over its heads, experts and vocabulary rows (``c``: the shard's
    view of the configuration; ``host``: the arrays the host makes, which go
    in whole). Without a mesh: the program itself, on one device."""
    if mesh is None:
        return partial(program, c=config, axis=None, shards=1)
    from jax import shard_map

    axis, n = _shards(config, mesh)
    mine = replace(config, num_heads=config.num_heads // n, num_kv_heads=config.num_kv_heads // n)
    pool, rings = _cache_specs(axis)
    return shard_map(
        partial(program, c=mine, axis=axis, shards=n), mesh=mesh,
        in_specs=(_param_specs(axis), pool, rings, P()),
        out_specs=((P(),) * n_replicated_out, pool, rings, P()), check_vma=False)


# -- the step programs --------------------------------------------------------------

def forward_chunk(
    params: Params, config: MellumConfig, tokens: jax.Array, positions: jax.Array,
    kv_cache: KVCache, block_tables: jax.Array, state: SlotState, lanes: jax.Array, mesh=None,
):
    """``models/trinity.py:forward_chunk``'s contract, to the word: a ``[R, C]``
    block of prompt tokens (``lanes`` ``[R]``: the row's slot; ``max_slots`` and
    above = a padding row), a lane's successive pieces in consecutive rows at
    any number of rows. Returns (hidden ``[R, C, E]`` after the final norm, the
    pool with the full layers' K and V written, the slot state with the window
    layers' written into the rows' lanes' rings, the counters
    ``[len(COUNTERS)]``). With ``mesh``: one ``shard_map`` (:func:`_per_shard`)."""
    (h,), cache, new_state, counters = _per_shard(_chunk, config, mesh, 1)(
        params, kv_cache, state, (tokens, positions, block_tables, lanes))
    return h, cache, new_state, counters


def _chunk(params, kv_cache, state, host, *, c, axis, shards):
    from dynamo_tpu.ops.attention import write_kv_to_pool

    tokens, positions, block_tables, lanes = host
    rows, width = tokens.shape
    periods, n_win, slots = state["k"].shape[:3]
    in_period = n_win + 1
    a_lane = min(rows, lane_rows_most(c, width))  # the rows one lane may fill of this dispatch
    if a_lane * width > c.ring_positions:
        raise ValueError(
            f"a lane's {a_lane} rows of {width} positions pass the {c.ring_positions} positions a "
            f"window layer keeps of it: two of them would be written to one entry of its ring")
    layout = chunk_layout(positions, lanes, slots)
    # the dispatch's fresh keys and values, every layer's: a group's rows go in where they stand
    none_yet = jnp.zeros((periods, in_period, *tokens.shape, c.num_kv_heads, c.head_dim), jnp.float32)
    group = partial(_chunk_rows, params, c, axis, _pool_pages(kv_cache), kv_cache["k"].shape[1],
                    state, layout)
    if rows <= ROWS_AT_ONCE:
        h, k, v, counters = group(_Left(jnp.int32(0), none_yet, none_yet), tokens, positions,
                                  block_tables, lanes)
    else:
        if rows % ROWS_AT_ONCE:
            raise ValueError(f"{rows} rows are no whole number of groups of {ROWS_AT_ONCE}")
        # the groups as far as the last row that holds a token, as `models/trinity.py` takes them,
        # but a conditional a group and no loop: a loop whose trip count the chip's compiler
        # cannot see, around collectives, aborts it (AOT for v5e:2x2, PR 68). Every shard sees
        # the same rows, so every shard takes the same branch
        fed = (lanes < slots) & (positions[:, 0] >= 0)
        last = jnp.max(jnp.where(fed, jnp.arange(rows) + 1, 0))

        def step(at, carry):
            sums, k, v, h = carry
            hg, k, v, more = group(_Left(jnp.int32(at), k, v), *(
                a[at:at + ROWS_AT_ONCE] for a in (tokens, positions, block_tables, lanes)))
            return sums + more, k, v, h.at[at:at + ROWS_AT_ONCE].set(hg)

        carry = (jnp.zeros((len(COUNTERS),), jnp.int32), none_yet, none_yet,
                 jnp.zeros((*tokens.shape, c.hidden_size), jnp.float32))
        for at in range(0, rows, ROWS_AT_ONCE):
            carry = jax.lax.cond(at < last, partial(step, at), lambda carry: carry, carry)
        counters, k, v, h = carry
    # a period's last layer is its full one: ONE scatter a pool array, one a stack of rings
    cache = {name: write_kv_to_pool(kv_cache[name], new[:, n_win], positions, block_tables)
             for name, new in (("k", k), ("v", v))}
    new_state = {name: _rings_written(state[name], new[:, :n_win].transpose(0, 1, 2, 4, 3, 5),
                                      positions, lanes)
                 for name, new in (("k", k), ("v", v))}
    return (h,), cache, new_state, _over_shards(counters, axis, shards)


def _rings_written(rings: jax.Array, new: jax.Array, positions: jax.Array, lanes: jax.Array):
    """``rings`` ``[periods, window layers, S, KVH, P, D]`` with ``new``
    ``[periods, window layers, B, KVH, T, D]`` written at ``positions`` ``[B,
    T]`` of the slots ``lanes`` ``[B]``: every layer's in ONE
    ``ops/ring.py:ring_write``, to which the stack is one ring of ``layers x
    S`` slots (a lane past the slots, a padding row, stays past them)."""
    periods, n_win, slots = rings.shape[:3]
    layers = periods * n_win
    of_layer = jnp.arange(layers)[:, None] * slots + lanes[None, :]  # [layers, B]
    of_layer = jnp.where(lanes[None, :] < slots, of_layer, layers * slots).reshape(-1)
    flat = ring.ring_write(
        rings.reshape(layers * slots, *rings.shape[3:]), new.reshape(-1, *new.shape[3:]),
        jnp.tile(positions, (layers, 1)), of_layer)
    return flat.reshape(rings.shape)


def _chunk_rows(params, c, axis, pages, num_blocks, state, layout, left, tokens, positions,
                block_tables, lanes):
    """The layers over the rows given, all at once, the pool (its
    ``_pool_pages`` views) and the rings read and not written: (hidden after
    the final norm, every layer's fresh K and V, the counters of this shard).
    ``models/trinity.py:_chunk_rows`` with the layers a ``lax.scan`` over the
    periods: the K and V returned are the DISPATCH's so far, ``[periods,
    layers a period, N, C, KVH, D]``."""
    n = tokens.shape[0]
    slots = state["k"].shape[2]
    lane = jnp.clip(lanes, 0, slots - 1)
    fed = (lanes < slots) & (positions[:, 0] >= 0)
    in_period = len(c.period)

    scale = c.head_dim ** -0.5
    block_size = pages["k"].shape[1]
    table_blocks = block_tables.shape[1]
    tile_blocks = history_tile(block_size, table_blocks) // block_size
    # where each row's cached history ends: at its lane's first row of the dispatch
    ends, _ = layout.rows(left.at, n)
    starts = ends[:, 0]
    history_len = jnp.clip(starts, 0, table_blocks * block_size)
    n_tiles = chunk_history_tiles(ends, block_size, table_blocks)
    tables = jnp.pad(block_tables, (
        (0, 0), (0, history_tiles_full(block_size, table_blocks) * tile_blocks - table_blocks)))
    n_trips = ring.ring_trips(jnp.where(fed, starts, 0), c.sliding_window)
    per_period, experts = _split(params)

    def period(through, xs):
        x, stats, pairs = through
        lp, ring_k, ring_v, left_k, left_v, p = xs
        fresh_k, fresh_v = [], []
        for j, kind in enumerate(c.period):
            window = c.sliding_window if kind == WINDOW else None

            def attend(q, k, v, j=j, kind=kind, window=window):
                if kind == FULL:
                    hist = chunk_history_partial(
                        c, q, pages, p * num_blocks + tables, history_len, n_tiles, positions,
                        scale, tile_blocks, block_size, jnp.float32)
                else:
                    hist = ring.chunk_ring_partial(
                        q, ring_k[j], ring_v[j], lane, starts, n_trips, positions, window, scale)
                part = _merge_partials(hist, _chunk_self_partial(c, q, k, v, positions, scale, window))
                k, v = (jax.lax.dynamic_update_slice_in_dim(all_rows[j], mine, left.at, 0)
                        for all_rows, mine in ((left_k, k), (left_v, v)))
                fresh_k.append(k)
                fresh_v.append(v)
                return ring.attended(chunk_rows_above_partial(
                    c, q, k, v, layout.positions, layout.lanes, left.at, layout.n_back, scale, part,
                    window))

            x, more, sent = _layer(jax.tree.map(lambda a: a[j], lp), experts, c, axis, kind,
                                   p * in_period + j, x, positions, attend)
            stats = stats + more
            pairs = pairs + jnp.stack([sent.max(), sent.sum()])
        return (x, stats, pairs), (jnp.stack(fresh_k), jnp.stack(fresh_v))

    (x, stats, pairs), (k, v) = jax.lax.scan(
        period,
        (_embed(params, c, tokens, axis), jnp.zeros((MOE_COUNTERS,), jnp.int32), jnp.zeros((2,), jnp.int32)),
        (per_period, state["k"], state["v"], left.k, left.v, jnp.arange(c.num_periods)))
    own = _swa_counts(c, fed, n_trips * ring.ring_tile(c.sliding_window) + RING_BLOCK,
                      positions[:, 0], jnp.clip(starts, 0))
    return (final_norm(params, c, x), k, v,
            _counted(c, axis, stats, pairs, own, n * tokens.shape[1]))


def decode(
    params: Params, config: MellumConfig, tokens: jax.Array, positions: jax.Array,
    kv_cache: KVCache, block_tables: jax.Array, state: SlotState, steps: int, max_pos: int,
    sample, carry, mesh=None,
):
    """``models/trinity.py:decode``'s contract: ``steps`` tokens of every slot
    (``tokens``, ``positions`` ``[S]``; position < 0 = the slot does not
    decode; a lane that passes ``max_pos`` stops there), the steps unrolled,
    pool and rings read-only inside the dispatch and written once after the
    steps. Returns (tokens, positions, carry, the stacked outputs, pool, state,
    counters ``[len(COUNTERS)]``). With ``mesh``: one ``shard_map``
    (:func:`_per_shard`); a step's logits are gathered whole on every shard,
    and ``sample`` (the engine's) runs on each alike."""
    program = partial(_decode, steps=steps, max_pos=max_pos, sample=sample)
    (toks, pos, carry, out), cache, new_state, counters = _per_shard(program, config, mesh, 4)(
        params, kv_cache, state, (tokens, positions, block_tables, carry))
    return toks, pos, carry, out, cache, new_state, counters


def _decode(params, kv_cache, state, host, *, c, axis, shards, steps, max_pos, sample):
    tokens, positions, block_tables, carry = host
    base = positions
    n_slots = tokens.shape[0]
    periods, n_win = state["k"].shape[:2]
    in_period = n_win + 1
    full_buffer = jnp.zeros((periods, n_slots, steps, c.num_kv_heads, c.head_dim), jnp.float32)
    ring_buffer = jnp.zeros((periods, n_win, n_slots, c.num_kv_heads, steps, c.head_dim), jnp.float32)
    scale = c.head_dim ** -0.5
    held = ring.held_positions(base, c.ring_positions)  # [S, P]
    in_cache = jnp.clip(base, 0)
    per_period, experts = _split(params)

    def run(history):
        live = history[1]

        def step(loop, k):
            toks, pos, carry, fk, fv, wk, wv, counters = loop
            pos2 = pos[:, None]
            in_buffer = (jnp.arange(steps)[None, :] <= k) & (base[:, None] >= 0)  # [S, W]
            sees_ring = ring.in_window(held, pos2, c.sliding_window)  # [S, 1, P], every window layer's
            sees_buffer = in_buffer[:, None, :] & (pos2 >= 0)[..., None]

            def period(through, xs):
                x, stats, pairs = through
                lp, ring_k, ring_v, fk, fv, wk, wv, live_k, live_v, p = xs
                wk, wv = list(wk), list(wv)
                for j, kind in enumerate(c.period):

                    def attend(q, kk, vv, j=j, kind=kind):
                        nonlocal fk, fv
                        if kind == FULL:
                            fk = jax.lax.dynamic_update_slice(fk, kk, (0, k, 0, 0))
                            fv = jax.lax.dynamic_update_slice(fv, vv, (0, k, 0, 0))
                            return _live_window_attention(
                                c, q, live, live_k, live_v, fk, fv, in_buffer, None)
                        wk[j] = jax.lax.dynamic_update_slice(wk[j], kk.transpose(0, 2, 1, 3), (0, 0, k, 0))
                        wv[j] = jax.lax.dynamic_update_slice(wv[j], vv.transpose(0, 2, 1, 3), (0, 0, k, 0))
                        return ring.attended(_merge_partials(
                            ring.masked_partial(q, ring_k[j], ring_v[j], sees_ring, scale),
                            ring.masked_partial(q, wk[j], wv[j], sees_buffer, scale)))

                    x, more, sent = _layer(jax.tree.map(lambda a: a[j], lp), experts, c, axis, kind,
                                           p * in_period + j, x, pos2, attend)
                    stats = stats + more
                    pairs = pairs + jnp.stack([sent.max(), sent.sum()])
                return (x, stats, pairs), (fk, fv, jnp.stack(wk), jnp.stack(wv))

            (x, stats, pairs), (fk, fv, wk, wv) = jax.lax.scan(
                period,
                (_embed(params, c, toks, axis)[:, None], jnp.zeros((MOE_COUNTERS,), jnp.int32),
                 jnp.zeros((2,), jnp.int32)),
                (per_period, state["k"], state["v"], fk, fv, wk, wv, live.k, live.v,
                 jnp.arange(periods)))
            nxt, carry, out = sample(_logits(params, c, final_norm(params, c, x), axis)[:, 0],
                                     pos, carry, k)
            own = _swa_counts(c, pos >= 0, c.ring_positions, pos, in_cache)
            counters = counters + _counted(c, axis, stats, pairs, own, n_slots)
            new_pos = jnp.where((pos >= 0) & (pos < max_pos), pos + 1, -1)
            return (nxt, new_pos, carry, fk, fv, wk, wv, counters), out

        loop = (tokens, positions, carry, full_buffer, full_buffer, ring_buffer, ring_buffer,
                jnp.zeros((len(COUNTERS),), jnp.int32))
        outs = []
        for k in range(steps):
            loop, out = step(loop, jnp.int32(k))
            outs.append(out)
        return loop, jax.tree.map(lambda *a: jnp.stack(a), *outs)

    (toks, pos, carry, fk, fv, wk, wv, counters), out = with_live_history(
        kv_cache, block_tables, base, run, out_dtype=jnp.float32)
    cache = flush_window(kv_cache, block_tables, base, fk, fv, max_pos)
    # the positions the steps wrote, as `flush_window` takes them: a lane that was padding or
    # ran past `max_pos` writes nowhere
    at = base[:, None] + jnp.arange(steps)[None, :]
    at = jnp.where((base[:, None] >= 0) & (at <= max_pos), at, -1)
    new_state = {name: _rings_written(state[name], new, at, jnp.arange(n_slots))
                 for name, new in (("k", wk), ("v", wv))}
    return (toks, pos, carry, out), cache, new_state, _over_shards(counters, axis, shards)
