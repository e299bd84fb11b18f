"""LFM2 expert decoder (``model_type: lfm2_moe``): gated short convolutions
beside a few rotary attention layers of 64-wide heads with a norm on every
head of q and k, a dense gated feed-forward after the first mixers and a
dropless expert layer after every other one, all of the model's experts held
here.

Three kinds of mixer-and-feed-forward pairs live in one model (conv + dense,
conv + experts, attention + experts), so the layers are a tuple of per-layer
trees walked in Python: no conditional on a layer's kind sits inside a loop,
and an expert layer's ``[X, E, F]`` matrices are handed to the grouped
product as they lie (sliced out of a stack inside a ``lax.scan``, the chip's
compiler would copy 1.2 GB a layer in front of the kernel). Two kinds of
state live side by side:

- the attention layers' pages: the Llama layout, ``{"k", "v"}``
  ``[L_attn, N, bs, KVH / 2, 2 * D]``, K normed and rotated before it is
  written; written, gathered and attended by ``models/llama.py``'s own
  functions (the chunk's history a tile at a time, a decode dispatch's through
  ``with_live_history``), exactly as ``models/jamba.py`` sends its pages, with
  the head norms and ``apply_rope`` in front. A head is 64 wide and the chip's
  lanes 128: a pool whose minor axis is 64 is padded to twice its bytes in HBM
  and copied whole into that layout by every dispatch (the chip's compiler, at
  the cell's shapes: PERF.md 6, PR 43). So TWO KV heads share a row of 128
  (:func:`_rows_of_heads`): to those functions the pool is 4 KV heads of 128,
  a query lies in its own head's half of the row with zeros in the other (the
  other head's keys then add exactly nothing to its scores), and of the values
  that come back its own half is kept. Paged, and it travels through
  ``kv/pages.py`` like any member.
- the convolution layers' state, PER SLOT (:class:`SlotState`, owned here): the
  last ``K - 1`` inputs of the depthwise convolution, oldest first, float32,
  ``[S, (K - 1) * E]`` a conv layer (a tuple: a layer's array is replaced
  whole, never updated inside a stack of them): 16 KB a slot and layer, and
  the whole of what a conv layer remembers. The chunk and decode programs read it and hand it
  back; a chunk row whose first position is 0 starts from zeros, which is how
  a slot is reset when a request is admitted to it; padding rows, padding
  positions and lanes that do not decode leave it untouched. Nothing outside
  this module indexes it, and ``pages.take`` / ``put`` never see it.

The weights are bfloat16 and the activations float32 from the embedding to
the head: the model routes, and noise on a router's input swaps the fourth and
fifth of 64 scores, after which another expert computes and ``logprob_rms``
reads 0.018-0.045 where it read 0.0016 (PERF.md 6, PR 36 and PR 43). So every
product that a later router sees takes its activation in THREE bfloat16 parts
(``ops/parts.py``: 24 bits, float32's own; ``PARTS``), the pages are float32
and attention's own products over them are taken at float32's precision
(``ATTENTION_PRECISION``); only the head, which no router follows, takes one
part. By part, on the chip at the cell's shape (my chip runs, PR 43): one part
everywhere 0.06-0.18 (4 probes); one part in the conv mixers' two projections
0.08-0.21, in the two dense feed-forwards 0.03-0.10, in the experts
0.015-0.045; bfloat16 pages, or float32 pages under the default precision,
0.002 in three probes and 0.045 in the fourth (one swap); TWO parts everywhere
0.0016-0.0017 in 39 probes and 0.018, 0.020, 0.039 in three (swaps: 16 bits
leave some near-tie in one prompt of fourteen open); three parts 0.0016-0.0017
in 42 of 42, all of it the head's one part (three parts there: under 0.00001).
A decode step streams weights and costs nearly the same in any of them
(17.7-18.3 ms); a chunk group of 8 rows 28 ms in one part, 36 in two, 44 in
three. The expert layer is ``ops/moe.py``'s (router, sort, three grouped
products), under the ``moe`` scope.
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
from dynamo_tpu.ops import moe
from dynamo_tpu.ops.parts import dot_parts, operand_parts

Params = Dict[str, Any]
KVCache = Dict[str, jax.Array]  # {"k", "v"}: [L_attn, N, bs, KVH, D]
SlotState = Dict[str, Tuple[jax.Array, ...]]  # {"conv": per conv layer [S, (K - 1) * E]}

# sums the step programs return, in this order (engine: /debug/engine): the six
# of ops/moe.py:dropless_experts, under the names models/kimi_linear.py gives
# them (a call is one expert layer over a decode step's lanes or over a group
# of a chunk's rows); convolution layers run (a group of a chunk's rows or a
# decode step each count their layers); rows that started a request; real rows
# of a chunk dispatch that took the convolutions' tails from the row above them
# (the rows of a dispatch less the lanes it fed)
COUNTERS = ("moe_layer_calls", "moe_held_rows", "moe_experts_hit", "moe_routed_pairs",
            "moe_rows_computed", "moe_expert_reads", "conv_layer_calls", "slot_state_resets",
            "conv_tail_handovers")
MOE_COUNTERS = COUNTERS.index("conv_layer_calls")  # the first: what dropless_experts counts
# A lane may fill several rows of one chunk dispatch with successive pieces of
# its prompt (engine_jax/engine.py:chunk_rows_of; docs/kv_cache_manager.md,
# "State per slot", says what a module with state per slot owes for it): under
# the full width a row whose lane is that of the row above it starts each
# convolution from that row's last inputs and attends its lane's earlier rows'
# fresh keys (`llama.ChunkLayout`), and a lane's last row alone leaves the slot its tail
LANE_TAKES_ROWS = True
# rows of a chunk computed at once: the rows are independent, and a chunk of
# more is taken in groups. 8 rows of 128 positions route 4,096 pairs, 64 rows
# an expert, which fills the grouped product's longest tile; more at once only
# adds temporaries beside 10.5 GB of weights
ROWS_AT_ONCE = 8
# bfloat16 parts of the float32 activation in every product against a weight
# that a later router sees (ops/parts.py: three carry float32's 24 bits); the
# head takes one
PARTS = 3
_expert_parts = partial(operand_parts, parts=PARTS)  # ops/moe.py:dropless_experts' ``parts_of``
# the pages are float32 under any weights, and attention's own products over
# them are taken at float32's precision: models/llama.py's einsums name none,
# so they take the one in force where they are traced (it moves float32
# operands only)
ATTENTION_PRECISION = "highest"
# what the published code adds to the sum of the chosen scores before it divides
ROUTER_EPS = 1e-6


@dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776  # the dense feed-forward of the first layers
    num_layers: int = 40
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    # "conv" or "full_attention" for each layer, as published
    layer_types: Tuple[str, ...] = ()
    num_dense_layers: int = 2
    conv_kernel: int = 3  # conv_L_cache
    moe_intermediate_size: int = 1536
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    tie_embeddings: bool = True
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if len(self.layer_types) != self.num_layers or set(self.layer_types) - {"conv", "full_attention"}:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers of kinds "
                f"{sorted(set(self.layer_types))}: {self.num_layers} of 'conv' / 'full_attention' wanted")

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def heads_a_row(self) -> int:
        """KV heads side by side in one row of a page: as many as fill the
        chip's 128 lanes (1 for a head that fills them itself)."""
        return math.gcd(self.num_kv_heads, max(1, 128 // self.head_dim))


def is_expert_layer(c: Lfm2Config, layer: int) -> bool:
    """The first ``num_dense_layers`` feed-forwards are dense, the rest experts."""
    return layer >= c.num_dense_layers


# -- parameters ---------------------------------------------------------------

def init_params(rng: jax.Array, config: Lfm2Config) -> Params:
    """Random init with fan-in scaling; the router and the convolution's taps
    float32; the selection bias (published as a trained buffer) small seeded
    values, so that it moves some choices."""
    c = config
    e = c.hidden_size

    def dense(key, shape, fan_in, dtype=None):
        w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
        return w.astype(dtype or c.dtype)

    def conv(key):
        k = jax.random.split(key, 3)
        return {"w_in": dense(k[0], (e, 3 * e), e),
                "conv_w": dense(k[1], (c.conv_kernel, e), c.conv_kernel, jnp.float32),
                "w_out": dense(k[2], (e, e), e)}

    def attn(key):
        k = jax.random.split(key, 4)
        return {"wq": dense(k[0], (e, c.q_dim), e), "wk": dense(k[1], (e, c.kv_dim), e),
                "wv": dense(k[2], (e, c.kv_dim), e), "wo": dense(k[3], (c.q_dim, e), c.q_dim),
                "q_norm": jnp.ones((c.head_dim,), jnp.float32),
                "k_norm": jnp.ones((c.head_dim,), jnp.float32)}

    def ffn(key, experts: bool):
        k = jax.random.split(key, 5)
        if not experts:
            f = c.intermediate_size
            return {"w_gate": dense(k[0], (e, f), e), "w_up": dense(k[1], (e, f), e),
                    "w_down": dense(k[2], (f, e), f)}
        x, f = c.num_experts, c.moe_intermediate_size
        return {"router": dense(k[0], (e, x), e, jnp.float32),
                "router_bias": 0.02 * jax.random.normal(k[1], (x,), jnp.float32),
                "w_gate": dense(k[2], (x, e, f), e), "w_up": dense(k[3], (x, e, f), e),
                "w_down": dense(k[4], (x, f, e), f)}

    layers = []
    for i, kind in enumerate(c.layer_types):
        key = jax.random.fold_in(rng, i)
        layers.append({
            "operator_norm": jnp.ones((e,), jnp.float32),
            "ffn_norm": jnp.ones((e,), jnp.float32),
            **(conv if kind == "conv" else attn)(jax.random.fold_in(key, 0)),
            **ffn(jax.random.fold_in(key, 1), is_expert_layer(c, i)),
        })
    params = {
        "embed": dense(jax.random.fold_in(rng, 1000), (c.vocab_size, e), e),
        "final_norm": jnp.ones((e,), jnp.float32),
        "layers": tuple(layers),
    }
    if not c.tie_embeddings:
        params["lm_head"] = dense(jax.random.fold_in(rng, 1001), (e, c.vocab_size), e)
    return params


def param_shardings(config: Lfm2Config, mesh):
    raise NotImplementedError(
        "lfm2 runs on one device: experts over the chips of a host are "
        "ROADMAP M1's remainder"
    )


# -- the two kinds of state ---------------------------------------------------

def make_kv_cache(
    config: Lfm2Config, num_blocks: int, block_size: int, dtype: Any = None,
    quantized: bool = False,
) -> KVCache:
    """The attention layers' page pool, in the Llama layout."""
    if quantized:
        raise ValueError("lfm2 has no int8 page layout")
    c = config
    shape = (c.layer_types.count("full_attention"), num_blocks, block_size,
             *_rows_of_heads(c)[1:])
    return {"k": jnp.zeros(shape, dtype or jnp.float32), "v": jnp.zeros(shape, dtype or jnp.float32)}


def make_slot_state(config: Lfm2Config, slots: int) -> SlotState:
    """The convolution layers' state of every slot, zeroed: the ``K - 1`` last
    inputs of the depthwise convolution, oldest first, side by side along the
    minor axis."""
    c = config
    return {"conv": tuple(jnp.zeros((slots, (c.conv_kernel - 1) * c.hidden_size), jnp.float32)
                          for _ in range(c.layer_types.count("conv")))}


# -- the layers ---------------------------------------------------------------

def lm_head(params: Params, config: Lfm2Config, h: jax.Array) -> jax.Array:
    """Final hidden states to float32 logits (the head is the embedding
    table where it is tied)."""
    return dot_parts(h, params["embed"].T if config.tie_embeddings else params["lm_head"])


def conv_mixer(lp: Params, c: Lfm2Config, u: jax.Array, valid: jax.Array, tail: jax.Array,
               above=None):
    """The gated short convolution over ``[B, T, E]`` normed inputs whose
    valid tokens are a prefix of each row, from the convolution's tail
    ``[B, (K - 1) * E]``: ``[B, C, x] = u W_in``; ``g = B * x``; a causal
    depthwise convolution of ``g`` (no bias, no activation); ``y = C * conv``;
    out ``y W_out``. Returns (output ``[B, T, E]``, the new tail: the row's
    last ``K - 1`` valid values of ``g``).

    ``above`` = (``takes`` ``[B]``, a tail ``[(K - 1) * E]``): a row that
    ``takes`` goes on where the row above it ends, a FULL row of the same
    sequence, so it starts from that row's last ``K - 1`` values of ``g``
    (row 0 from the tail given) and not from ``tail``. ``g`` is the row's own
    inputs' alone, so the rows are still computed all at once."""
    bsz, t, e = u.shape
    kk = c.conv_kernel
    bcx = dot_parts(u, lp["w_in"], PARTS)
    g = bcx[..., :e] * bcx[..., 2 * e:]
    if above is not None:
        takes, first = above
        if t < kk - 1:
            raise ValueError(f"a row of {t} tokens holds no tail of {kk - 1}")
        ends = jnp.concatenate([first[None], g[:-1, t - (kk - 1):].reshape(bsz - 1, (kk - 1) * e)])
        tail = jnp.where(takes[:, None], ends, tail)
    # tap K-1 is the token itself, tap 0 the oldest input
    seq = jnp.concatenate([tail.reshape(bsz, kk - 1, e), g], axis=1)  # [B, K-1+T, E]
    y = bcx[..., e:2 * e] * sum(seq[:, j:j + t] * lp["conv_w"][j] for j in range(kk))
    tail_at = valid.sum(axis=1)[:, None] + jnp.arange(kk - 1)[None, :]  # the K-1 inputs before position n
    new_tail = jnp.take_along_axis(seq, tail_at[:, :, None], axis=1).reshape(bsz, -1)
    return dot_parts(y, lp["w_out"], PARTS), new_tail


class _Rows(NamedTuple):
    """What ``models/llama.py``'s page functions read of a config."""

    num_heads: int
    num_kv_heads: int
    head_dim: int


def _rows_of_heads(c: Lfm2Config) -> _Rows:
    """The attention layers as the pages hold them: ``heads_a_row`` KV heads
    side by side in one row."""
    return _Rows(c.num_heads, c.num_kv_heads // c.heads_a_row, c.heads_a_row * c.head_dim)


def _own_half(c: Lfm2Config, a: jax.Array) -> jax.Array:
    """``a`` ``[B, T, H, n * D]`` seen as (row, head of the row, query of the
    head, half of the row, D) with every half but the head's own zeroed."""
    b, t = a.shape[:2]
    n, r = c.heads_a_row, _rows_of_heads(c)
    a = a.reshape(b, t, r.num_kv_heads, n, c.num_heads // c.num_kv_heads, -1, c.head_dim)
    return a * jnp.eye(n, dtype=a.dtype)[:, None, :, None]


def _project_qkv(lp: Params, c: Lfm2Config, x: jax.Array, positions: jax.Array, dtype: Any):
    """q, k, v of normed inputs ``[B, T, E]``, split into heads, q and k
    normed over each head's ``D`` and THEN rotated, all three in the pages'
    ``dtype`` and as the pages hold heads (attention's own arithmetic is
    ``models/llama.py``'s, over rows of ``heads_a_row`` heads): k and v a row
    of KV heads side by side, q in its KV head's part of the row with zeros
    beside it, times ``sqrt(heads_a_row)`` (those functions scale a score by
    the ROW's width). No bias."""
    b, t, _ = x.shape
    q = dot_parts(x, lp["wq"], PARTS).reshape(b, t, c.num_heads, c.head_dim)
    k = dot_parts(x, lp["wk"], PARTS).reshape(b, t, c.num_kv_heads, c.head_dim)
    v = dot_parts(x, lp["wv"], PARTS)
    q = apply_rope(rms_norm(q, lp["q_norm"], c.norm_eps), positions, c.rope_theta)
    k = apply_rope(rms_norm(k, lp["k_norm"], c.norm_eps), positions, c.rope_theta)
    r = _rows_of_heads(c)
    q = _own_half(c, jnp.tile(q * math.sqrt(c.heads_a_row), (1, 1, 1, c.heads_a_row)))
    return (q.reshape(b, t, r.num_heads, r.head_dim).astype(dtype),
            k.reshape(b, t, r.num_kv_heads, r.head_dim).astype(dtype),
            v.reshape(b, t, r.num_kv_heads, r.head_dim).astype(dtype))


def _attended(lp: Params, c: Lfm2Config, attn: jax.Array) -> jax.Array:
    """The out-projection of attention's values ``[B, T, H, heads_a_row * D]``:
    each head's own part of its row, the heads side by side."""
    b, t = attn.shape[:2]
    own = _own_half(c, attn.astype(jnp.float32)).sum(axis=5)
    return dot_parts(own.reshape(b, t, c.q_dim), lp["wo"], PARTS)


def feed_forward(lp: Params, c: Lfm2Config, layer: int, h: jax.Array, valid: jax.Array):
    """(``h + FF(RMSNorm(h))`` ``[B, T, E]``, the expert layer's counters: the
    first ``MOE_COUNTERS`` of ``COUNTERS``; a dense layer counts nothing)."""
    x = rms_norm(h, lp["ffn_norm"], c.norm_eps)
    if not is_expert_layer(c, layer):
        with jax.named_scope("mlp"):
            gate = jax.nn.silu(dot_parts(x, lp["w_gate"], PARTS))
            y = dot_parts(gate * dot_parts(x, lp["w_up"], PARTS), lp["w_down"], PARTS)
        return h + y, jnp.zeros((MOE_COUNTERS,), jnp.int32)
    with jax.named_scope("moe"):
        b, t, e = x.shape
        flat = x.reshape(b * t, e)
        bias = lp["router_bias"] if c.use_expert_bias else jnp.zeros_like(lp["router_bias"])
        ids, weights = moe.route_sigmoid_topk(
            flat, lp["router"], bias, c.num_experts_per_tok, c.routed_scaling_factor,
            c.norm_topk_prob, ROUTER_EPS)
        y, stats = moe.dropless_experts(
            flat, ids, weights, lp["w_gate"], lp["w_up"], lp["w_down"],
            token_valid=valid.reshape(-1), parts_of=_expert_parts)
    return h + y.reshape(b, t, e), stats


# -- the step programs --------------------------------------------------------

class _Left(NamedTuple):
    """What the groups of such a dispatch so far leave the next one: all that
    the loop over the groups carries from group to group."""

    at: jax.Array  # the dispatch's row that is the next group's first
    tails: jax.Array  # [L_conv, (K - 1) * E] the tails the row above that one left
    k: jax.Array  # [L_attn, N, C, KVH, D] the dispatch's fresh keys so far, zeros from `at` on
    v: jax.Array


def forward_chunk(
    params: Params, config: Lfm2Config, tokens: jax.Array, positions: jax.Array,
    kv_cache: KVCache, block_tables: jax.Array, state: SlotState, lanes: jax.Array,
):
    """A ``[R, C]`` block of prompt tokens (``lanes`` ``[R]``: the row's slot;
    ``max_slots`` and above = a padding row), valid tokens (position >= 0) a
    prefix of each row. Under the full width (``R`` < the state's slots) a lane
    may fill several CONSECUTIVE rows with successive pieces of its prompt, in
    order, each full but the last; at it, one row a lane.

    Returns (hidden ``[R, C, E]`` after the final norm, the pool with the
    rows' K and V written, the slot state with the rows' slots advanced, the
    counters ``[len(COUNTERS)]``). A row whose first position is 0 starts from
    a zeroed tail: a slot is reset by the first chunk of the request admitted
    to it. More than ``ROWS_AT_ONCE`` rows are taken in groups of that many,
    one after another; the pool and the state are only read inside the loop
    (a row touches its own slot and pages only), and what the rows made is
    written after it: one scatter a pool array and one a conv layer.

    A row whose lane is that of the row above it (both real) goes on where
    that row ends, inside the program (``llama.ChunkLayout``; the loop over the groups
    carries what a later group needs of the earlier ones, ``_Left``, and no
    more): its convolutions start from that row's last inputs and not from
    the slot's tail (:func:`conv_mixer`), its pool history ends where its
    lane's FIRST row of the dispatch starts, and one more partial attends the
    fresh keys of its lane's rows above it (``chunk_rows_above_partial``).
    Only a lane's LAST row leaves the slot its tail. Where every lane has one
    row nothing is taken from a row above and the sibling loop makes no trip;
    at the full width none of it is in the program."""
    from dynamo_tpu.ops.attention import write_kv_to_pool

    c = config
    rows = tokens.shape[0]
    slots = state["conv"][0].shape[0]
    pages = _pool_pages(kv_cache)
    num_blocks = kv_cache["k"].shape[1]
    layout = left = None
    if rows < slots:  # at the full width a lane has one row, and the program is what it was
        layout = chunk_layout(positions, lanes, slots)
        none_yet = jnp.zeros((c.layer_types.count("full_attention"), *tokens.shape,
                              *_rows_of_heads(c)[1:]), kv_cache["k"].dtype)
        left = _Left(
            at=jnp.int32(0), k=none_yet, v=none_yet,
            tails=jnp.zeros((len(state["conv"]), state["conv"][0].shape[1]), jnp.float32))
    group = partial(_chunk_rows, params, c, pages, num_blocks, state["conv"], layout)
    if rows <= ROWS_AT_ONCE:
        h, k, v, tails, counters = group(left, tokens, positions, block_tables, lanes)
    else:
        if rows % ROWS_AT_ONCE:
            raise ValueError(f"{rows} rows are no whole number of groups of {ROWS_AT_ONCE}")

        def grouped(a):
            return a.reshape(rows // ROWS_AT_ONCE, ROWS_AT_ONCE, *a.shape[1:])

        def step(carry, xs):
            sums, left = carry
            h, k, v, tails, more = group(left, *xs)
            if left is None:
                return (sums + more, None), (h, tails, k, v)
            # the dispatch's K and V so far and the last row's tails go on to the next group
            return (sums + more, _Left(left.at + ROWS_AT_ONCE, tails[:, -1], k, v)), (h, tails)

        (counters, left), (h, tails, *kv) = jax.lax.scan(
            step, (jnp.zeros((len(COUNTERS),), jnp.int32), left),
            (grouped(tokens), grouped(positions), grouped(block_tables), grouped(lanes)))
        h = h.reshape(rows, *h.shape[2:])
        # [G, L, R, ...] -> [L, G * R, ...]
        tails, *kv = (jnp.moveaxis(a, 0, 1).reshape(a.shape[1], rows, *a.shape[3:])
                      for a in (tails, *kv))
        k, v = kv or (left.k, left.v)
    cache = {"k": write_kv_to_pool(kv_cache["k"], k, positions, block_tables),
             "v": write_kv_to_pool(kv_cache["v"], v, positions, block_tables)}
    # a padding row writes nowhere: its slot index lies past the state
    back = jnp.where(lanes < slots, lanes, slots)
    if layout is not None:
        # nor does a row that the row under it goes on from: one write a slot, its lane's last row's
        back = jnp.where(jnp.concatenate([layout.takes[1:], jnp.zeros((1,), bool)]), slots, back)
    conv = tuple(was.at[back].set(tail, mode="drop") for was, tail in zip(state["conv"], tails))
    return h, cache, {"conv": conv}, counters


def _chunk_rows(params, c, pages, num_blocks, conv, layout, left, tokens, positions, block_tables,
                lanes):
    """The layers over the rows given, all at once, the pool (its
    ``_pool_pages`` views) and the slots' tails ``conv`` read and not written:
    (hidden after the final norm, the attention layers' fresh K and V
    ``[L_attn, R, C, KVH, D]``, the conv layers' new tails ``[L_conv, R, (K -
    1) * E]``, the counters). With a ``layout`` the rows are ``left.at``
    onwards of a dispatch in which a lane may fill several, and the K and V
    returned are the DISPATCH's so far, ``[L_attn, N, C, KVH, D]``."""
    valid = positions >= 0
    fresh = positions[:, 0] == 0
    slots = conv[0].shape[0]
    lane = jnp.clip(lanes, 0, slots - 1)
    real = lanes < slots
    n = tokens.shape[0]

    dtype = pages["k"].dtype
    r = _rows_of_heads(c)
    scale = r.head_dim ** -0.5
    block_size = pages["k"].shape[1]
    table_blocks = block_tables.shape[1]
    tile_blocks = history_tile(block_size, table_blocks) // block_size
    # positions whose first says where each row's pool history ends: the row's own, or
    # with rows above those of its lane's first row (the rows between: their keys in hand)
    ends, takes = (positions, None) if layout is None else layout.rows(left.at, n)
    history_len = jnp.clip(ends[:, 0], 0, table_blocks * block_size)
    n_tiles = chunk_history_tiles(ends, block_size, table_blocks)
    tables = jnp.pad(block_tables, (
        (0, 0), (0, history_tiles_full(block_size, table_blocks) * tile_blocks - table_blocks)))

    h = embed_lookup(params, tokens, c.dtype).astype(jnp.float32)
    tails, fresh_k, fresh_v = [], [], []
    stats = jnp.zeros((MOE_COUNTERS,), jnp.int32)
    for i, kind in enumerate(c.layer_types):
        lp = params["layers"][i]
        u = rms_norm(h, lp["operator_norm"], c.norm_eps)
        if kind == "conv":
            with jax.named_scope("conv"):
                tail = jnp.where(fresh[:, None], 0.0, conv[len(tails)][lane])
                y, tail = conv_mixer(
                    lp, c, u, valid, tail,
                    None if layout is None else (takes, left.tails[len(tails)]))
                tails.append(tail)
        else:
            with jax.named_scope("attn"):
                j = len(fresh_k)
                q, k, v = _project_qkv(lp, c, u, positions, dtype)
                with jax.default_matmul_precision(ATTENTION_PRECISION):
                    hist = chunk_history_partial(
                        r, q, pages, j * num_blocks + tables, history_len, n_tiles, positions,
                        scale, tile_blocks, block_size, dtype)
                    part = _merge_partials(
                        hist, _chunk_self_partial(r, q, k, v, positions, scale))
                    if layout is not None:
                        k, v = (jax.lax.dynamic_update_slice_in_dim(all_rows[j], mine, left.at, 0)
                                for all_rows, mine in ((left.k, k), (left.v, v)))
                        part = chunk_rows_above_partial(
                            r, q, k, v, layout.positions, layout.lanes, left.at, layout.n_back,
                            scale, part)
                    num, _, den = part
                y = _attended(lp, c, jnp.where(
                    (den > 0.0).transpose(0, 2, 1)[..., None],
                    num / jnp.maximum(den, 1e-30).transpose(0, 2, 1)[..., None], 0.0))
                fresh_k.append(k)
                fresh_v.append(v)
        h, more = feed_forward(lp, c, i, h + y, valid)
        stats = stats + more
    h = rms_norm(h, params["final_norm"], c.norm_eps)
    counters = jnp.concatenate([stats, jnp.stack([
        jnp.int32(len(tails)), jnp.sum(fresh & real),
        jnp.int32(0) if layout is None else jnp.sum(takes)]).astype(jnp.int32)])
    return h, jnp.stack(fresh_k), jnp.stack(fresh_v), jnp.stack(tails), counters


def decode(
    params: Params, config: Lfm2Config, tokens: jax.Array, positions: jax.Array,
    kv_cache: KVCache, block_tables: jax.Array, state: SlotState, steps: int, max_pos: int,
    sample, carry,
):
    """``steps`` tokens of every slot (``tokens``, ``positions`` ``[S]``;
    position < 0 = the slot does not decode, and its tail stays as it is; a
    lane that passes ``max_pos`` stops there).

    The ``steps`` (a handful) are unrolled, so that one step's tails are the
    next step's and no buffer is copied. The attention layers are the dense
    tier of the Llama decode program, as ``models/jamba.py`` has it: the pool
    is read-only inside the dispatch, its live (lane, tile) pairs gathered once
    (``with_live_history``), a step's K and V go to a window buffer and the
    pool takes the window after the steps in one scatter a pool array
    (``flush_window``). ``sample(logits [S, V], positions, carry, k) -> (next
    tokens [S], carry, outputs)`` is the engine's. Returns (tokens, positions,
    carry, the stacked outputs, pool, state, counters ``[len(COUNTERS)]``)."""
    c = config
    base = positions
    n_slots = tokens.shape[0]
    dtype = kv_cache["k"].dtype
    r = _rows_of_heads(c)
    window = jnp.zeros((n_slots, steps, r.num_kv_heads, r.head_dim), dtype)
    n_attn = c.layer_types.count("full_attention")
    n_conv = c.num_layers - n_attn

    def run(history):
        live = history[1]

        def step(loop, k):
            toks, pos, carry, conv, wk, wv, stats = loop
            valid = (pos >= 0)[:, None]
            in_window = (jnp.arange(steps)[None, :] <= k) & (base[:, None] >= 0)  # [S, W]
            conv, wk, wv = list(conv), list(wk), list(wv)
            h = embed_lookup(params, toks, c.dtype).astype(jnp.float32)[:, None]  # [S, 1, E]
            i_conv = j = 0
            for i, kind in enumerate(c.layer_types):
                lp = params["layers"][i]
                u = rms_norm(h, lp["operator_norm"], c.norm_eps)
                if kind == "conv":
                    with jax.named_scope("conv"):
                        y, conv[i_conv] = conv_mixer(lp, c, u, valid, conv[i_conv])
                    i_conv += 1
                else:
                    with jax.named_scope("attn"):
                        q, kk, vv = _project_qkv(lp, c, u, pos[:, None], dtype)
                        wk[j] = jax.lax.dynamic_update_slice(wk[j], kk, (0, k, 0, 0))
                        wv[j] = jax.lax.dynamic_update_slice(wv[j], vv, (0, k, 0, 0))
                        with jax.default_matmul_precision(ATTENTION_PRECISION):
                            attn = _live_window_attention(
                                r, q, live, live.k[j], live.v[j], wk[j], wv[j], in_window, None)
                        y = _attended(lp, c, attn)
                    j += 1
                h, more = feed_forward(lp, c, i, h + y, valid)
                stats = stats + more
            h = rms_norm(h, params["final_norm"], c.norm_eps)
            nxt, carry, out = sample(lm_head(params, c, h)[:, 0], pos, carry, k)
            new_pos = jnp.where((pos >= 0) & (pos < max_pos), pos + 1, -1)
            return (nxt, new_pos, carry, tuple(conv), tuple(wk), tuple(wv), stats), out

        loop = (tokens, positions, carry, state["conv"], (window,) * n_attn, (window,) * n_attn,
                jnp.zeros((MOE_COUNTERS,), jnp.int32))
        outs = []
        for k in range(steps):
            loop, out = step(loop, jnp.int32(k))
            outs.append(out)
        return loop, jax.tree.map(lambda *a: jnp.stack(a), *outs)

    (toks, pos, carry, conv, wk, wv, stats), out = with_live_history(
        kv_cache, block_tables, base, run, out_dtype=dtype)
    cache = flush_window(kv_cache, block_tables, base, jnp.stack(wk), jnp.stack(wv), max_pos)
    counters = jnp.concatenate([stats, jnp.asarray([steps * n_conv, 0, 0], jnp.int32)])
    return toks, pos, carry, out, cache, {"conv": conv}, counters
