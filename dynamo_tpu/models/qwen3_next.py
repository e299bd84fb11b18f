"""Qwen3-Next decoder (``model_type: qwen3_next``): Gated DeltaNet layers (the
delta rule with ONE decay a head) beside gated softmax attention of 256-wide
heads rotated in their first quarter, and after EVERY mixer a dropless expert
layer behind a softmax router with a gated shared expert beside it.

Two kinds of layer live in one model (DeltaNet + experts, attention +
experts), so the layers are a tuple of per-layer trees walked in Python: no
conditional on a layer's kind sits inside a loop, and an expert layer's ``[X,
E, F]`` matrices are handed to the grouped product as they lie. (Each kind's
body under its own ``jax.jit``, traced once a shape, was built first: the
chip's compiler then scheduled the chunk program so that it never ended on one
prompt in five, the same mathematics unrolled in Python ends on all: PERF.md 6,
PR 48.) Two kinds of state live side by side:

- the attention layers' pages: the Llama layout, ``{"k", "v"}`` ``[L_attn, N,
  bs, KVH, 256]``, K normed and rotated before it is written; written,
  gathered and attended by ``models/llama.py``'s own functions (the chunk's
  history a tile at a time, a decode dispatch's through
  ``with_live_history``), as ``models/jamba.py`` and ``models/lfm2.py`` send
  theirs. A head is two registers' lanes wide: nothing is packed or padded.
- the DeltaNet layers' state, PER SLOT (:class:`SlotState`, owned here): a
  float32 ``[S, H_v, d_k, d_v]`` matrix and the last ``K - 1`` inputs of the
  one short convolution over q, k and v, ``[S, (K - 1) * (2 H_k d_k + H_v
  d_v)]``, one array of each a DeltaNet layer (a tuple: a layer's array is
  replaced whole). The chunk and decode programs read it and hand it back; a
  chunk row whose first position is 0 starts from zeros, which is how a slot is
  reset when a request is admitted to it; padding rows, padding positions and
  lanes that do not decode leave it untouched. Nothing outside this module
  indexes it, and ``pages.take`` / ``put`` never see it. Under the full width
  a lane may fill several rows of a chunk dispatch (``LANE_TAKES_ROWS``): a
  later row goes on from the row above it (the state inside the kernel, the
  convolution from that row's last inputs, attention over that row's fresh
  keys), and the slot is written once (:func:`forward_chunk`).

The recurrence is Kimi-Linear's kernel, as it is: Gated DeltaNet IS
``ops/pallas/kda_scan.py``'s delta rule with the head's one log-decay spread
over its key channels (``S = exp(g) S``; ``u = (v - S^T k) beta``; ``S += k
u^T``; ``o = S^T q``), each key head's q and k serving ``H_v / H_k``
consecutive value heads. A chunk is one call of ``kda_scan`` a layer, a decode
step's one token ``kda_step``, the same operations in ``jax.numpy``: the shape
picks the path.

The weights are bfloat16 and the activations float32 from the embedding to
the head: the model routes 10 of 512, and noise on a router's input swaps the
tenth and the eleventh score, after which another expert computes (PERF.md 6,
PR 36, PR 43 and PR 48). So every product that a later router sees takes its
activation in ``PARTS`` bfloat16 parts (``ops/parts.py``), the pages are
float32 and attention's own products over them are taken at float32's
precision (``ATTENTION_PRECISION``); only the head, which no router follows,
takes one part. The readings by part are in
``benchmark/configs/qwen3-next-80b-a3b.json`` (``assumed``) and PERF.md 6.
The expert layer is ``ops/moe.py``'s (router, sort, three grouped products),
under the ``moe`` scope, the shared expert under ``moe/shared``.
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
from dynamo_tpu.ops.pallas.kda_scan import kda_scan, kda_step
from dynamo_tpu.ops.parts import dot_parts, operand_parts

Params = Dict[str, Any]
KVCache = Dict[str, jax.Array]  # {"k", "v"}: [L_attn, N, bs, KVH, D]
SlotState = Dict[str, Tuple[jax.Array, ...]]  # {"s", "conv"}: one array a DeltaNet layer

# sums the step programs return, in this order (engine: /debug/engine): the six
# of ops/moe.py:dropless_experts, under the names models/kimi_linear.py gives
# them (a call is one expert layer over a decode step's lanes or over a group
# of a chunk's rows); what the chunks' kernel advanced, as Kimi's ``kda_*``:
# valid tokens, and lanes of a group with one (each a read and a write of a
# sequence's state), summed over the DeltaNet layers; rows that started a
# request; rows that took their state from the row above them
COUNTERS = ("moe_layer_calls", "moe_held_rows", "moe_experts_hit", "moe_routed_pairs",
            "moe_rows_computed", "moe_expert_reads",
            "gdn_chunk_tokens", "gdn_state_passes", "slot_state_resets", "gdn_state_handovers")
# a lane may fill several rows of a chunk dispatch under the full width (engine.py:chunk_rows_of):
# a row whose lane is that of the row above it goes on where that row ends (`forward_chunk`)
LANE_TAKES_ROWS = True
MOE_COUNTERS = COUNTERS.index("gdn_chunk_tokens")  # the first: what dropless_experts counts
# rows of a chunk computed at once: the rows are independent, and a chunk of
# more is taken in groups. 8 rows of 128 positions route 10,240 pairs, 20 rows
# an expert held of 512; more at once only adds temporaries beside the weights
ROWS_AT_ONCE = 8
# bfloat16 parts of the float32 activation in every product against a weight
# that a later router sees (ops/parts.py: three carry float32's 24 bits); the
# head takes one
PARTS = 3
_expert_parts = partial(operand_parts, parts=PARTS)  # ops/moe.py:dropless_experts' ``parts_of``
# the pages are float32 under any weights, and attention's own products over
# them are taken at float32's precision: models/llama.py's einsums name none,
# so they take the one in force where they are traced
ATTENTION_PRECISION = "highest"
# the two DeltaNet epsilons of the published kernels: q and k are divided by
# sqrt(sum x^2 + 1e-6)
L2_EPS = 1e-6

KINDS = ("linear_attention", "full_attention")  # the published ``layer_types`` names


@dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    # "linear_attention" or "full_attention" for each layer (``layer_kinds``
    # makes them from ``full_attention_interval`` where a card has no list)
    layer_types: Tuple[str, ...] = ()
    # gated attention
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    # Gated DeltaNet
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    # experts, every layer
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512  # held here, ids first_expert ...
    num_experts_published: int = 512  # the router's width
    first_expert: int = 0
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if len(self.layer_types) != self.num_layers or set(self.layer_types) - set(KINDS):
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers of kinds "
                f"{sorted(set(self.layer_types))}: {self.num_layers} of {KINDS} wanted")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("a key head serves a whole number of value heads")
        if self.linear_key_head_dim != self.linear_value_head_dim:
            raise ValueError("ops/pallas/kda_scan.py keeps a square state: d_k = d_v")

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of the one short convolution: q, k and v side by side."""
        return 2 * self.key_dim + self.value_dim


def layer_kinds(num_layers: int, full_attention_interval: int) -> Tuple[str, ...]:
    """Layer ``i`` is full attention where ``(i + 1) % interval == 0``."""
    return tuple(KINDS[(i + 1) % full_attention_interval == 0] for i in range(num_layers))


# -- parameters ---------------------------------------------------------------

def init_params(rng: jax.Array, config: Qwen3NextConfig) -> Params:
    """Random init with fan-in scaling; the router, the convolution's taps,
    ``a_log``, ``dt_bias`` and the norms float32. The norms of the stream and
    of q and k are ZERO-centred (``x * (1 + w)``) and the router is small, so
    both are normal x 0.02; the norm inside DeltaNet is plain, ones. ``a_log =
    log U(0, 16)`` and ``dt_bias = 1``, as the published initialiser has
    them."""
    c = config
    e = c.hidden_size

    def dense(key, shape, fan_in, dtype=None):
        w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
        return w.astype(dtype or c.dtype)

    def small(key, shape):
        return 0.02 * jax.random.normal(key, shape, jnp.float32)

    def gdn(key):
        k = jax.random.split(key, 5)
        hv = c.linear_num_value_heads
        return {"w_qkvz": dense(k[0], (e, c.conv_dim + c.value_dim), e),  # q | k | v | z
                "w_ba": dense(k[1], (e, 2 * hv), e),  # b | a
                "conv_w": dense(k[2], (c.linear_conv_kernel_dim, c.conv_dim),
                                c.linear_conv_kernel_dim, jnp.float32),
                "a_log": jnp.log(jax.random.uniform(k[3], (hv,), jnp.float32, 0.0, 16.0)),
                "dt_bias": jnp.ones((hv,), jnp.float32),
                "o_norm": jnp.ones((c.linear_value_head_dim,), jnp.float32),
                "wo": dense(k[4], (c.value_dim, e), c.value_dim)}

    def attn(key):
        k = jax.random.split(key, 6)
        q, kv = c.num_heads * c.head_dim, c.num_kv_heads * c.head_dim
        return {"wq": dense(k[0], (e, 2 * q), e),  # [q | gate] a head
                "wk": dense(k[1], (e, kv), e), "wv": dense(k[2], (e, kv), e),
                "wo": dense(k[3], (q, e), q),
                "q_norm": small(k[4], (c.head_dim,)), "k_norm": small(k[5], (c.head_dim,))}

    def ffn(key):
        k = jax.random.split(key, 8)
        x, f, fs = c.num_experts, c.moe_intermediate_size, c.shared_expert_intermediate_size
        return {"router": small(k[0], (e, c.num_experts_published)),
                "w_gate": dense(k[1], (x, e, f), e), "w_up": dense(k[2], (x, e, f), e),
                "w_down": dense(k[3], (x, f, e), f),
                "ws_gate": dense(k[4], (e, fs), e), "ws_up": dense(k[5], (e, fs), e),
                "ws_down": dense(k[6], (fs, e), fs),
                "shared_gate": dense(k[7], (e,), e)}

    layers = []
    for i, kind in enumerate(c.layer_types):
        key = jax.random.fold_in(rng, i)
        layers.append({
            "mixer_norm": small(jax.random.fold_in(key, 2), (e,)),
            "ffn_norm": small(jax.random.fold_in(key, 3), (e,)),
            **(gdn if kind == "linear_attention" else attn)(jax.random.fold_in(key, 0)),
            **ffn(jax.random.fold_in(key, 1)),
        })
    params = {
        "embed": dense(jax.random.fold_in(rng, 1000), (c.vocab_size, e), e),
        "final_norm": small(jax.random.fold_in(rng, 1002), (e,)),
        "layers": tuple(layers),
    }
    if not c.tie_embeddings:
        params["lm_head"] = dense(jax.random.fold_in(rng, 1001), (e, c.vocab_size), e)
    return params


def param_shardings(config: Qwen3NextConfig, mesh):
    raise NotImplementedError(
        "qwen3_next runs on one device: experts over the chips of a host are "
        "ROADMAP M1's remainder"
    )


# -- the two kinds of state ---------------------------------------------------

def make_kv_cache(
    config: Qwen3NextConfig, num_blocks: int, block_size: int, dtype: Any = None,
    quantized: bool = False,
) -> KVCache:
    """The attention layers' page pool, in the Llama layout."""
    if quantized:
        raise ValueError("qwen3_next has no int8 page layout")
    c = config
    shape = (c.layer_types.count("full_attention"), num_blocks, block_size, c.num_kv_heads, c.head_dim)
    return {"k": jnp.zeros(shape, dtype or jnp.float32), "v": jnp.zeros(shape, dtype or jnp.float32)}


def make_slot_state(config: Qwen3NextConfig, slots: int) -> SlotState:
    """The DeltaNet layers' state of every slot, zeroed: per layer the float32
    ``[S, H_v, d_k, d_v]`` matrix and the convolution's ``K - 1`` last inputs,
    oldest first, side by side along the minor axis."""
    c = config
    n = c.layer_types.count("linear_attention")
    return {
        "s": tuple(jnp.zeros((slots, c.linear_num_value_heads, c.linear_key_head_dim,
                              c.linear_value_head_dim), jnp.float32) for _ in range(n)),
        "conv": tuple(jnp.zeros((slots, (c.linear_conv_kernel_dim - 1) * c.conv_dim), jnp.float32)
                      for _ in range(n)),
    }


# -- the layers ---------------------------------------------------------------

def _norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """The zero-centred RMS norm: ``x * rsqrt(mean(x^2) + eps) * (1 + w)``."""
    return rms_norm(x, 1.0 + weight, eps)


def lm_head(params: Params, config: Qwen3NextConfig, h: jax.Array) -> jax.Array:
    """Final hidden states to float32 logits over the vocabulary rows held."""
    return dot_parts(h, params["embed"].T if config.tie_embeddings else params["lm_head"])


def gdn_mixer(lp: Params, c: Qwen3NextConfig, u: jax.Array, valid: jax.Array,
              s: jax.Array, tail: jax.Array, above=None):
    """The Gated DeltaNet mixer over ``[B, T, E]`` normed inputs whose valid
    tokens are a prefix of each row, from the rows' state ``s`` ``[B, H_v, d_k,
    d_v]`` and the convolution's tail ``[B, (K - 1) * conv_dim]``: (output
    ``[B, T, E]``, the state after each row's last valid token, the new tail:
    the row's last ``K - 1`` valid inputs of the convolution). One token (a
    decode step) is ``kda_step``; more (a chunk) are one call of the kernel
    that keeps the state on the chip (outputs past a row's valid tokens:
    zeros).

    ``above`` = (``takes`` ``[B]``, ``continues`` ``[B]``, a tail ``[(K - 1) *
    conv_dim]``): a row that ``takes`` goes on where the row above it ends, a
    FULL row of the same sequence, so its convolution starts from that row's
    last ``K - 1`` inputs (row 0 from the tail given) and not from ``tail``:
    they are the projection of that row's own tokens, so the rows are still
    convolved all at once. A row that ``continues`` (a row that takes, below
    row 0) starts its recurrence from that row's state INSIDE the kernel: the
    state returned is then the SEQUENCE's, after its last row, at its first,
    and the rows that continue have no entry of their own."""
    b, t, _ = u.shape
    hk, hv, d, kk = (c.linear_num_key_heads, c.linear_num_value_heads, c.linear_key_head_dim,
                     c.linear_conv_kernel_dim)
    qkvz = dot_parts(u, lp["w_qkvz"], PARTS)
    ba = dot_parts(u, lp["w_ba"], PARTS)
    continues = None
    if above is not None:
        takes, continues, first = above
        if t < kk - 1:
            raise ValueError(f"a row of {t} tokens holds no tail of {kk - 1}")
        ends = qkvz[:-1, t - (kk - 1):, :c.conv_dim].reshape(b - 1, (kk - 1) * c.conv_dim)
        tail = jnp.where(takes[:, None], jnp.concatenate([first[None], ends]), tail)
    # causal depthwise over q, k and v together: tap K-1 is the token itself, tap 0 the oldest input
    seq = jnp.concatenate([tail.reshape(b, kk - 1, c.conv_dim), qkvz[..., :c.conv_dim]], axis=1)
    mixed = jax.nn.silu(sum(seq[:, j:j + t] * lp["conv_w"][j] for j in range(kk)))
    tail_at = valid.sum(axis=1)[:, None] + jnp.arange(kk - 1)[None, :]  # the K-1 inputs before position n
    new_tail = jnp.take_along_axis(seq, tail_at[:, :, None], axis=1).reshape(b, -1)

    q = mixed[..., :c.key_dim].reshape(b, t, hk, d)
    k = mixed[..., c.key_dim:2 * c.key_dim].reshape(b, t, hk, d)
    v = mixed[..., 2 * c.key_dim:].reshape(b, t, hv, d)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS) * d ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))  # a key head's consecutive value heads
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(lp["a_log"]) * jax.nn.softplus(ba[..., hv:] + lp["dt_bias"])  # [B, T, H_v]: a head's log-decay

    if t == 1:
        new, o = kda_step(s, q[:, 0], k[:, 0], v[:, 0], g[:, 0, :, None], beta[:, 0])
        s, o = jnp.where(valid[:, 0, None, None, None], new, s), o[:, None]
    else:
        o, s = kda_scan(q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta, s, valid.sum(axis=1),
                        continues, interpret=jax.default_backend() == "cpu")
    # the gated norm over each head's d_v, a plain weight shared by the heads, times silu(z)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + c.rms_norm_eps) * lp["o_norm"]
    z = qkvz[..., c.conv_dim:]
    return dot_parts(o.reshape(b, t, c.value_dim) * jax.nn.silu(z), lp["wo"], PARTS), s, new_tail


def feed_forward(lp: Params, c: Qwen3NextConfig, h: jax.Array, valid: jax.Array):
    """(``h + FF(Norm(h))`` ``[B, T, E]``, the expert layer's counters: the
    first ``MOE_COUNTERS`` of ``COUNTERS``): the held experts' part of the
    routed sum, and the shared expert behind its own gate, which every chip of
    the deployment computes alike."""
    x = _norm(h, lp["ffn_norm"], c.rms_norm_eps)
    with jax.named_scope("moe"):
        b, t, e = x.shape
        flat = x.reshape(b * t, e)
        ids, weights = moe.route_softmax_topk(flat, lp["router"], c.num_experts_per_tok, c.norm_topk_prob)
        y, stats = moe.dropless_experts(
            flat, ids, weights, lp["w_gate"], lp["w_up"], lp["w_down"],
            first_expert=c.first_expert, num_experts_total=c.num_experts_published,
            token_valid=valid.reshape(-1), parts_of=_expert_parts)
        with jax.named_scope("shared"):
            gate = jax.nn.sigmoid(jnp.sum(flat * lp["shared_gate"].astype(jnp.float32), axis=-1, keepdims=True))
            hidden = jax.nn.silu(dot_parts(flat, lp["ws_gate"], PARTS)) * dot_parts(flat, lp["ws_up"], PARTS)
            y = y + gate * dot_parts(hidden, lp["ws_down"], PARTS)
    return h + y.reshape(b, t, e), stats


def _gdn_layer(lp: Params, c: Qwen3NextConfig, h, valid, s, tail, above=None):
    """A DeltaNet layer and its expert layer over ``h`` ``[B, T, E]``: (h, the
    rows' state and tail after it, the expert layer's counters)."""
    with jax.named_scope("gdn"):
        y, s, tail = gdn_mixer(lp, c, _norm(h, lp["mixer_norm"], c.rms_norm_eps), valid, s, tail, above)
    h, stats = feed_forward(lp, c, h + y, valid)
    return h, s, tail, stats


def _attn_inputs(lp: Params, c: Qwen3NextConfig, h, positions, dtype: Any):
    """q, k, v of an attention layer in the pages' ``dtype`` and the output
    gate (float32) of ``h`` ``[B, T, E]``: ``[q | gate]`` a head from one
    projection; q and k normed over each head's ``D`` (zero-centred, one weight
    shared by the heads) and THEN rotated in their first ``rotary_dim``
    channels, the rest passing as they are. No bias."""
    with jax.named_scope("attn"):
        b, t, _ = h.shape
        u = _norm(h, lp["mixer_norm"], c.rms_norm_eps)
        qg = dot_parts(u, lp["wq"], PARTS).reshape(b, t, c.num_heads, 2 * c.head_dim)
        k = dot_parts(u, lp["wk"], PARTS).reshape(b, t, c.num_kv_heads, c.head_dim)
        v = dot_parts(u, lp["wv"], PARTS).reshape(b, t, c.num_kv_heads, c.head_dim)

        def rotated(a, weight):
            a = _norm(a, weight, c.rms_norm_eps)
            r = c.rotary_dim
            return jnp.concatenate([apply_rope(a[..., :r], positions, c.rope_theta), a[..., r:]], axis=-1)

        q = rotated(qg[..., :c.head_dim], lp["q_norm"])
        k = rotated(k, lp["k_norm"])
        return q.astype(dtype), k.astype(dtype), v.astype(dtype), qg[..., c.head_dim:]


def _attn_outputs(lp: Params, c: Qwen3NextConfig, h, attn, gate, valid):
    """``h + W_o [attn * sigmoid(gate)]`` and the expert layer after it."""
    with jax.named_scope("attn"):
        b, t = attn.shape[:2]
        gated = attn.astype(jnp.float32) * jax.nn.sigmoid(gate)
        y = dot_parts(gated.reshape(b, t, c.num_heads * c.head_dim), lp["wo"], PARTS)
    return feed_forward(lp, c, h + y, valid)


# -- the step programs --------------------------------------------------------

class _Left(NamedTuple):
    """What the groups of such a dispatch so far leave the next one: all that
    the loop over the groups carries from group to group. One array a layer of
    its kind, as the slots' state is."""

    at: jax.Array  # the dispatch's row that is the next group's first
    tails: Tuple[jax.Array, ...]  # [(K - 1) * conv_dim] the tail the row above that one left
    s: Tuple[jax.Array, ...]  # [H_v, d_k, d_v] the state of the sequence that row ended
    k: Tuple[jax.Array, ...]  # [N, C, KVH, D] the dispatch's fresh keys so far, zeros from `at` on
    v: Tuple[jax.Array, ...]


def forward_chunk(
    params: Params, config: Qwen3NextConfig, tokens: jax.Array, positions: jax.Array,
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
    a zeroed state: a slot is reset by the first chunk of the request admitted
    to it. More than ``ROWS_AT_ONCE`` rows are taken in groups of that many,
    one after another; the pool and the state are only read inside the loop
    (a row touches its own slot and pages only), and what the rows made is
    written after it: one scatter a pool array and one a state array.

    A row whose lane is that of the row above it (both real) goes on where
    that row ends, inside the program (``llama.ChunkLayout``; the loop over the groups
    carries what a later group needs of the earlier ones, ``_Left``, and no
    more): its convolution starts from that row's last inputs and its
    recurrence from that row's state, inside the kernel where the two share a
    group and as the kernel's ``s0`` where the row is its group's first
    (:func:`gdn_mixer`); its pool history ends where its lane's FIRST row of
    the dispatch starts, and one more partial attends the fresh keys of its
    lane's rows above it (``chunk_rows_above_partial``). One write a slot and
    layer: the state from the row where the lane's LAST kernel sequence of the
    dispatch starts (the kernel leaves it there), the tail from the lane's last
    row. Where every lane has one row nothing is taken from a row above and
    the sibling loop makes no trip; at the full width none of it is in the
    program."""
    from dynamo_tpu.ops.attention import write_kv_to_pool

    c = config
    rows = tokens.shape[0]
    slots = state["s"][0].shape[0]
    pages = _pool_pages(kv_cache)
    num_blocks = kv_cache["k"].shape[1]
    layout = left = None
    if rows < slots:  # at the full width a lane has one row, and none of this is in the program
        layout = chunk_layout(positions, lanes, slots)
        none_yet = jnp.zeros((*tokens.shape, c.num_kv_heads, c.head_dim), kv_cache["k"].dtype)
        left = _Left(
            at=jnp.int32(0),
            tails=tuple(jnp.zeros(a.shape[1:], a.dtype) for a in state["conv"]),
            s=tuple(jnp.zeros(a.shape[1:], a.dtype) for a in state["s"]),
            k=(none_yet,) * kv_cache["k"].shape[0], v=(none_yet,) * kv_cache["k"].shape[0])
    group = partial(_chunk_rows, params, c, pages, num_blocks, state, layout)
    if rows <= ROWS_AT_ONCE:
        h, k, v, s, tails, left, counters = group(left, tokens, positions, block_tables, lanes)
    else:
        if rows % ROWS_AT_ONCE:
            raise ValueError(f"{rows} rows are no whole number of groups of {ROWS_AT_ONCE}")

        def grouped(a):
            return a.reshape(rows // ROWS_AT_ONCE, ROWS_AT_ONCE, *a.shape[1:])

        def step(carry, xs):
            sums, left = carry
            h, k, v, s, tails, left, more = group(left, *xs)
            # with a layout the dispatch's K and V so far go on to the next group in ``left``
            return (sums + more, left), (h, s, tails, *((k, v) if left is None else ()))

        (counters, left), (h, s, tails, *kv) = jax.lax.scan(
            step, (jnp.zeros((len(COUNTERS),), jnp.int32), left),
            (grouped(tokens), grouped(positions), grouped(block_tables), grouped(lanes)))
        # [G, R, ...] -> [G * R, ...], every layer's array by itself: nothing is transposed. The
        # rows' new states stay as the loop stacked them, and their slots take that shape
        h, kv = jax.tree.map(lambda a: a.reshape(rows, *a.shape[2:]), (h, kv))
        k, v = kv or (left.k, left.v)
        lanes = grouped(lanes)
    cache = {"k": write_kv_to_pool(kv_cache["k"], jnp.stack(k), positions, block_tables),
             "v": write_kv_to_pool(kv_cache["v"], jnp.stack(v), positions, block_tables)}
    # a padding row writes nowhere: its slot index lies past the state
    back = s_back = jnp.where(lanes < slots, lanes, slots)
    if layout is not None:
        # nor do a lane's rows but ONE: the tail is its last row's, the state lies where its last
        # kernel sequence starts (a row that takes nothing, or a group's first row; the lane's
        # last such row: one that a later group's first row goes on from holds a state since passed)
        row = jnp.arange(rows)
        heads = layout.takes & (row % ROWS_AT_ONCE == 0)
        passed = ((layout.lanes[:, None] == layout.lanes[None, :]) & heads[None, :]
                  & (row[None, :] > row[:, None])).any(axis=1)
        sequence = (~layout.takes | heads) & ~passed
        under = jnp.concatenate([layout.takes[1:], jnp.zeros((1,), bool)])
        s_back = jnp.where(sequence.reshape(back.shape), back, slots)
        back = jnp.where(under.reshape(back.shape), slots, back)

    def written(was, new, at):
        return tuple(a.at[at].set(b, mode="drop") for a, b in zip(was, new))

    return h, cache, {"s": written(state["s"], s, s_back), "conv": written(state["conv"], tails, back)}, counters


def _chunk_rows(params, c, pages, num_blocks, state, layout, left, tokens, positions, block_tables,
                lanes):
    """The layers over the rows given, all at once, the pool (its
    ``_pool_pages`` views) and the slots' ``state`` read and not written:
    (hidden after the final norm, the attention layers' fresh K and V, each a
    tuple of ``[R, C, KVH, D]``, the DeltaNet layers' new states and tails,
    each a tuple of a layer's rows, what the next group is left, the
    counters). With a ``layout`` the rows are ``left.at`` onwards of a
    dispatch in which a lane may fill several, the K and V returned are the
    DISPATCH's so far, ``[N, C, KVH, D]``, and a layer's new states are its
    kernel sequences', each at its first row of the group."""
    valid = positions >= 0
    fresh = positions[:, 0] == 0
    slots = state["s"][0].shape[0]
    lane = jnp.clip(lanes, 0, slots - 1)
    real = lanes < slots
    n = tokens.shape[0]

    dtype = pages["k"].dtype
    scale = c.head_dim ** -0.5
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
    continues = None
    if layout is not None:
        # the kernel's sequences are the group's own: its first row starts one whatever it takes
        # (what the group before left it comes as its ``s0``), a later row that takes continues
        continues = takes.at[0].set(False)
        last = jax.lax.cummax(jnp.where(continues, 0, jnp.arange(n)))[-1]  # where the last row's starts

    h = embed_lookup(params, tokens, c.dtype).astype(jnp.float32)
    s_new, tails, fresh_k, fresh_v = [], [], [], []
    stats = jnp.zeros((MOE_COUNTERS,), jnp.int32)
    for lp, kind in zip(params["layers"], c.layer_types):
        if kind == "linear_attention":
            i = len(s_new)
            s0 = jnp.where(fresh[:, None, None, None], 0.0, state["s"][i][lane])
            tail0 = jnp.where(fresh[:, None], 0.0, state["conv"][i][lane])
            above = None
            if layout is not None:
                s0 = s0.at[0].set(jnp.where(takes[0], left.s[i], s0[0]))
                above = (takes, continues, left.tails[i])
            h, s1, tail1, more = _gdn_layer(lp, c, h, valid, s0, tail0, above)
            s_new.append(s1)
            tails.append(tail1)
        else:
            j = len(fresh_k)
            q, k, v, gate = _attn_inputs(lp, c, h, positions, dtype)
            with jax.named_scope("attn"), jax.default_matmul_precision(ATTENTION_PRECISION):
                hist = chunk_history_partial(
                    c, q, pages, j * num_blocks + tables, history_len, n_tiles, positions,
                    scale, tile_blocks, block_size, dtype)
                part = _merge_partials(hist, _chunk_self_partial(c, q, k, v, positions, scale))
                if layout is not None:
                    k, v = (jax.lax.dynamic_update_slice_in_dim(all_rows[j], mine, left.at, 0)
                            for all_rows, mine in ((left.k, k), (left.v, v)))
                    part = chunk_rows_above_partial(
                        c, q, k, v, layout.positions, layout.lanes, left.at, layout.n_back, scale, part)
                num, _, den = part
                attn = jnp.where(
                    (den > 0.0).transpose(0, 2, 1)[..., None],
                    num / jnp.maximum(den, 1e-30).transpose(0, 2, 1)[..., None], 0.0)
            h, more = _attn_outputs(lp, c, h, attn, gate, valid)
            fresh_k.append(k)
            fresh_v.append(v)
        stats = stats + more
    h = _norm(h, params["final_norm"], c.rms_norm_eps)
    advanced = valid.sum(axis=1)  # a DeltaNet layer's kernel advances each row by its valid tokens
    begins = advanced > 0  # a kernel sequence's first row: where a state goes in and out
    if layout is not None:
        begins &= ~continues
        left = _Left(left.at + n, tuple(t[-1] for t in tails), tuple(s1[last] for s1 in s_new),
                     tuple(fresh_k), tuple(fresh_v))
    own = jnp.stack([len(s_new) * advanced.sum(), len(s_new) * jnp.sum(begins), jnp.sum(fresh & real),
                     jnp.int32(0) if layout is None else jnp.sum(takes)]).astype(jnp.int32)
    return (h, tuple(fresh_k), tuple(fresh_v), tuple(s_new), tuple(tails), left,
            jnp.concatenate([stats, own]))


def decode(
    params: Params, config: Qwen3NextConfig, tokens: jax.Array, positions: jax.Array,
    kv_cache: KVCache, block_tables: jax.Array, state: SlotState, steps: int, max_pos: int,
    sample, carry,
):
    """``steps`` tokens of every slot (``tokens``, ``positions`` ``[S]``;
    position < 0 = the slot does not decode, and its state stays as it is; a
    lane that passes ``max_pos`` stops there).

    The ``steps`` (a handful) are unrolled, so that one step leaves the state
    where the next takes it, and the steps take the state as what
    ``with_live_history`` CARRIES (one conditional a history width, each
    width's steps the last branch of their own: the state is updated where it
    lies, ``models/jamba.py`` says why). The attention layers are the dense
    tier of the Llama decode program, as ``models/jamba.py`` and
    ``models/lfm2.py`` have it: the pool is read-only inside the dispatch, its
    live (lane, tile) pairs gathered once, a step's K and V go to a window
    buffer and the pool takes the window after the steps in one scatter a pool
    array (``flush_window``). ``sample(logits [S, V], positions, carry, k) ->
    (next tokens [S], carry, outputs)`` is the engine's. Returns (tokens,
    positions, carry, the stacked outputs, pool, state, counters
    ``[len(COUNTERS)]``)."""
    c = config
    base = positions
    n_slots = tokens.shape[0]
    dtype = kv_cache["k"].dtype
    window = jnp.zeros((n_slots, steps, c.num_kv_heads, c.head_dim), dtype)
    n_attn = c.layer_types.count("full_attention")

    def run(history):
        live = history[1]

        def step(loop, k):
            toks, pos, carry, s_all, conv_all, wk, wv, stats = loop
            valid = (pos >= 0)[:, None]
            in_window = (jnp.arange(steps)[None, :] <= k) & (base[:, None] >= 0)  # [S, W]
            s_all, conv_all, wk, wv = list(s_all), list(conv_all), list(wk), list(wv)
            h = embed_lookup(params, toks, c.dtype).astype(jnp.float32)[:, None]  # [S, 1, E]
            i = j = 0
            for lp, kind in zip(params["layers"], c.layer_types):
                if kind == "linear_attention":
                    h, s_all[i], conv_all[i], more = _gdn_layer(lp, c, h, valid, s_all[i], conv_all[i])
                    i += 1
                else:
                    q, kk, vv, gate = _attn_inputs(lp, c, h, pos[:, None], dtype)
                    with jax.named_scope("attn"), jax.default_matmul_precision(ATTENTION_PRECISION):
                        wk[j] = jax.lax.dynamic_update_slice(wk[j], kk, (0, k, 0, 0))
                        wv[j] = jax.lax.dynamic_update_slice(wv[j], vv, (0, k, 0, 0))
                        attn = _live_window_attention(
                            c, q, live, live.k[j], live.v[j], wk[j], wv[j], in_window, None)
                    h, more = _attn_outputs(lp, c, h, attn, gate, valid)
                    j += 1
                stats = stats + more
            h = _norm(h, params["final_norm"], c.rms_norm_eps)
            nxt, carry, out = sample(lm_head(params, c, h)[:, 0], pos, carry, k)
            new_pos = jnp.where((pos >= 0) & (pos < max_pos), pos + 1, -1)
            return (nxt, new_pos, carry, tuple(s_all), tuple(conv_all), tuple(wk), tuple(wv), stats), out

        def unrolled(loop, _):
            outs = []
            for k in range(steps):
                loop, out = step(loop, jnp.int32(k))
                outs.append(out)
            return loop, jax.tree.map(lambda *a: jnp.stack(a), *outs)

        return unrolled

    # the steps update the state where it lies, so they take it as ``carried``, and with it
    # room for what a step's ``sample`` gives, stacked over the steps
    out = jax.eval_shape(lambda: sample(
        jnp.zeros((n_slots, c.vocab_size), jnp.float32), positions, carry, jnp.int32(0))[2])
    loop = (tokens, positions, carry, state["s"], state["conv"], (window,) * n_attn, (window,) * n_attn,
            jnp.zeros((MOE_COUNTERS,), jnp.int32))
    (toks, pos, carry, s_all, conv_all, wk, wv, stats), out = with_live_history(
        kv_cache, block_tables, base, run, out_dtype=dtype,
        carried=(loop, jax.tree.map(lambda a: jnp.zeros((steps, *a.shape), a.dtype), out)))
    cache = flush_window(kv_cache, block_tables, base, jnp.stack(wk), jnp.stack(wv), max_pos)
    counters = jnp.concatenate([stats, jnp.zeros((len(COUNTERS) - MOE_COUNTERS,), jnp.int32)])
    return toks, pos, carry, out, cache, {"s": s_all, "conv": conv_all}, counters
