"""Kimi-Linear decoder: delta-rule linear attention (KDA) beside latent
attention (MLA), a dense first feed-forward and expert layers after it.

Layers differ in kind within one model, so the layers are a tuple of per-layer
trees and the programs unroll over them (``layer_kinds``); nothing is stacked.
Two kinds of state live side by side:

- the MLA layers' pages: ONE member ``latent`` of the pool,
  ``[L_mla, N, bs, kv_lora_rank + qk_rope_head_dim]``: the normed latent and
  the shared key part of a token. It is paged, and travels through
  ``kv/pages.py`` like any member.
- the KDA layers' state, PER SLOT (:class:`SlotState`, owned here): a float32
  ``[S, H, d_k, d_v]`` matrix and the last ``K - 1`` inputs of the three short
  convolutions, one array per KDA layer. The chunk and decode programs read it
  and hand it back; a chunk row whose first position is 0 starts from zeros,
  which is how a slot is reset when a request is admitted to it. Nothing
  outside this module indexes it, and ``pages.take`` / ``put`` never see it.
  Under the full width a lane may fill several rows of a chunk dispatch
  (``LANE_TAKES_ROWS``): a later row goes on from the row above it, the
  state inside the kernel and the convolutions behind that row's last
  inputs, and the slot is written once (:func:`forward_chunk`).

Every mechanism but two is plain ``jax.numpy`` through XLA (the expert layer's
products and a chunk's KDA recurrence are kernels, below). The weights are bfloat16
and the activations float32 from the embedding to the head, with every product
against a weight taken at float32's precision (``ops/latent.py:wdot`` says why: a
router's choice of 8 among 256 scores is a discontinuity, and bfloat16's
noise on its input swaps experts in several token-layers of a hundred). That
arithmetic is this module's alone: no shared op takes it up (a deployment
would serve bfloat16 and be a quarter faster a step; ROADMAP B7). The
latent pages are float32 with them: a latent rounded to bfloat16 on its way
into the pool was enough to swap an expert in three probes of fourteen on the
chip (PERF.md, the model's section). The chunk form of the KDA
recurrence is the recurrence itself, token by token, in ONE kernel a layer
that holds a (lane, head) pair's state on the chip from the first token of the
lane's first row to the last valid one of its last (``ops/pallas/kda_scan.py``);
a decode step, one token, is the same step in ``jax.numpy``: the shape picks
the path. The MLA layer attends in the absorbed form: the query's no-position
part goes through ``W_kvb``'s key half into the latent space, scores and the
weighted sum are taken against the cached latent, and ``W_kvb``'s value half
comes after; the same mathematics as expanding keys and values from the latent
at every step.

The expert layer holds ``num_experts`` experts from ``first_expert`` on, of
the ``num_experts_published`` the router scores (``ops/moe.py``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.models.llama import embed_lookup, history_tiles_full, rms_norm
from dynamo_tpu.ops import moe
from dynamo_tpu.ops.latent import (
    PASSES, attend_absorbed, attend_absorbed_live, cached_latent, gather_latent as _gather_latent,
    live_history_tiles, live_latents, mm as _mm, recent_latents, write_latent as _write_latent,
)
from dynamo_tpu.ops.pallas.kda_scan import kda_scan, kda_step as _kda_step
from dynamo_tpu.ops.parts import operand_parts

Params = Dict[str, Any]
KVCache = Dict[str, jax.Array]  # {"latent": [L_mla, N, bs, rank + rope]} float32
SlotState = Dict[str, Tuple[jax.Array, ...]]  # {"s": per KDA layer, "conv": ...}

# sums the step programs return, in this order (engine: /debug/engine)
COUNTERS = ("moe_layer_calls", "moe_held_rows", "moe_experts_hit", "moe_routed_pairs",
            "moe_rows_computed", "moe_expert_reads",
            # what the chunks' KDA kernel advanced: valid tokens, and lanes of a dispatch
            # with one (each a read and a write of a slot's state); summed over the KDA layers
            "kda_chunk_tokens", "kda_state_passes", "slot_state_resets",
            # rows that took their state from the row above them, on the chip
            "kda_state_handovers")
# a lane may fill several rows of a chunk dispatch under the full width (engine.py:chunk_rows_of):
# a row whose lane is that of the row above it goes on where that row ends (`forward_chunk`)
LANE_TAKES_ROWS = True
# rows of a chunk computed at once: the rows are independent, and a chunk of
# more is taken in groups, which bounds what the program holds beside its
# arguments (64 rows at once: 3.7 GB of temporaries next to 9.4 GB)
ROWS_AT_ONCE = 16
MOE_COUNTERS = COUNTERS.index("kda_chunk_tokens")  # the first: what ops/moe.py:dropless_experts counts


# The arithmetic (float32 activations in ``PASSES`` bfloat16 parts against
# bfloat16 weights: ``wdot``, ``_mm``) is ``ops/latent.py``'s, shared with
# ``models/openpangu.py``; it says why.
_expert_parts = partial(operand_parts, parts=PASSES)  # ops/moe.py:dropless_experts' ``parts_of``


def lm_head(params: Params, config: "KimiLinearConfig", h: jax.Array) -> jax.Array:
    """Final hidden states to float32 logits (the head is untied)."""
    return _mm(h.astype(jnp.float32), params["lm_head"])


@dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216  # the dense feed-forward of the first layers
    num_layers: int = 27
    # MLA
    num_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # KDA
    kda_heads: int = 32
    kda_head_dim: int = 128
    conv_kernel: int = 4
    # published 1-based layer ids; an id past num_layers is ignored
    kda_layers: Tuple[int, ...] = ()
    full_attn_layers: Tuple[int, ...] = ()
    # experts
    first_k_dense: int = 1
    moe_intermediate_size: int = 1024
    num_experts: int = 256  # held here, ids first_expert ...
    num_experts_published: int = 256  # the router's width
    first_expert: int = 0
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    moe_renormalize: bool = True
    rms_norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16

    @property
    def kda_dim(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def gate_rank(self) -> int:
        """Rank of the decay gate and the output gate: the head size (from the
        published modeling code, not from config.json)."""
        return self.kda_head_dim

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def layer_kinds(c: KimiLinearConfig) -> Tuple[str, ...]:
    """``"kda"`` or ``"mla"`` for each layer held, from the published lists."""
    kinds = []
    for i in range(1, c.num_layers + 1):
        if i in c.full_attn_layers:
            kinds.append("mla")
        elif i in c.kda_layers:
            kinds.append("kda")
        else:
            raise ValueError(f"layer {i} is in neither kda_layers nor full_attn_layers")
    return tuple(kinds)


def is_expert_layer(c: KimiLinearConfig, layer: int) -> bool:
    return layer >= c.first_k_dense


# -- parameters ---------------------------------------------------------------

def init_params(rng: jax.Array, config: KimiLinearConfig) -> Params:
    """Random init with fan-in scaling. ``a_log`` and ``dt_bias`` as the
    published initialiser has them (log U(1, 16); the inverse softplus of
    U(0.001, 0.1)), so that the decay lies in (0, 1) and away from both ends.
    The router's selection bias: small values, so that a test can tell the
    choice (by score + bias) from the weight (by score)."""
    c = config
    e, d = c.hidden_size, c.kda_dim

    def dense(key, shape, fan_in, dtype=None):
        w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
        return w.astype(dtype or c.dtype)

    def kda(key):
        k = jax.random.split(key, 12)
        dt = jax.random.uniform(k[10], (d,), jnp.float32, 0.001, 0.1)
        return {
            "wq": dense(k[0], (e, d), e), "wk": dense(k[1], (e, d), e),
            "wv": dense(k[2], (e, d), e),
            "conv_q": dense(k[3], (c.conv_kernel, d), c.conv_kernel, jnp.float32),
            "conv_k": dense(k[4], (c.conv_kernel, d), c.conv_kernel, jnp.float32),
            "conv_v": dense(k[5], (c.conv_kernel, d), c.conv_kernel, jnp.float32),
            "a_log": jnp.log(jax.random.uniform(k[11], (c.kda_heads,), jnp.float32, 1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "wf_down": dense(k[6], (e, c.gate_rank), e),
            "wf_up": dense(jax.random.fold_in(k[6], 1), (c.gate_rank, d), c.gate_rank),
            "w_beta": dense(k[7], (e, c.kda_heads), e),
            "wg_down": dense(k[8], (e, c.gate_rank), e),
            "wg_up": dense(jax.random.fold_in(k[8], 1), (c.gate_rank, d), c.gate_rank),
            "o_norm": jnp.ones((c.kda_head_dim,), jnp.float32),
            "wo": dense(k[9], (d, e), d),
        }

    def mla(key):
        k = jax.random.split(key, 4)
        return {
            "wq": dense(k[0], (e, c.num_heads * c.qk_head_dim), e),
            "w_kva": dense(k[1], (e, c.latent_dim), e),
            "kv_norm": jnp.ones((c.kv_lora_rank,), jnp.float32),
            "w_kvb": dense(k[2], (c.kv_lora_rank, c.num_heads * (c.qk_nope_head_dim + c.v_head_dim)),
                           c.kv_lora_rank),
            "wo": dense(k[3], (c.num_heads * c.v_head_dim, e), c.num_heads * c.v_head_dim),
        }

    def ffn(key, experts: bool):
        k = jax.random.split(key, 8)
        if not experts:
            f = c.intermediate_size
            return {"w_gate": dense(k[0], (e, f), e), "w_up": dense(k[1], (e, f), e),
                    "w_down": dense(k[2], (f, e), f)}
        x, f = c.num_experts, c.moe_intermediate_size
        fs = f * c.num_shared_experts
        return {
            "router": dense(k[0], (e, c.num_experts_published), e, jnp.float32),
            "router_bias": 0.02 * jax.random.normal(k[1], (c.num_experts_published,), jnp.float32),
            "w_gate": dense(k[2], (x, e, f), e), "w_up": dense(k[3], (x, e, f), e),
            "w_down": dense(k[4], (x, f, e), f),
            "ws_gate": dense(k[5], (e, fs), e), "ws_up": dense(k[6], (e, fs), e),
            "ws_down": dense(k[7], (fs, e), fs),
        }

    layers = []
    for i, kind in enumerate(layer_kinds(c)):
        key = jax.random.fold_in(rng, i)
        layers.append({
            "attn_norm": jnp.ones((e,), jnp.float32),
            "mlp_norm": jnp.ones((e,), jnp.float32),
            **(kda if kind == "kda" else mla)(jax.random.fold_in(key, 0)),
            **ffn(jax.random.fold_in(key, 1), is_expert_layer(c, i)),
        })
    return {
        "embed": dense(jax.random.fold_in(rng, 1000), (c.vocab_size, e), e),
        "final_norm": jnp.ones((e,), jnp.float32),
        "layers": tuple(layers),
        "lm_head": dense(jax.random.fold_in(rng, 1001), (e, c.vocab_size), e),
    }


def param_shardings(config: KimiLinearConfig, mesh):
    raise NotImplementedError(
        "kimi_linear runs on one device: experts over the chips of a host are "
        "ROADMAP M1's remainder"
    )


# -- the two kinds of state ---------------------------------------------------

def make_kv_cache(
    config: KimiLinearConfig, num_blocks: int, block_size: int, dtype: Any = None,
    quantized: bool = False,
) -> KVCache:
    """The MLA layers' page pool: one ``latent`` member, float32 as the
    activations are unless the caller names a ``dtype``."""
    if quantized:
        raise ValueError("kimi_linear has no int8 page layout")
    n_mla = layer_kinds(config).count("mla")
    return {"latent": jnp.zeros(
        (n_mla, num_blocks, block_size, config.latent_dim), dtype or jnp.float32)}


def make_slot_state(config: KimiLinearConfig, slots: int) -> SlotState:
    """The KDA layers' state of every slot, zeroed: per KDA layer a float32
    ``[S, H, d_k, d_v]`` matrix and the ``K - 1`` last inputs of the q, k and v
    convolutions, ``[S, K - 1, 3 * H * d_k]``, float32 as the activations are."""
    c = config
    n_kda = layer_kinds(c).count("kda")
    return {
        "s": tuple(jnp.zeros((slots, c.kda_heads, c.kda_head_dim, c.kda_head_dim), jnp.float32)
                   for _ in range(n_kda)),
        "conv": tuple(jnp.zeros((slots, c.conv_kernel - 1, 3 * c.kda_dim), jnp.float32)
                      for _ in range(n_kda)),
    }


def chunk_history_tiles(positions, block_size: int, table_blocks: int, lanes=None) -> int:
    """Tiles of a block table a chunk dispatch reads, for the host's count
    (``models/llama.py`` has the form): :func:`mla_attend` scores every row's
    whole table, whatever it holds and whichever rows are one lane's
    (``lanes`` is taken and not read)."""
    return history_tiles_full(block_size, table_blocks)


def decode_history_tiles(base, block_size: int, table_blocks: int):
    """(lane, tile) pairs a decode step attends for ``base`` ``[B]`` (a lane's
    history is the positions < base; -1: the lane does not decode): the tiles
    that hold history, a block of lanes as far as its longest
    (``ops/latent.py:live_history_tiles``, for a traced array and a numpy one
    alike)."""
    return live_history_tiles(base, block_size, table_blocks)


# -- KDA ----------------------------------------------------------------------

def _kda_inputs(lp: Params, c: KimiLinearConfig, x: jax.Array, conv_tail: jax.Array,
                valid: jax.Array, above=None):
    """Everything the recurrence needs of a ``[B, T, E]`` block of normed
    inputs whose valid tokens are a prefix of each row: q, k (normalised), v,
    log-decay, beta (float32, ``[B, T, H, ...]``), the output gate, and the
    convolutions' new tails (the last ``K - 1`` valid inputs of each row). A
    chunk's row that goes on from the row ``above`` it (``[B]`` bool), a FULL
    row of the same sequence, starts its three convolutions behind that row's
    last ``K - 1`` inputs and not behind ``conv_tail``: they are the
    projections of that row's own tokens, so the rows are still convolved all
    at once."""
    b, t, _ = x.shape
    h, dk, kk = c.kda_heads, c.kda_head_dim, c.conv_kernel
    pre = jnp.concatenate([_mm(x, lp["wq"]), _mm(x, lp["wk"]), _mm(x, lp["wv"])], axis=-1)
    if above is not None:
        if t < kk - 1:
            raise ValueError(f"a row of {t} tokens holds no tail of {kk - 1}")
        ends = pre[:, t - (kk - 1):]  # what each row, if full, leaves the row under it
        conv_tail = jnp.where(above[:, None, None], jnp.concatenate([conv_tail[:1], ends[:-1]]), conv_tail)
    seq = jnp.concatenate([conv_tail, pre], axis=1)  # [B, K-1+T, 3D]
    w = jnp.concatenate([lp["conv_q"], lp["conv_k"], lp["conv_v"]], axis=-1)  # [K, 3D]
    # causal depthwise: tap K-1 is the token itself, tap 0 the oldest input
    mixed = sum(seq[:, j:j + t] * w[j] for j in range(kk))
    q, k, v = jnp.split(jax.nn.silu(mixed), 3, axis=-1)
    q, k, v = (a.reshape(b, t, h, dk) for a in (q, k, v))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * dk ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    n = valid.sum(axis=1)  # [B] valid tokens of the row
    tail_at = n[:, None] + jnp.arange(kk - 1)[None, :]  # the K-1 inputs before position n
    new_tail = jnp.take_along_axis(seq, tail_at[:, :, None], axis=1)

    f = _mm(_mm(x, lp["wf_down"]), lp["wf_up"]) + lp["dt_bias"]
    log_decay = -jnp.exp(lp["a_log"])[:, None] * jax.nn.softplus(f).reshape(b, t, h, dk)
    beta = jax.nn.sigmoid(_mm(x, lp["w_beta"]))  # [B, T, H]
    gate = jax.nn.sigmoid(_mm(_mm(x, lp["wg_down"]), lp["wg_up"]))
    return q, k, v, log_decay, beta, gate, new_tail


def _kda_output(lp: Params, c: KimiLinearConfig, o: jax.Array, gate: jax.Array):
    """``W_o [RMSNorm_head(o) * gate]``; ``o`` float32 ``[B, T, H, d_v]``."""
    b, t = o.shape[:2]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + c.rms_norm_eps) * lp["o_norm"]
    return _mm(o.reshape(b, t, -1) * gate, lp["wo"])


def kda_mixer(lp: Params, c: KimiLinearConfig, x: jax.Array, valid: jax.Array,
              s: jax.Array, conv_tail: jax.Array, above=None):
    """The KDA mixer over ``[B, T, E]`` normed inputs from the rows' state:
    (output ``[B, T, E]``, state after the last valid token, new tails). One
    token (a decode step) is ``ops/pallas/kda_scan.py:kda_step`` (shared with
    ``models/qwen3_next.py``); more (a chunk) are one call of the kernel that
    keeps the state on the chip (outputs past a row's valid tokens: zeros). A
    chunk's row that goes on from the row ``above`` it takes that row's last
    inputs and state, the state inside the kernel: the state returned is then
    the SEQUENCE's, after its last row, at its first (the rows that go on have
    no entry of their own), and the tails are each row's own."""
    q, k, v, log_decay, beta, gate, new_tail = _kda_inputs(lp, c, x, conv_tail, valid, above)
    if x.shape[1] == 1:
        new, o = _kda_step(s, q[:, 0], k[:, 0], v[:, 0], log_decay[:, 0], beta[:, 0])
        s, o = jnp.where(valid[:, 0, None, None, None], new, s), o[:, None]
    else:
        o, s = kda_scan(q, k, v, log_decay, beta, s, valid.sum(axis=1), above,
                        interpret=jax.default_backend() == "cpu")
    return _kda_output(lp, c, o, gate), s, new_tail


# -- MLA ----------------------------------------------------------------------

def mla_latent(lp: Params, c: KimiLinearConfig, x: jax.Array) -> jax.Array:
    """What the cache holds of a token: ``[RMSNorm(c) ; k^r]``."""
    return cached_latent(x, lp["w_kva"], lp["kv_norm"], c.kv_lora_rank, c.rms_norm_eps)


def _absorbed(lp: Params, c: KimiLinearConfig, x: jax.Array):
    """What ``ops/latent.py``'s forms of absorbed attention take of a layer
    around the keys: (the queries of ``x`` ``[B, T, E]``, ``W_kvb``, ``W_o``)
    in front, (rank, no-position width, value width, the scores' scale)
    behind. No rotation anywhere (``mla_use_nope``): position comes from the
    KDA layers."""
    b, t, _ = x.shape
    q = _mm(x, lp["wq"]).reshape(b, t, c.num_heads, c.qk_head_dim)
    return ((q, lp["w_kvb"], lp["wo"]),
            (c.kv_lora_rank, c.qk_nope_head_dim, c.v_head_dim, c.qk_head_dim ** -0.5))


def mla_attend(lp: Params, c: KimiLinearConfig, x: jax.Array, latent: jax.Array,
               mask: jax.Array) -> jax.Array:
    """Absorbed latent attention (``ops/latent.py``): queries of ``x`` ``[B, T,
    E]`` against the cached ``latent`` ``[B, P, rank + rope]`` under ``mask``
    ``[B, T, P]`` (a chunk's rows, every row's whole table)."""
    ends, dims = _absorbed(lp, c, x)
    return attend_absorbed(*ends, latent, mask, *dims)


# -- feed-forward -------------------------------------------------------------

def _swiglu(x, w_gate, w_up, w_down):
    return _mm(jax.nn.silu(_mm(x, w_gate)) * _mm(x, w_up), w_down)


def feed_forward(lp: Params, c: KimiLinearConfig, layer: int, x: jax.Array, valid: jax.Array):
    """(output ``[B, T, E]``, the expert layer's counters: the first
    ``MOE_COUNTERS`` of ``COUNTERS``). The dense first layers count nothing."""
    if not is_expert_layer(c, layer):
        return _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"]), jnp.zeros((MOE_COUNTERS,), jnp.int32)
    with jax.named_scope("moe"):
        b, t, e = x.shape
        flat = x.reshape(b * t, e)
        ids, weights = moe.route_sigmoid_topk(
            flat, lp["router"], lp["router_bias"], c.num_experts_per_tok,
            c.routed_scaling_factor, c.moe_renormalize)
        y, stats = moe.dropless_experts(
            flat, ids, weights, lp["w_gate"], lp["w_up"], lp["w_down"],
            first_expert=c.first_expert, num_experts_total=c.num_experts_published,
            token_valid=valid.reshape(-1), parts_of=_expert_parts)
        y = y + _swiglu(flat, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        return y.reshape(b, t, e), stats


# -- the step programs --------------------------------------------------------

def forward_chunk(
    params: Params, config: KimiLinearConfig, tokens: jax.Array, positions: jax.Array,
    kv_cache: KVCache, block_tables: jax.Array, state: SlotState, lanes: jax.Array,
):
    """A ``[R, C]`` block of prompt tokens (``lanes`` ``[R]``: the row's slot;
    ``max_slots`` and above = a padding row), valid tokens (position >= 0) a
    prefix of each row. Under the full width (``R`` < the state's slots) a lane
    may fill several CONSECUTIVE rows with successive pieces of its prompt, in
    order, each full but the last; at it, one row a lane.

    Returns (hidden ``[R, C, E]`` after the final norm, the pool with the
    rows' latents written, the slot state with the rows' slots advanced, the
    counters ``[len(COUNTERS)]``). A row whose first position is 0 starts from a zeroed
    state: a slot is reset by the first chunk of the request admitted to it.
    More than ``ROWS_AT_ONCE`` rows are taken in groups of that many, one
    after another over one pool and one state.

    A row whose lane is that of the row above it in its group (both real, with
    a valid token) goes on where that row ends: its convolutions start behind
    that row's last inputs and its recurrence from that row's state inside the
    kernel (:func:`kda_mixer`), and only the lane's FIRST row of the group
    writes the slot's state (the kernel leaves it there), only its LAST the
    tail. The latent attention needs nothing said: a layer writes every row's
    latents into the pool before any row gathers its table, under a causal
    mask by position, and a lane's rows share a table. A lane whose rows
    straddle two groups is served by the loop's carry: the later group's first
    row goes on from nothing inside its group and reads the slot's state and
    tail, which the group before wrote. At the full width none of this is in
    the program."""
    rows = tokens.shape[0]
    handed = rows < (state["s"][0].shape[0] if state["s"] else 0)
    if rows <= ROWS_AT_ONCE:
        return _chunk_rows(params, config, tokens, positions, kv_cache, block_tables, state, lanes, handed)
    if rows % ROWS_AT_ONCE:
        raise ValueError(f"{rows} rows are no whole number of groups of {ROWS_AT_ONCE}")

    def group(carry, xs):
        kv_cache, state, sums = carry
        toks, pos, tables, lanes = xs
        h, kv_cache, state, more = _chunk_rows(
            params, config, toks, pos, kv_cache, tables, state, lanes, handed)
        return (kv_cache, state, sums + more), h

    def grouped(a):
        return a.reshape(rows // ROWS_AT_ONCE, ROWS_AT_ONCE, *a.shape[1:])

    (kv_cache, state, sums), h = jax.lax.scan(
        group, (kv_cache, state, jnp.zeros((len(COUNTERS),), jnp.int32)),
        (grouped(tokens), grouped(positions), grouped(block_tables), grouped(lanes)))
    return h.reshape(rows, *h.shape[2:]), kv_cache, state, sums


def _chunk_rows(params, config, tokens, positions, kv_cache, block_tables, state, lanes, handed):
    """:func:`forward_chunk` of the rows given, all at once; ``handed``: a lane
    may fill several of them (the dispatch is under the full width)."""
    c = config
    valid = positions >= 0
    fresh = positions[:, 0] == 0
    slots = state["s"][0].shape[0] if state["s"] else 0
    lane = jnp.clip(lanes, 0, max(slots - 1, 0))
    # a padding row writes nowhere: its slot index lies past the state
    back = s_back = jnp.where(lanes < slots, lanes, slots)
    above = None
    if handed:
        live = (lanes < slots) & valid[:, 0]
        above = jnp.concatenate([jnp.zeros((1,), bool), (lanes[1:] == lanes[:-1]) & live[1:] & live[:-1]])
        # nor do a lane's rows but ONE: the first holds the state after the last, the last the tail
        s_back = jnp.where(above, slots, back)
        back = jnp.where(jnp.concatenate([above[1:], jnp.zeros((1,), bool)]), slots, back)
    pool = kv_cache["latent"]
    s_out, conv_out = list(state["s"]), list(state["conv"])
    counters = jnp.zeros((MOE_COUNTERS,), jnp.int32)
    # key p of a gathered table is position p: a query sees keys up to its own
    key_pos = jnp.arange(block_tables.shape[1] * pool.shape[2])
    mask = (key_pos[None, None, :] <= positions[:, :, None]) & valid[:, :, None]

    h = embed_lookup(params, tokens, c.dtype).astype(jnp.float32)
    i_kda = i_mla = 0
    for i, kind in enumerate(layer_kinds(c)):
        lp = params["layers"][i]
        x = rms_norm(h, lp["attn_norm"], c.rms_norm_eps)
        if kind == "kda":
            with jax.named_scope("kda"):
                keep = ~fresh
                s0 = jnp.where(keep[:, None, None, None], state["s"][i_kda][lane], 0.0)
                tail0 = jnp.where(keep[:, None, None], state["conv"][i_kda][lane], 0)
                y, s1, tail1 = kda_mixer(lp, c, x, valid, s0, tail0, above)
                s_out[i_kda] = state["s"][i_kda].at[s_back].set(s1, mode="drop")
                conv_out[i_kda] = state["conv"][i_kda].at[back].set(tail1, mode="drop")
            i_kda += 1
        else:
            with jax.named_scope("mla"):
                pool = _write_latent(pool, i_mla, mla_latent(lp, c, x), positions, block_tables)
                y = mla_attend(lp, c, x, _gather_latent(pool, i_mla, block_tables), mask)
            i_mla += 1
        h = h + y
        y, stats = feed_forward(lp, c, i, rms_norm(h, lp["mlp_norm"], c.rms_norm_eps), valid)
        h = h + y
        counters = counters + stats
    h = rms_norm(h, params["final_norm"], c.rms_norm_eps)
    resets = jnp.sum(fresh & (lanes < slots))
    advanced = valid.sum(axis=1)  # a KDA layer's kernel advances each row by its valid tokens
    begins = advanced > 0  # a lane's first row of the group: where its state goes in and out
    if above is not None:
        begins &= ~above
    own = jnp.stack([i_kda * advanced.sum(), i_kda * jnp.sum(begins), resets,
                     jnp.int32(0) if above is None else jnp.sum(above)]).astype(jnp.int32)
    return (h, {"latent": pool}, {"s": tuple(s_out), "conv": tuple(conv_out)},
            jnp.concatenate([counters, own]))


def decode(
    params: Params, config: KimiLinearConfig, tokens: jax.Array, positions: jax.Array,
    kv_cache: KVCache, block_tables: jax.Array, state: SlotState, steps: int, max_pos: int,
    sample, carry,
):
    """``steps`` tokens of every slot (``tokens``, ``positions`` ``[S]``;
    position < 0 = the slot does not decode, and its state stays as it is).

    The MLA layers' history is gathered ONCE a dispatch, the lanes longest
    first in blocks (``ops/latent.py:live_latents``), and a step attends what
    of it is live: a block of lanes the tiles up to its longest lane's
    (:func:`decode_history_tiles` is the count). A step's latent goes to a
    small ``[S, steps, D]`` buffer a layer; the step attends the lane's tiles
    and the buffer's rows up to its own (``attend_absorbed_live``), and the
    pool takes the buffers after the loop in one scatter a layer.
    ``sample(logits [S, V], positions, carry, k) -> (next tokens [S], carry,
    outputs)`` is the engine's. Returns (tokens, positions, carry, the stacked outputs, pool,
    state, counters ``[len(COUNTERS)]``)."""
    c = config
    kinds = layer_kinds(c)
    pool = kv_cache["latent"]
    n_mla = kinds.count("mla")
    live = live_latents(pool, n_mla, block_tables, positions)

    def step(loop, k):
        toks, pos, carry, s_all, conv_all, recent, counters = loop
        valid = (pos >= 0)[:, None]
        s_all, conv_all, recent = list(s_all), list(conv_all), list(recent)
        h = embed_lookup(params, toks, c.dtype).astype(jnp.float32)[:, None]
        i_kda = i_mla = 0
        for i, kind in enumerate(kinds):
            lp = params["layers"][i]
            x = rms_norm(h, lp["attn_norm"], c.rms_norm_eps)
            if kind == "kda":
                with jax.named_scope("kda"):
                    y, s_all[i_kda], conv_all[i_kda] = kda_mixer(
                        lp, c, x, valid, s_all[i_kda], conv_all[i_kda])
                i_kda += 1
            else:
                with jax.named_scope("mla"):
                    ends, dims = _absorbed(lp, c, x)
                    y, recent[i_mla] = attend_absorbed_live(
                        *ends, live, i_mla, recent[i_mla], mla_latent(lp, c, x), k, pos >= 0, *dims)
                i_mla += 1
            h = h + y
            y, stats = feed_forward(lp, c, i, rms_norm(h, lp["mlp_norm"], c.rms_norm_eps), valid)
            h = h + y
            counters = counters + stats
        h = rms_norm(h, params["final_norm"], c.rms_norm_eps)
        nxt, carry, out = sample(lm_head(params, c, h)[:, 0], pos, carry, k)
        new_pos = jnp.where((pos >= 0) & (pos < max_pos), pos + 1, -1)
        return ((nxt, new_pos, carry, tuple(s_all), tuple(conv_all), tuple(recent), counters),
                (out, pos))

    (toks, pos, carry, s_all, conv_all, recent, counters), (out, at) = jax.lax.scan(
        step,
        (tokens, positions, carry, state["s"], state["conv"],
         recent_latents(pool, n_mla, tokens.shape[0], steps), jnp.zeros((MOE_COUNTERS,), jnp.int32)),
        jnp.arange(steps))
    for j, lat in enumerate(recent):  # [S, steps, D], written at `at` [steps, S]
        pool = _write_latent(pool, j, lat, at.T, block_tables)
    return (toks, pos, carry, out, {"latent": pool}, {"s": s_all, "conv": conv_all},
            jnp.concatenate([counters, jnp.zeros((len(COUNTERS) - MOE_COUNTERS,), jnp.int32)]))
