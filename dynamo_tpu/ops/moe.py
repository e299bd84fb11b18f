"""Sparse mixture-of-experts MLP with expert parallelism over the ``ep``
mesh axis.

GShard/Switch-style static dispatch, which is the TPU-native shape for
MoE: top-k routing becomes a one-hot dispatch tensor with a fixed per-
expert capacity, expert batches form via einsum (no dynamic shapes, no
host control flow), each expert's FFN runs with the expert axis sharded
over ``ep`` (XLA inserts the all-to-alls at the dispatch/combine
einsums), and outputs recombine weighted by the router probabilities.
Tokens overflowing an expert's capacity fall through with zero
contribution from that expert (standard capacity-factor semantics).

The reference has NO expert parallelism (SURVEY.md §2.12: EP absent —
a DeepSeek config tweak only); this module is the TPU-native extension
completing the dp/pp/tp/sp/ep mesh story. Sharding follows the standard
recipe: annotate the expert axis (parallel/mesh.py logical rule
``experts`` → ep), let GSPMD place the collectives.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.ops.pallas.grouped_product import grouped_product, make_schedule


@dataclass(frozen=True)
class MoeConfig:
    hidden_size: int
    intermediate_size: int  # per-expert FFN width
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25

    def capacity(self, n_tokens: int) -> int:
        """Static per-expert token capacity for an n_tokens batch."""
        c = math.ceil(n_tokens * self.top_k / self.num_experts * self.capacity_factor)
        return max(self.top_k, c)


def init_moe_params(rng: jax.Array, cfg: MoeConfig, dtype=jnp.bfloat16) -> Dict[str, Any]:
    e, f, x = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
    ks = jax.random.split(rng, 4)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)

    return {
        "router": dense(ks[0], (e, x), e).astype(jnp.float32),
        "w_gate": dense(ks[1], (x, e, f), e),
        "w_up": dense(ks[2], (x, e, f), e),
        "w_down": dense(ks[3], (x, f, e), f),
    }


def moe_param_logical_axes() -> Dict[str, Tuple[Optional[str], ...]]:
    """Logical sharding per leaf (resolved by parallel/mesh.py): the expert
    axis shards over ep, the FFN width over tp — ep × tp compose."""
    return {
        "router": ("embed", None),  # tiny; replicated
        "w_gate": ("experts", "embed", "mlp"),
        "w_up": ("experts", "embed", "mlp"),
        "w_down": ("experts", "mlp", "embed"),
    }


def _expert_mat(x: jax.Array, w, pattern: str) -> jax.Array:
    """Expert-batched einsum against a plain or int8 ``{"q","s"}`` weight.

    Scales are per (expert, out-channel) — ``[X, out]`` — and the batched
    patterns here all produce ``[X, C, out]``, so one broadcast rule
    (``s[:, None, :]``) covers gate/up/down. Same quantization contract as
    models/llama.py ``matw``: int8 load converts inline (the decode weight
    stream halves), scales multiply in f32."""
    if isinstance(w, dict):
        y = jnp.einsum(pattern, x, w["q"].astype(x.dtype))
        return (y.astype(jnp.float32) * w["s"][:, None, :]).astype(x.dtype)
    return jnp.einsum(pattern, x, w)


def moe_mlp(
    params: Dict[str, Any],
    cfg: MoeConfig,
    x: jax.Array,  # [B, T, E]
    *,
    router_noise_key: Optional[jax.Array] = None,
    token_valid: Optional[jax.Array] = None,  # [B, T] bool; None = all valid
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Sparse MoE FFN. Returns (output [B, T, E], aux) where aux carries the
    load-balancing loss term and routing stats.

    ``router_noise_key`` adds train-time exploration noise; None (serving)
    routes deterministically. ``token_valid`` masks padding tokens OUT of
    routing entirely — the serving engine's batches are padded to static
    shapes, and identically-zero padding rows would otherwise all route to
    the same experts and burn their capacity ahead of real tokens (dropping
    real tokens' expert contributions).
    """
    b, t, e = x.shape
    n = b * t
    xe = cfg.num_experts
    cap = cfg.capacity(n)
    xt = x.reshape(n, e)

    logits = (xt.astype(jnp.float32)) @ params["router"]  # [N, X]
    if router_noise_key is not None:
        logits = logits + jax.random.normal(router_noise_key, logits.shape) * 0.01
    probs = jax.nn.softmax(logits, axis=-1)

    # top-k expert choices per token, renormalized over the chosen experts
    top_p, top_idx = jax.lax.top_k(probs, cfg.top_k)  # [N, K]
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # position of each (token, choice) within its expert's capacity:
    # one-hot over experts per choice rank, cumsum over tokens. Later
    # choice ranks stack after earlier ones (k-major ordering).
    onehot = jax.nn.one_hot(top_idx, xe, dtype=jnp.int32)  # [N, K, X]
    if token_valid is not None:
        valid_n = token_valid.reshape(n).astype(jnp.int32)
        onehot = onehot * valid_n[:, None, None]  # padding claims no slot
    prio = onehot.transpose(1, 0, 2).reshape(cfg.top_k * n, xe)  # k-major
    pos_flat = jnp.cumsum(prio, axis=0) - prio  # arrival index per expert
    pos = pos_flat.reshape(cfg.top_k, n, xe).transpose(1, 0, 2)  # [N, K, X]
    within = (pos < cap) & (onehot > 0)

    # dispatch [N, X, C]: routes token n to its expert slot; combine adds
    # the router weight
    slot = jnp.where(within, pos, cap)  # [N, K, X]; cap = dropped
    disp_k = jax.nn.one_hot(slot, cap + 1, dtype=jnp.float32)[..., :cap]  # [N,K,X,C]
    dispatch = disp_k.sum(axis=1)  # [N, X, C] (an expert appears once per token)
    combine = (disp_k * top_p[:, :, None, None]).sum(axis=1)  # [N, X, C]

    # expert batches; the X axis is sharded over ep (GSPMD all-to-all)
    expert_in = jnp.einsum("nxc,ne->xce", dispatch.astype(x.dtype), xt)
    gate = jax.nn.silu(
        _expert_mat(expert_in, params["w_gate"], "xce,xef->xcf").astype(jnp.float32)
    ).astype(x.dtype)
    up = _expert_mat(expert_in, params["w_up"], "xce,xef->xcf")
    expert_out = _expert_mat(gate * up, params["w_down"], "xcf,xfe->xce")

    out = jnp.einsum("nxc,xce->ne", combine.astype(x.dtype), expert_out)

    # GShard aux loss: mean fraction routed x mean router prob, per expert —
    # averaged over VALID tokens only (padding rows all route identically
    # and would both dilute frac and skew imp toward the zero vector's
    # favorite expert)
    routed = within.any(axis=-1).astype(jnp.float32)  # [N, K]
    if token_valid is not None:
        vf = token_valid.reshape(n).astype(jnp.float32)
        nv = jnp.maximum(vf.sum(), 1.0)
        frac = onehot.sum(axis=1).astype(jnp.float32).sum(axis=0) / nv  # [X]
        imp = (probs * vf[:, None]).sum(axis=0) / nv
        dropped = 1.0 - (routed * vf[:, None]).sum() / (nv * cfg.top_k)
    else:
        frac = onehot.sum(axis=1).astype(jnp.float32).mean(axis=0)  # [X]
        imp = probs.mean(axis=0)
        dropped = 1.0 - routed.mean()
    aux = {
        "load_balancing_loss": (frac * imp).sum() * xe,
        "dropped_fraction": dropped,
    }
    return out.reshape(b, t, e), aux


def moe_mlp_reference(params, cfg: MoeConfig, x: jax.Array) -> jax.Array:
    """Dense per-token reference (no capacity, no drops) for parity tests:
    every token gets its exact top-k mixture."""
    b, t, e = x.shape
    xt = x.reshape(-1, e)
    logits = xt.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, cfg.top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    def ffn(xe_, wi):  # all experts for one token, then select
        gate = jax.nn.silu(
            jnp.einsum("e,xef->xf", xe_, params["w_gate"]).astype(jnp.float32)
        ).astype(x.dtype)
        up = jnp.einsum("e,xef->xf", xe_, params["w_up"])
        return jnp.einsum("xf,xfe->xe", gate * up, params["w_down"])

    all_out = jax.vmap(ffn, in_axes=(0, None))(xt, None)  # [N, X, E]
    sel = jnp.take_along_axis(all_out, top_idx[:, :, None], axis=1)  # [N, K, E]
    out = (sel * top_p[:, :, None].astype(x.dtype)).sum(axis=1)
    return out.reshape(b, t, e)


# -- a dropless expert layer that holds a share of the experts ----------------
#
# The layer above drops what overflows a fixed capacity, which no published
# expert model does. The functions below drop nothing and are told which
# experts they hold: the router scores ALL experts and picks ``top_k`` of
# them, and ``dropless_experts`` computes the part of the result that the held
# experts give (models/kimi_linear.py; the model-configs guide, section 4).
# What the absent experts would add is left out; on several chips the partial
# sums add up (tests/test_kimi_linear.py holds the shares to the whole).

def route_sigmoid_topk(
    x: jax.Array,  # [T, E]
    router: jax.Array,  # [E, X] float32, X = every expert of the model
    bias: jax.Array,  # [X] float32: the selection bias
    top_k: int,
    scale: float,
    renormalize: bool = True,
    renormalize_eps: float = 1e-20,
) -> Tuple[jax.Array, jax.Array]:
    """Sigmoid scores in float32 at the highest precision (a near-tie decides
    which expert computes); the ``top_k`` largest of score + bias are chosen,
    and a chosen expert weighs ``scale * score / (sum of the chosen scores +
    renormalize_eps)``: the bias moves the choice and never the weight, and the
    epsilon is the published code's of the model's family (1e-20 Kimi's, 1e-6
    LFM2's). Returns (ids ``[T, k]`` int32, weights ``[T, k]`` float32)."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router, precision=jax.lax.Precision.HIGHEST))
    _, ids = jax.lax.top_k(scores + bias, top_k)
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    if renormalize:
        chosen = chosen / (chosen.sum(axis=-1, keepdims=True) + renormalize_eps)
    return ids.astype(jnp.int32), chosen * scale


def route_softmax_topk(
    x: jax.Array,  # [T, E]
    router: jax.Array,  # [E, X] float32, X = every expert of the model
    top_k: int,
    renormalize: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Softmax over ALL ``X`` logits in float32 at the highest precision (a
    near-tie decides which expert computes), THEN the ``top_k`` largest (of
    equal scores the lower id, ``lax.top_k``'s order); a chosen expert weighs
    its probability, divided by the sum of the chosen ones where
    ``renormalize`` (Qwen3-Next's ``norm_topk_prob``). No selection bias, no
    scale. Returns (ids ``[T, k]`` int32, weights ``[T, k]`` float32)."""
    probs = jax.nn.softmax(jnp.dot(
        x.astype(jnp.float32), router, precision=jax.lax.Precision.HIGHEST), axis=-1)
    chosen, ids = jax.lax.top_k(probs, top_k)
    if renormalize:
        chosen = chosen / chosen.sum(axis=-1, keepdims=True)
    return ids.astype(jnp.int32), chosen


def _parts(x: jax.Array, dtype) -> List[jax.Array]:
    """``x`` as the arrays, in the weights' ``dtype``, whose products against
    a weight add up to ``x``'s: one rounding, for a caller that brings none."""
    return [x.astype(dtype)]


def rows_per_tile(n_tokens: int, top_k: int, num_experts_total: int) -> int:
    """Rows of activations in one tile of :func:`dropless_experts`' products:
    what even routing gives an expert, in sixteens (a bfloat16 tile's rows)
    and 64 at most. A tile is computed whole once an expert with a row in it,
    so a tile much longer than a run computes mostly other experts' rows."""
    even = n_tokens * top_k / num_experts_total
    return min(64, 16 * max(1, math.ceil(even / 16)))


def shard_pairs(ids: jax.Array, valid: jax.Array, experts_a_shard: int, shards: int) -> jax.Array:
    """``[shards]`` int32: of the pairs ``ids`` ``[T, k]`` routes (tokens not
    ``valid`` ``[T]`` route none), how many go to each shard of a layer whose
    experts lie ``experts_a_shard`` a shard in order of their ids (expert
    parallelism: ``models/mellum.py``). The router is whole on every shard, so
    every shard counts every shard's pairs and no collective carries them; the
    shard with the most is the one the layer's all-reduce waits for."""
    shard = jnp.where(valid[:, None], ids // experts_a_shard, shards).reshape(-1)
    return jnp.zeros((shards + 1,), jnp.int32).at[shard].add(1)[:shards]


@functools.partial(jax.jit, static_argnames=(
    "first_expert", "num_experts_total", "parts_of", "experts_a_layer"))
def dropless_experts(
    x: jax.Array,  # [T, E] float32
    ids: jax.Array,  # [T, k] expert ids over ALL experts
    weights: jax.Array,  # [T, k] float32
    w_gate: jax.Array,  # [X, E, F] the held experts, ids first_expert ...
    w_up: jax.Array,
    w_down: jax.Array,  # [X, F, E]
    *,
    first_expert: int = 0,
    num_experts_total: Optional[int] = None,
    token_valid: Optional[jax.Array] = None,  # [T] bool; False = padding
    parts_of: Callable[[jax.Array, Any], List[jax.Array]] = _parts,
    stacked_at: Optional[jax.Array] = None,  # the layer of a stack ``[L * X, ...]`` of weights
    experts_a_layer: Optional[int] = None,  # X of such a stack
) -> Tuple[jax.Array, jax.Array]:
    """``sum over the chosen experts held here of weight * E_e(x)`` for every
    token, ``[T, E]``, and the counters ``[6]`` int32 (1, pairs computed here,
    held experts with a row, pairs routed, rows the products ran over, expert
    reads).

    The (token, expert) pairs routed to a held expert are sorted by expert, so
    that an expert's rows are a run, and each of the three products is ONE
    grouped product over the sorted rows (ops/pallas/grouped_product.py): a
    row meets its own expert's matrix only, a tile of ``rows_per_tile`` rows
    past the last held pair is never computed, and an expert without a row is
    never read. No pair is dropped whatever the routing: the rows are all
    ``T * k`` pairs at most. ``parts_of(a, dtype)`` is the caller's arithmetic:
    the arrays whose products against a weight of ``dtype``, taken in the
    arrays' own dtype and summed in float32, are the product of the float32
    ``a`` (a model whose next router reads this layer's output brings three
    bfloat16 parts, models/kimi_linear.py). The last two counters are the
    products' schedule: its visits x the rows of a tile (a tile that spans
    several runs is computed once a run), and the runs it holds (the tiles of
    a run follow each other, so a hit expert's matrix is read once).

    A caller whose layers' weights are ONE stack (a ``lax.scan`` over layers:
    ``models/mellum.py``) hands the stack whole, ``[L * experts_a_layer, ...]``,
    and the layer this call computes as ``stacked_at`` (traced): the products'
    schedule then names the layer's experts where they lie in the stack, and
    nothing is sliced out of it (a slice of a stack in front of the kernel is a
    copy of the layer's matrices on the chip: ``models/lfm2.py``)."""
    t, k = ids.shape
    x_held = experts_a_layer or w_gate.shape[0]
    n_pairs = t * k
    r = rows_per_tile(t, k, num_experts_total or x_held)
    local = ids - first_expert
    held = (local >= 0) & (local < x_held)
    if token_valid is not None:
        held = held & token_valid[:, None]
    expert = jnp.where(held, local, x_held).reshape(n_pairs)  # x_held: not here
    order = jnp.argsort(expert, stable=True)  # the pairs, an expert's together
    counts = jnp.zeros((x_held + 1,), jnp.int32).at[expert].add(1)[:x_held]
    place = jnp.zeros((n_pairs,), jnp.int32).at[order].set(jnp.arange(n_pairs, dtype=jnp.int32))
    rows = -(-n_pairs // r) * r  # whole tiles
    token_of = jnp.pad((order // k).astype(jnp.int32), (0, rows - n_pairs))
    schedule = make_schedule(counts, rows, r)
    visits = schedule[3]
    if stacked_at is not None:
        # the layer's experts are groups `at` .. `at + x_held - 1` of the stack: the visits name
        # them there, and the rows' offsets stand where the kernel looks a group's up
        at = stacked_at * x_held
        offsets = jax.lax.dynamic_update_slice(
            jnp.zeros((w_gate.shape[0] + 1,), jnp.int32), schedule[0], (at,))
        schedule = (offsets, schedule[1] + at, schedule[2], visits)

    def product(a, w):
        """Sorted rows ``a`` ``[rows, K]`` float32, each against its expert's
        ``[K, N]``: ``[rows, N]`` float32 (past the held pairs: anything)."""
        parts = jnp.stack(parts_of(a, w.dtype))
        return grouped_product(parts, w.astype(parts.dtype), schedule, rows_per_tile=r,
                               interpret=jax.default_backend() == "cpu")

    xs = x[token_of]
    hidden = jax.nn.silu(product(xs, w_gate)) * product(xs, w_up)
    ys = product(hidden, w_down)
    got = ys[place.reshape(t, k)]  # [T, k, E]: each pair takes its row back
    y = jnp.sum(jnp.where(held[..., None], weights[..., None] * got, 0.0), axis=1)

    routed = (jnp.sum(token_valid) if token_valid is not None else t) * k
    hit = jnp.sum(counts > 0)
    stats = jnp.stack([jnp.int32(1), counts.sum(), hit, jnp.asarray(routed, jnp.int32),
                       visits * r, hit]).astype(jnp.int32)
    return y.astype(x.dtype), stats
