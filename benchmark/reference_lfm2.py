"""The plain reference of the LFM2 expert decoder (``model_type: lfm2_moe``):
gated short convolutions beside rotary grouped-query attention layers with a
norm on every head of q and k, dense gated feed-forwards in the first layers
and 64 routed experts, 4 a token, in the rest; a tied head.

The yardstick's own: nothing here is imported from the program or from
``dynamo_tpu/ops``. One sequence, the whole prompt at once, no cache, no pages,
no chunks, no slots, no sorting of tokens by expert: every expert is computed
for EVERY token, one expert at a time, and weighed by what the router gave the
token for it (zero where it was not chosen). Every product in float32 at the
highest precision, over the weights as the program holds them
(``models/lfm2.py:init_params`` names the leaves: ``layers`` a tuple of
per-layer trees, matrices ``[in, out]``, the experts ``[X, in, out]``). A
weight is widened to float32 where it is multiplied, one matrix at a time, so
that 10.5 GB of bf16 weights and this pass fit one chip together. The sizes
come from the configuration's published ``config.json`` keys.

The equations are ISSUE 43's (from the published ``modeling_lfm2_moe.py``).
What the config does not say and this file sets, as the configuration's
``assumed`` lists: the in-projection's thirds are ``B, C, x`` in this order;
the router's renormalisation adds 1e-6 to the sum of the chosen scores; q and k
are normed BEFORE they are rotated; the head is the embedding's transpose.
Departures from the published code: the convolution's taps are held ``[K, E]``
(published ``[E, 1, K]``); the loop over experts is a ``lax.scan`` over their
stacked matrices (one expert's mathematics, traced once).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
ROUTER_EPS = 1e-6


def _f32(a):
    return a.astype(jnp.float32)


def _dot(x, w):
    return jnp.dot(x, _f32(w), precision=HIGHEST)


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(weight)


def _theta(shape: dict) -> float:
    group = shape.get("rope_parameters") or {}
    return float(group["rope_theta"] if "rope_theta" in group else shape["rope_theta"])


def conv_mixer(lp: dict, shape: dict, u, dot=_dot):
    """``u`` ``[T, E]`` normed. ``[B, C, x] = u W_in``; ``g = B * x``;
    ``c_t = sum_j w[j] * g_(t - (K - 1) + j)`` with zeros before the sequence's
    start, no bias and no activation; out ``(C * c) W_out``."""
    e, kk, t = shape["hidden_size"], shape["conv_L_cache"], u.shape[0]
    bcx = dot(u, lp["w_in"])
    b, c, x = bcx[:, :e], bcx[:, e:2 * e], bcx[:, 2 * e:]
    seq = jnp.concatenate([jnp.zeros((kk - 1, e), jnp.float32), b * x])
    conv = sum(seq[j:j + t] * _f32(lp["conv_w"])[j] for j in range(kk))
    return dot(c * conv, lp["w_out"])


def _rope(x, theta: float):
    """The half-split rotation of ``x`` ``[T, H, D]`` at positions 0 .. T-1."""
    t, _, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs  # [T, D/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention_mixer(lp: dict, shape: dict, u, dot=_dot):
    """Causal softmax attention, query head ``n`` over key/value head ``n //
    (heads / kv_heads)``; q and k normed over each head (one weight of ``D``
    for all heads), then rotated; no bias."""
    heads, kv_heads = shape["num_attention_heads"], shape["num_key_value_heads"]
    d = shape.get("head_dim") or shape["hidden_size"] // heads
    t, eps, theta = u.shape[0], shape["norm_eps"], _theta(shape)
    q = _rope(_rms(dot(u, lp["wq"]).reshape(t, heads, d), lp["q_norm"], eps), theta)
    k = _rope(_rms(dot(u, lp["wk"]).reshape(t, kv_heads, d), lp["k_norm"], eps), theta)
    v = dot(u, lp["wv"]).reshape(t, kv_heads, d)
    k, v = (jnp.repeat(a, heads // kv_heads, axis=1) for a in (k, v))
    scores = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST) * d ** -0.5
    probs = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hts,shd->thd", probs, v, precision=HIGHEST)
    return dot(out.reshape(t, heads * d), lp["wo"])


def swiglu(x, w_gate, w_up, w_down, dot=_dot):
    return dot(jax.nn.silu(dot(x, w_gate)) * dot(x, w_up), w_down)


def route(lp: dict, shape: dict, x):
    """Weights ``[T, X]`` float32, zero where a token did not choose the
    expert: sigmoid scores, the ``num_experts_per_tok`` largest of score +
    selection bias chosen (the bias for the choice only), a chosen expert
    weighing ``routed_scaling_factor * score / (sum of the chosen scores +
    1e-6)``. Always float32 (the control leaves the router as it is: a
    near-tie decides which expert computes, and a deployment one precision
    down keeps its router)."""
    scores = jax.nn.sigmoid(_dot(x, lp["router"]))
    bias = _f32(lp["router_bias"]) if shape.get("use_expert_bias", True) else 0.0
    _, ids = jax.lax.top_k(scores + bias, shape["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    if shape.get("norm_topk_prob", True):
        chosen = chosen / (chosen.sum(axis=-1, keepdims=True) + ROUTER_EPS)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, ids].set(chosen * shape.get("routed_scaling_factor", 1.0))


def expert_layer(lp: dict, shape: dict, x, dot=_dot):
    """``sum_e weight_e * E_e(x)`` over all the experts, one at a time."""
    def one(y, xs):
        w_gate, w_up, w_down, weight = xs  # weight: [T]
        return y + weight[:, None] * swiglu(x, w_gate, w_up, w_down, dot), None

    weights = route(lp, shape, x)  # [T, X]
    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (lp["w_gate"], lp["w_up"], lp["w_down"], weights.T))
    return y


def logits(params: dict, shape: dict, tokens, at, dot=_dot) -> jax.Array:
    """Float32 logits ``[len(at), vocab]`` of the next token at the positions
    ``at`` of the sequence ``tokens`` (``[T]`` token ids). ``dot`` is the
    product against a weight matrix; only the control of ``correct``
    (reference_control_lfm2.py) passes another."""
    eps, kinds = shape["norm_eps"], shape["layer_types"]
    assert len(kinds) == shape["num_hidden_layers"] == len(params["layers"]), kinds
    x = _f32(params["embed"][tokens])
    for i, lp in enumerate(params["layers"]):
        mixer = {"conv": conv_mixer, "full_attention": attention_mixer}[kinds[i]]
        x = x + mixer(lp, shape, _rms(x, lp["operator_norm"], eps), dot)
        h = _rms(x, lp["ffn_norm"], eps)
        if i < shape["num_dense_layers"]:
            x = x + swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], dot)
        else:
            x = x + expert_layer(lp, shape, h, dot)
    x = _rms(x[at], params["final_norm"], eps)
    head = params["embed"].T if shape.get("tie_word_embeddings", True) else params["lm_head"]
    return dot(x, head)
