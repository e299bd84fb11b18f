"""The plain reference of the Qwen3-Next decoder (``model_type: qwen3_next``):
Gated DeltaNet layers beside gated softmax attention layers (every
``full_attention_interval``-th), and after EVERY mixer 512 routed experts, 10 a
token by softmax, beside one shared expert behind its own gate; an untied head.

The yardstick's own: nothing here is imported from the program or from
``dynamo_tpu/ops``. One sequence, the whole prompt at once, no cache, no pages,
no chunks, no slots, no kernel, no sorting of tokens by expert: the recurrence
is a ``lax.scan`` over the tokens from a zero state, and every HELD expert is
computed for EVERY token, one expert at a time, and weighed by what the router
gave the token for it (zero where it was not chosen). Every product in float32
at the highest precision, over the weights as the program holds them
(``models/qwen3_next.py:init_params`` names the leaves: ``layers`` a tuple of
per-layer trees, matrices ``[in, out]``, the experts ``[X, in, out]``). A
weight is widened to float32 where it is multiplied, one matrix at a time. The
sizes come from the configuration's published ``config.json`` keys.

The equations are ISSUE 48's (from memory of the published
``modeling_qwen3_next.py``; the configuration's ``assumed`` lists each):

- ``Norm(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)``: the weight is
  ZERO-centred, for the two norms a layer, the final norm and the head norms of
  q and k; the norm inside DeltaNet has a plain weight;
- the experts held are ``w_gate.shape[0]`` of the ``num_experts_published`` the
  router scores, ids 0 on: what the absent experts would add is left out (the
  model-configs guide, section 4), the 10 weights renormalised over all 10,
  and that partial sum goes on to the next layer; the shared expert is whole;
- the softmax runs over ALL the router's logits BEFORE the choice.

Departures from the published code: the projections' column order is the
program's own (``q | k | v | z`` and ``b | a`` in blocks, ``[q | gate]`` a
head; a permutation of the published one, which only a checkpoint loader must
know); the convolution's taps are held ``[K, C]`` (published ``[C, 1, K]``);
the loop over experts is a ``lax.scan`` over their stacked matrices; no
multi-token-prediction module.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6


def _f32(a):
    return a.astype(jnp.float32)


def _dot(x, w):
    return jnp.dot(x, _f32(w), precision=HIGHEST)


def _norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + _f32(weight))


def is_attention(shape: dict, layer: int) -> bool:
    kinds = shape.get("layer_types")
    if kinds:
        return kinds[layer] == "full_attention"
    return (layer + 1) % shape["full_attention_interval"] == 0


def gdn_mixer(lp: dict, shape: dict, u, dot=_dot):
    """``u`` ``[T, E]`` normed. Token by token from ``S = 0``:
    ``S = exp(g_t) S``; ``u_t = (v_t - S^T k_t) beta_t``; ``S = S + k_t u_t^T``;
    ``o_t = S^T q_t``; a head's ``S`` is ``[d_k, d_v]``."""
    hk, hv = shape["linear_num_key_heads"], shape["linear_num_value_heads"]
    dk, dv, kk = shape["linear_key_head_dim"], shape["linear_value_head_dim"], shape["linear_conv_kernel_dim"]
    t, key_dim, value_dim = u.shape[0], hk * dk, hv * dv
    conv_dim = 2 * key_dim + value_dim
    qkvz, ba = dot(u, lp["w_qkvz"]), dot(u, lp["w_ba"])
    # causal depthwise convolution over q, k and v together, zeros before the start, no bias, then silu
    seq = jnp.concatenate([jnp.zeros((kk - 1, conv_dim), jnp.float32), qkvz[:, :conv_dim]])
    mixed = jax.nn.silu(sum(seq[j:j + t] * _f32(lp["conv_w"])[j] for j in range(kk)))
    q = mixed[:, :key_dim].reshape(t, hk, dk)
    k = mixed[:, key_dim:2 * key_dim].reshape(t, hk, dk)
    v = mixed[:, 2 * key_dim:].reshape(t, hv, dv)
    z = qkvz[:, conv_dim:].reshape(t, hv, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS) * dk ** -0.5
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    q, k = (jnp.repeat(a, hv // hk, axis=1) for a in (q, k))  # key head n serves value heads 2n, 2n + 1
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(_f32(lp["a_log"])) * jax.nn.softplus(ba[:, hv:] + _f32(lp["dt_bias"]))  # [T, H_v]

    def token(s, xs):  # s: [H_v, d_k, d_v]
        q, k, v, g, beta = xs
        s = jnp.exp(g)[:, None, None] * s
        delta = (v - jnp.sum(s * k[:, :, None], axis=1)) * beta[:, None]
        s = s + k[:, :, None] * delta[:, None, :]
        return s, jnp.sum(s * q[:, :, None], axis=1)

    _, o = jax.lax.scan(token, jnp.zeros((hv, dk, dv), jnp.float32), (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + shape["rms_norm_eps"]) * _f32(lp["o_norm"])
    return dot((o * jax.nn.silu(z)).reshape(t, value_dim), lp["wo"])


def _rope_part(x, theta: float, rotary: int):
    """The half-split rotation of the first ``rotary`` channels of ``x`` ``[T,
    H, D]`` at positions 0 .. T-1; the other channels pass as they are."""
    t = x.shape[0]
    freqs = theta ** (-jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs  # [T, rotary / 2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :rotary // 2], x[..., rotary // 2:rotary]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotary:]], axis=-1)


def attention_mixer(lp: dict, shape: dict, u, dot=_dot):
    """Causal softmax attention, query head ``n`` over key/value head ``n //
    (heads / kv_heads)``; ``[q | gate]`` a head from one projection; q and k
    normed over each head (zero-centred, one weight of ``D`` for all heads),
    then rotated in their first ``D x partial_rotary_factor`` channels; the
    output times ``sigmoid(gate)``; no bias."""
    heads, kv_heads, d = shape["num_attention_heads"], shape["num_key_value_heads"], shape["head_dim"]
    t, eps, theta = u.shape[0], shape["rms_norm_eps"], float(shape["rope_theta"])
    rotary = int(d * shape["partial_rotary_factor"])
    qg = dot(u, lp["wq"]).reshape(t, heads, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    q = _rope_part(_norm(q, lp["q_norm"], eps), theta, rotary)
    k = _rope_part(_norm(dot(u, lp["wk"]).reshape(t, kv_heads, d), lp["k_norm"], eps), theta, rotary)
    v = dot(u, lp["wv"]).reshape(t, kv_heads, d)
    k, v = (jnp.repeat(a, heads // kv_heads, axis=1) for a in (k, v))
    scores = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST) * d ** -0.5
    probs = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hts,shd->thd", probs, v, precision=HIGHEST)
    return dot((out * jax.nn.sigmoid(gate)).reshape(t, heads * d), lp["wo"])


def swiglu(x, w_gate, w_up, w_down, dot=_dot):
    return dot(jax.nn.silu(dot(x, w_gate)) * dot(x, w_up), w_down)


def route(lp: dict, shape: dict, x):
    """Weights ``[T, experts published]`` float32, zero where a token did not
    choose the expert: softmax over all the logits, the
    ``num_experts_per_tok`` largest chosen, a chosen expert weighing its
    probability over the sum of the chosen ones (``norm_topk_prob``). Always
    float32 (the control leaves the router as it is: a near-tie decides which
    expert computes, and a deployment one precision down keeps its router)."""
    probs = jax.nn.softmax(_dot(x, lp["router"]), axis=-1)
    chosen, ids = jax.lax.top_k(probs, shape["num_experts_per_tok"])
    if shape.get("norm_topk_prob", True):
        chosen = chosen / chosen.sum(axis=-1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, ids].set(chosen)


def expert_layer(lp: dict, shape: dict, x, dot=_dot, first_expert: int = 0, shared: bool = True):
    """The held experts' part of the routed sum, one expert at a time (ids
    ``first_expert`` on), plus (``shared``) the shared expert behind its
    gate."""
    def one(y, xs):
        w_gate, w_up, w_down, weight = xs  # weight: [T]
        return y + weight[:, None] * swiglu(x, w_gate, w_up, w_down, dot), None

    held = lp["w_gate"].shape[0]
    weights = route(lp, shape, x)[:, first_expert:first_expert + held]  # [T, held]
    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (lp["w_gate"], lp["w_up"], lp["w_down"], weights.T))
    if shared:
        gate = jax.nn.sigmoid(_dot(x, lp["shared_gate"][:, None]))
        y = y + gate * swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"], dot)
    return y


def logits(params: dict, shape: dict, tokens, at, dot=_dot) -> jax.Array:
    """Float32 logits ``[len(at), vocab rows held]`` of the next token at the
    positions ``at`` of the sequence ``tokens`` (``[T]`` token ids). ``dot`` is
    the product against a weight matrix; only the control of ``correct``
    (reference_control_qwen3_next.py) passes another."""
    eps = shape["rms_norm_eps"]
    assert shape["num_hidden_layers"] == len(params["layers"]), len(params["layers"])
    x = _f32(params["embed"][tokens])
    for i, lp in enumerate(params["layers"]):
        mixer = attention_mixer if is_attention(shape, i) else gdn_mixer
        x = x + mixer(lp, shape, _norm(x, lp["mixer_norm"], eps), dot)
        x = x + expert_layer(lp, shape, _norm(x, lp["ffn_norm"], eps), dot)
    x = _norm(x[at], params["final_norm"], eps)
    head = params["embed"].T if shape.get("tie_word_embeddings", False) else params["lm_head"]
    return dot(x, head)
