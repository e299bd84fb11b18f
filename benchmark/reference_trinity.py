"""The plain reference of the Trinity decoder (``model_type: afmoe``): window
attention layers (rotary, ``sliding_window`` positions) beside full attention
layers (no positional encoding), three to one, both with a norm on every head
of q and k and a sigmoid gate on the attended values; four RMS norms a layer;
a dense gated feed-forward in the first layers and sigmoid-routed experts
beside one shared expert in the rest; embeddings times ``sqrt(hidden)``; an
untied head.

The yardstick's own: nothing here is imported from the program or from
``dynamo_tpu/ops``. One sequence, the whole prompt at once, no cache, no ring,
no pages, no chunks, no slots, no sorting of tokens by expert: attention is the
naive masked softmax over every key of the sequence (a block of
``QUERY_BLOCK`` queries at a time, so that six thousand positions of 48 heads
fit beside the weights; a block's mathematics is the whole's), and every HELD
expert is computed for EVERY token, one expert at a time, and weighed by what
the router gave the token for it (zero where it was not chosen). Every product
in float32 at the highest precision, over the weights as the program holds
them (``models/trinity.py:init_params`` names the leaves: ``layers`` a tuple of
per-layer trees, matrices ``[in, out]``, the experts ``[X, in, out]``). A weight
is widened to float32 where it is multiplied, one matrix at a time. The sizes
come from the configuration's published ``config.json`` keys.

The cut is the program's (``configs/trinity-large-preview.json``,
``deployment``): the router scores all ``num_experts_published`` experts and
chooses ``num_experts_per_tok`` of them; of the chosen, those held here (ids
``first_expert`` .. ``first_expert + num_experts - 1``) compute, and what the
absent ones would have added is left out; the shared expert computes for every
token.

The equations are ISSUE 65's, from the catalog row's ``config`` and, where that
is silent, from ``described_as`` and the published ``modeling_afmoe.py`` as
remembered; the configuration's ``assumed`` lists each such line: the head
norms come before the rotation; the rotation is the half-split form; a window
layer's query at ``p`` sees keys ``p - sliding_window + 1 .. p``; the gate is
``sigmoid(a W_g)`` of the layer's normed input, on the attended values before
the out-projection; the norms stand as ``x += N(Attn(N(x)))``, ``x +=
N(FF(N(x)))``; the router's renormalisation adds 1e-20 to the sum of the chosen
scores; the selection bias moves the choice only. Departure from the published
code: the loop over experts is a ``lax.scan`` over their stacked matrices.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
ROUTER_EPS = 1e-20
QUERY_BLOCK = 512
WINDOW, FULL = "sliding_attention", "full_attention"


def _f32(a):
    return a.astype(jnp.float32)


def _dot(x, w):
    return jnp.dot(x, _f32(w), precision=HIGHEST)


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(weight)


def _rope(x, theta: float):
    """The half-split rotation of ``x`` ``[T, H, D]`` at positions 0 .. T-1."""
    t, _, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs  # [T, D/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attend(q, k, v, window):
    """Softmax attention of ``q`` ``[T, H, D]`` over ``k``, ``v`` ``[T, H,
    D]``: query ``p`` sees keys ``0 .. p``, or with a ``window`` keys ``p -
    window + 1 .. p``; scale ``D ** -0.5``. A block of queries at a time."""
    t, _, d = q.shape
    keys = jnp.arange(t)[None, :]
    out = []
    for start in range(0, t, QUERY_BLOCK):
        at = jnp.arange(start, min(start + QUERY_BLOCK, t))[:, None]
        sees = keys <= at
        if window is not None:
            sees &= keys > at - window
        scores = jnp.einsum("thd,shd->hts", q[start:start + QUERY_BLOCK], k, precision=HIGHEST)
        probs = jax.nn.softmax(jnp.where(sees, scores * d ** -0.5, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hts,shd->thd", probs, v, precision=HIGHEST))
    return jnp.concatenate(out)


def attention(lp: dict, shape: dict, kind: str, a, dot=_dot):
    """``a`` ``[T, E]`` normed. q and k normed over each head (one weight of
    ``D`` for all heads); rotated in a window layer and not in a full one;
    query head ``n`` over key/value head ``n // (heads / kv_heads)``; the
    attended values times ``sigmoid(a W_g)``, then the out-projection."""
    heads, kv_heads, d = shape["num_attention_heads"], shape["num_key_value_heads"], shape["head_dim"]
    t, eps = a.shape[0], shape["rms_norm_eps"]
    q = _rms(dot(a, lp["wq"]).reshape(t, heads, d), lp["q_norm"], eps)
    k = _rms(dot(a, lp["wk"]).reshape(t, kv_heads, d), lp["k_norm"], eps)
    v = dot(a, lp["wv"]).reshape(t, kv_heads, d)
    if kind == WINDOW:
        q, k = _rope(q, float(shape["rope_theta"])), _rope(k, float(shape["rope_theta"]))
    k, v = (jnp.repeat(x, heads // kv_heads, axis=1) for x in (k, v))
    out = attend(q, k, v, shape["sliding_window"] if kind == WINDOW else None)
    return dot(out.reshape(t, heads * d) * jax.nn.sigmoid(dot(a, lp["wg"])), lp["wo"])


def swiglu(x, w_gate, w_up, w_down, dot=_dot):
    return dot(jax.nn.silu(dot(x, w_gate)) * dot(x, w_up), w_down)


def route(lp: dict, shape: dict, x):
    """Weights ``[T, num_experts_published]`` float32, zero where a token did
    not choose the expert: sigmoid scores, the ``num_experts_per_tok`` largest
    of score + selection bias chosen (the bias for the choice only), a chosen
    expert weighing ``route_scale * score / (sum of the chosen scores +
    1e-20)`` (``route_norm``). Always float32 (the control leaves the router
    as it is: a near-tie decides which expert computes)."""
    scores = jax.nn.sigmoid(_dot(x, lp["router"]))
    _, ids = jax.lax.top_k(scores + _f32(lp["e_bias"]), shape["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    if shape.get("route_norm", True):
        chosen = chosen / (chosen.sum(axis=-1, keepdims=True) + ROUTER_EPS)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, ids].set(chosen * shape.get("route_scale", 1.0))


def routed_part(lp: dict, shape: dict, x, dot=_dot):
    """``sum_e weight_e * E_e(x)`` over the experts HELD (``lp["w_gate"]``
    ``[num_experts, ...]``: ids ``first_expert`` onwards), one at a time."""
    def one(y, xs):
        w_gate, w_up, w_down, weight = xs  # weight: [T]
        return y + weight[:, None] * swiglu(x, w_gate, w_up, w_down, dot), None

    first, held = shape.get("first_expert", 0), lp["w_gate"].shape[0]
    weights = route(lp, shape, x)[:, first:first + held]  # [T, held]
    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (lp["w_gate"], lp["w_up"], lp["w_down"], weights.T))
    return y


def shared_part(lp: dict, x, dot=_dot):
    return swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"], dot)


def logits(params: dict, shape: dict, tokens, at, dot=_dot) -> jax.Array:
    """Float32 logits ``[len(at), vocab]`` of the next token at the positions
    ``at`` of the sequence ``tokens`` (``[T]`` token ids). ``dot`` is the
    product against a weight matrix; only the control of ``correct``
    (reference_control_trinity.py) passes another."""
    eps, kinds = shape["rms_norm_eps"], shape["layer_types"]
    assert len(kinds) == shape["num_hidden_layers"] == len(params["layers"]), kinds
    x = _f32(params["embed"][tokens])
    if shape.get("mup_enabled", False):
        x = x * math.sqrt(shape["hidden_size"])
    for i, lp in enumerate(params["layers"]):
        x = x + _rms(attention(lp, shape, kinds[i], _rms(x, lp["in_norm"], eps), dot),
                     lp["post_attn_norm"], eps)
        m = _rms(x, lp["pre_mlp_norm"], eps)
        if i < shape["num_dense_layers"]:
            y = swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"], dot)
        else:
            y = shared_part(lp, m, dot) + routed_part(lp, shape, m, dot)
        x = x + _rms(y, lp["post_mlp_norm"], eps)
    return dot(_rms(x[at], params["final_norm"], eps), params["lm_head"])
