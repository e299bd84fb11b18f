"""The plain reference of the Jamba decoder (``model_type: jamba``): Mamba-1
mixers with Jamba's three inner norms beside a few attention layers without
any positional encoding, dense gated feed-forwards, a tied head.

The yardstick's own: nothing here is imported from the program. One sequence,
token by token through every recurrence, no cache, no pages, no chunks, no
slots; every product in float32 at the highest precision, over the weights as
the program holds them (``models/jamba.py:init_params`` names the leaves:
``mamba`` is a tuple of runs of consecutive Mamba layers, each a tree stacked
on a leading layer axis, ``attn`` a tuple of attention layers, matrices
``[in, out]``). A weight is widened to float32 where it is multiplied, one
matrix at a time, so that 6 GB of bf16 weights and this pass fit one chip
together. The sizes come from the configuration's published ``config.json``
keys (:func:`sizes`).

The equations are the slow path of the published ``modeling_jamba.py``
(ISSUE 41 writes them out). Departures, each noted where it is made:

- ``a_log`` is held ``[N, D]``, the published ``A_log`` transposed (the
  program's state is ``[N, D]`` a slot: a minor axis of 16 pads to 128 lanes);
- the published loop multiplies the state by ``C`` in the model's dtype
  (``ssm_state.to(dtype)``); here it stays float32, as the published kernels
  keep it;
- the runs of Mamba layers go through ``lax.scan`` over their stacked weights
  (one layer's mathematics, traced once); nothing else is batched.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _f32(a):
    return a.astype(jnp.float32)


def _dot(x, w):
    return jnp.dot(x, _f32(w), precision=HIGHEST)


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(weight)


def sizes(shape: dict) -> dict:
    """The Mamba mixer's sizes and the kind of every layer, as the published
    code reads the config: layer ``i`` (0-based) is attention where
    ``i % attn_layer_period == attn_layer_offset``."""
    heads = shape["num_attention_heads"]
    return {
        "d_inner": shape["mamba_expand"] * shape["hidden_size"],
        "d_state": shape["mamba_d_state"],
        "dt_rank": shape["mamba_dt_rank"],
        "d_conv": shape["mamba_d_conv"],
        "head_dim": shape.get("head_dim") or shape["hidden_size"] // heads,
        "kinds": tuple(
            "attn" if i % shape["attn_layer_period"] == shape["attn_layer_offset"] else "mamba"
            for i in range(shape["num_hidden_layers"])),
    }


def mamba_mixer(lp: dict, shape: dict, u, dot=_dot):
    """``u`` ``[T, E]`` normed. Per token: ``[x, z] = u W_in``; a causal
    depthwise convolution of width K and silu over x; ``[dt, B, C] = x W_x``,
    each normed; ``delta = softplus(dt W_dt + b_dt)``; ``A = -exp(A_log)``;
    ``s_t = exp(delta_t A) * s_(t-1) + (delta_t x_t) B_t`` from ``s = 0``;
    ``y_t = s_t C_t + D x_t``; out ``(y * silu(z)) W_out``."""
    z, eps = sizes(shape), shape["rms_norm_eps"]
    d, n, r, kk = z["d_inner"], z["d_state"], z["dt_rank"], z["d_conv"]
    t = u.shape[0]
    xz = dot(u, lp["w_in"])
    x, gate = xz[:, :d], xz[:, d:]
    # zeros before position 0; tap K-1 is the token itself
    seq = jnp.concatenate([jnp.zeros((kk - 1, d), jnp.float32), x])
    x = jax.nn.silu(sum(seq[j:j + t] * _f32(lp["conv_w"])[j] for j in range(kk))
                    + _f32(lp["conv_b"]))
    dbc = dot(x, lp["w_x"])
    dt = _rms(dbc[:, :r], lp["dt_norm"], eps)
    b = _rms(dbc[:, r:r + n], lp["b_norm"], eps)
    c = _rms(dbc[:, r + n:], lp["c_norm"], eps)
    delta = jax.nn.softplus(dot(dt, lp["w_dt"]) + _f32(lp["b_dt"]))  # [T, D]
    a = -jnp.exp(_f32(lp["a_log"]))  # [N, D]: the published A_log transposed

    def token(s, xs):  # s: [N, D]
        delta_t, x_t, b_t, c_t = xs
        s = jnp.exp(delta_t[None, :] * a) * s + (delta_t * x_t)[None, :] * b_t[:, None]
        return s, jnp.sum(s * c_t[:, None], axis=0)

    _, y = jax.lax.scan(token, jnp.zeros((n, d), jnp.float32), (delta, x, b, c))
    y = y + _f32(lp["d_skip"]) * x
    return dot(y * jax.nn.silu(gate), lp["w_out"])


def attention_mixer(lp: dict, shape: dict, u, dot=_dot):
    """Causal softmax attention, the query heads over the few key/value heads,
    no rotation and no bias anywhere: position comes from the Mamba layers."""
    z = sizes(shape)
    heads, kv_heads, d = shape["num_attention_heads"], shape["num_key_value_heads"], z["head_dim"]
    t = u.shape[0]
    q = dot(u, lp["wq"]).reshape(t, heads, d)
    k = dot(u, lp["wk"]).reshape(t, kv_heads, d)
    v = dot(u, lp["wv"]).reshape(t, kv_heads, d)
    k, v = (jnp.repeat(a, heads // kv_heads, axis=1) for a in (k, v))
    scores = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST) * d ** -0.5
    probs = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hts,shd->thd", probs, v, precision=HIGHEST)
    return dot(out.reshape(t, heads * d), lp["wo"])


def block(lp: dict, shape: dict, x, mixer, dot=_dot):
    """``h += Mixer(norm(h))``, ``h += MLP(norm(h))``."""
    eps = shape["rms_norm_eps"]
    x = x + mixer(lp, shape, _rms(x, lp["mixer_norm"], eps), dot)
    h = _rms(x, lp["mlp_norm"], eps)
    return x + dot(jax.nn.silu(dot(h, lp["w_gate"])) * dot(h, lp["w_up"]), lp["w_down"])


def logits(params: dict, shape: dict, tokens, at, dot=_dot) -> jax.Array:
    """Float32 logits ``[len(at), vocab]`` of the next token at the positions
    ``at`` of the sequence ``tokens`` (``[T]`` token ids). ``dot`` is the
    product against a weight matrix; only the control of ``correct``
    (reference_control_jamba.py) passes another."""
    kinds = sizes(shape)["kinds"]
    x = _f32(params["embed"][tokens])
    runs, attn, i = iter(params["mamba"]), iter(params["attn"]), 0
    while i < len(kinds):
        if kinds[i] == "attn":
            x = block(next(attn), shape, x, attention_mixer, dot)
            i += 1
            continue
        run = next(runs)  # the Mamba layers up to the next attention layer
        x, _ = jax.lax.scan(lambda x, lp: (block(lp, shape, x, mamba_mixer, dot), None), x, run)
        i += jax.tree.leaves(run)[0].shape[0]
    x = _rms(x[at], params["final_norm"], shape["rms_norm_eps"])
    head = params["embed"].T if shape.get("tie_word_embeddings") else params["lm_head"]
    return dot(x, head)
