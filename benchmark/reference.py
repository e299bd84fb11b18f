"""The plain reference of the Qwen2 / Llama decoder the configurations name.

The yardstick's own: nothing here is imported from the program. One sequence,
no cache, no paging, no chunks; every product in float32 at the highest
precision, over the weights as the program holds them (a tree of stacked
layers: ``embed``, ``final_norm``, ``layers.{attn_norm, wq, wk, wv, wo,
mlp_norm, w_gate, w_up, w_down, bq, bk, bv}``, ``lm_head`` when the head is
untied; matrices are [in, out]). The sizes come from the configuration's
published ``config.json`` keys.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _f32(a):
    return a.astype(jnp.float32)


def _dot(x, w):
    return jnp.dot(x, _f32(w), precision=HIGHEST)


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(weight)


def _rope(x, cos, sin):
    """HF's rotate_half convention; x: [T, heads, D]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def logits(params: dict, shape: dict, tokens, at, dot=_dot) -> jax.Array:
    """Float32 logits [len(at), vocab] of the next token at the positions
    ``at`` of the sequence ``tokens`` ([T] token ids, causal attention).
    ``dot`` is the product against a weight matrix; only the control of
    ``correct`` (reference_control.py) passes another."""
    heads, kv_heads = shape["num_attention_heads"], shape["num_key_value_heads"]
    d = shape.get("head_dim") or shape["hidden_size"] // heads
    eps, t = shape["rms_norm_eps"], tokens.shape[0]
    inv = shape["rope_theta"] ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def layer(x, lp):
        h = _rms(x, lp["attn_norm"], eps)
        q, k, v = dot(h, lp["wq"]), dot(h, lp["wk"]), dot(h, lp["wv"])
        if "bq" in lp:
            q, k, v = q + _f32(lp["bq"]), k + _f32(lp["bk"]), v + _f32(lp["bv"])
        q = _rope(q.reshape(t, heads, d), cos, sin)
        k = _rope(k.reshape(t, kv_heads, d), cos, sin)
        v = v.reshape(t, kv_heads, d)
        # query head h reads key/value head h // (heads / kv_heads)
        k, v = (jnp.repeat(a, heads // kv_heads, axis=1) for a in (k, v))
        scores = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        attn = jnp.einsum("hts,shd->thd", probs, v, precision=HIGHEST)
        x = x + dot(attn.reshape(t, heads * d), lp["wo"])
        h = _rms(x, lp["mlp_norm"], eps)
        x = x + dot(jax.nn.silu(dot(h, lp["w_gate"])) * dot(h, lp["w_up"]), lp["w_down"])
        return x, None

    x, _ = jax.lax.scan(layer, _f32(params["embed"][tokens]), params["layers"])
    x = _rms(x[at], params["final_norm"], eps)
    head = params["embed"].T if shape.get("tie_word_embeddings") else params["lm_head"]
    return dot(x, head)
