"""The plain reference of the Kimi-Linear decoder (``model_type: kimi_linear``).

The yardstick's own: nothing here is imported from the program. One sequence,
no cache, no pages, no chunks, no slots; every product in float32 at the
highest precision, over the weights as the program holds them (a tuple of
per-layer trees, matrices ``[in, out]``; ``models/kimi_linear.py:init_params``
names the leaves). The sizes come from the configuration's ``config.json``
keys: the published ones, with ``linear_attn_config`` read nested where the
card has it and flat otherwise (``linear_attn_num_heads``,
``linear_attn_head_dim``, ``short_conv_kernel_size``, ``kda_layers``,
``full_attn_layers``; configs/kimi-linear-48b-a3b.json says why).

Pre-norm residual blocks, ``h += Mixer(norm(h))``, ``h += FFN(norm(h))``, a
final norm and an untied head. The equations are ISSUE 36's; each departure
from the published modeling code is noted where it is made:

- the experts held are ``w_gate.shape[0]`` of the ``num_experts_published``
  the router scores, ids ``first_expert`` on (0 here): what the absent experts
  would add is left out (the model-configs guide, section 4), and that partial
  sum goes on to the next layer;
- q and k of a KDA head are normalised as ``x * rsqrt(sum x^2 + 1e-6)``;
- the held experts are computed for EVERY token, a block of experts at a
  time, and weighted by the token's weight for them (zero where it did not
  choose them): float32 copies of 128 experts at once are 3.6 GB a layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
EXPERT_BLOCK = 16


def _f32(a):
    return a.astype(jnp.float32)


def _dot(x, w):
    return jnp.dot(x, _f32(w), precision=HIGHEST)


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(weight)


def sizes(shape: dict) -> dict:
    """The sizes the layers need, from either spelling of the KDA group."""
    group = shape.get("linear_attn_config") or {}

    def kda(nested, flat):
        return group[nested] if nested in group else shape[flat]

    return {
        "kda_heads": kda("num_heads", "linear_attn_num_heads"),
        "kda_head_dim": kda("head_dim", "linear_attn_head_dim"),
        "conv_kernel": kda("short_conv_kernel_size", "short_conv_kernel_size"),
        "kda_layers": tuple(kda("kda_layers", "kda_layers")),
        "full_attn_layers": tuple(kda("full_attn_layers", "full_attn_layers")),
        "experts_total": shape.get("num_experts_published", shape["num_experts"]),
    }


def kda_mixer(lp: dict, shape: dict, x, dot=_dot):
    """``x`` ``[T, E]`` normed. Token by token:
    ``S_t = (I - b_t k_t k_t^T) Diag(a_t) S_(t-1) + b_t k_t v_t^T``,
    ``o_t = S_t^T q_t``, from ``S = 0``."""
    z = sizes(shape)
    h, d, kk = z["kda_heads"], z["kda_head_dim"], z["conv_kernel"]
    t = x.shape[0]

    def conv_silu(pre, w):
        # causal depthwise convolution over time: tap K-1 is the token itself
        seq = jnp.concatenate([jnp.zeros((kk - 1, pre.shape[1]), jnp.float32), pre])
        return jax.nn.silu(sum(seq[j:j + t] * _f32(w)[j] for j in range(kk)))

    q = conv_silu(dot(x, lp["wq"]), lp["conv_q"]).reshape(t, h, d)
    k = conv_silu(dot(x, lp["wk"]), lp["conv_k"]).reshape(t, h, d)
    v = conv_silu(dot(x, lp["wv"]), lp["conv_v"]).reshape(t, h, d)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * d ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    f = dot(dot(x, lp["wf_down"]), lp["wf_up"]) + _f32(lp["dt_bias"])
    alpha = jnp.exp(-jnp.exp(_f32(lp["a_log"]))[:, None] * jax.nn.softplus(f).reshape(t, h, d))
    beta = jax.nn.sigmoid(dot(x, lp["w_beta"]))  # [T, H]

    def token(s, xs):  # s: [H, d_k, d_v]
        q, k, v, alpha, beta = xs
        s = alpha[:, :, None] * s
        s = s - beta[:, None, None] * k[:, :, None] * jnp.sum(k[:, :, None] * s, axis=1)[:, None, :]
        s = s + beta[:, None, None] * k[:, :, None] * v[:, None, :]
        return s, jnp.sum(q[:, :, None] * s, axis=1)

    _, o = jax.lax.scan(token, jnp.zeros((h, d, d), jnp.float32), (q, k, v, alpha, beta))
    o = _rms(o, lp["o_norm"], shape["rms_norm_eps"])  # per head
    gate = jax.nn.sigmoid(dot(dot(x, lp["wg_down"]), lp["wg_up"]))
    return dot(o.reshape(t, h * d) * gate, lp["wo"])


def mla_mixer(lp: dict, shape: dict, x, dot=_dot):
    """Latent attention without rotation (``mla_use_nope``), keys and values
    expanded from the latent of every position."""
    t = x.shape[0]
    h, r = shape["num_attention_heads"], shape["kv_lora_rank"]
    dn, dr, dv = shape["qk_nope_head_dim"], shape["qk_rope_head_dim"], shape["v_head_dim"]
    q = dot(x, lp["wq"]).reshape(t, h, dn + dr)
    kv = dot(x, lp["w_kva"])
    latent, k_rope = _rms(kv[:, :r], lp["kv_norm"], shape["rms_norm_eps"]), kv[:, r:]
    expanded = dot(latent, lp["w_kvb"]).reshape(t, h, dn + dv)
    k_nope, v = expanded[..., :dn], expanded[..., dn:]
    scores = (jnp.einsum("thd,shd->hts", q[..., :dn], k_nope, precision=HIGHEST)
              + jnp.einsum("thd,sd->hts", q[..., dn:], k_rope, precision=HIGHEST)) * (dn + dr) ** -0.5
    probs = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hts,shd->thd", probs, v, precision=HIGHEST)
    return dot(out.reshape(t, h * dv), lp["wo"])


def swiglu(x, w_gate, w_up, w_down, dot=_dot):
    return dot(jax.nn.silu(dot(x, w_gate)) * dot(x, w_up), w_down)


def route(lp: dict, shape: dict, x):
    """Weights ``[T, experts_total]`` float32, zero where a token did not
    choose the expert: sigmoid scores, the ``num_experts_per_token`` largest
    of score + selection bias chosen, a chosen expert weighing
    ``routed_scaling_factor * score / sum of the chosen scores``. Always
    float32 (the control leaves the router as it is: a near-tie decides which
    expert computes, and a deployment one precision down keeps its router)."""
    scores = jax.nn.sigmoid(_dot(x, lp["router"]))
    _, ids = jax.lax.top_k(scores + _f32(lp["router_bias"]), shape["num_experts_per_token"])
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    if shape.get("moe_renormalize", True):
        chosen = chosen / chosen.sum(axis=-1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, ids].set(chosen * shape["routed_scaling_factor"])


def expert_layer(lp: dict, shape: dict, x, dot=_dot, first_expert: int = 0):
    """The held experts' part of the routed sum, plus the shared expert."""
    held = lp["w_gate"].shape[0]
    weights = route(lp, shape, x)[:, first_expert:first_expert + held]  # [T, held]
    y = swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"], dot)
    for lo in range(0, held, EXPERT_BLOCK):
        block = slice(lo, min(lo + EXPERT_BLOCK, held))
        outs = jax.vmap(lambda g, u, d: swiglu(x, g, u, d, dot))(
            lp["w_gate"][block], lp["w_up"][block], lp["w_down"][block])  # [n, T, E]
        y = y + jnp.einsum("tn,nte->te", weights[:, block], outs, precision=HIGHEST)
    return y


def logits(params: dict, shape: dict, tokens, at, dot=_dot) -> jax.Array:
    """Float32 logits ``[len(at), vocab]`` of the next token at the positions
    ``at`` of the sequence ``tokens`` (``[T]`` token ids). ``dot`` is the
    product against a weight matrix; only the control of ``correct``
    (reference_control_kimi_linear.py) passes another."""
    z, eps = sizes(shape), shape["rms_norm_eps"]
    x = _f32(params["embed"][tokens])
    for i, lp in enumerate(params["layers"]):
        mixer = mla_mixer if i + 1 in z["full_attn_layers"] else kda_mixer
        assert mixer is mla_mixer or i + 1 in z["kda_layers"], i
        x = x + mixer(lp, shape, _rms(x, lp["attn_norm"], eps), dot)
        h = _rms(x, lp["mlp_norm"], eps)
        if i < shape["first_k_dense_replace"]:
            x = x + swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], dot)
        else:
            x = x + expert_layer(lp, shape, h, dot)
    return dot(_rms(x[at], params["final_norm"], eps), params["lm_head"])
