"""The plain reference of the openPangu-Ultra-MoE decoder (``model_type:
pangu_ultra_moe``): latent attention (MLA) in every layer, the sandwich norm,
dense feed-forwards first and sigmoid-routed experts beside one shared expert
after them, and the multi-token-prediction module behind the last layer.

The yardstick's own: nothing here is imported from the program or from
``dynamo_tpu/ops``. One sequence, the whole prompt at once, no cache, no pages,
no chunks, no slots, no kernel, no sorting of tokens by expert. Attention is in
the NAIVE form: every token's keys and values are expanded from its latent for
every head (``k_h = [c W_kb,h ; k_r]``, ``v_h = c W_vb,h``) and attended as any
multi-head attention is, a block of heads at a time so that the scores fit;
the program takes the absorbed form, so the two share no step. Every HELD
expert is computed for EVERY token, one expert at a time, and weighed by what
the router's full choice gave the token for it (zero where it was not chosen).
Every product in float32 at the highest precision, over the weights as the
program holds them (``models/openpangu.py:init_params`` names the leaves:
``layers`` a tuple of per-layer trees, matrices ``[in, out]``, the experts ``[X,
in, out]``, ``mtp`` the module). A weight is widened to float32 where it is
multiplied, one matrix at a time. The sizes come from the configuration's
published ``config.json`` keys.

The equations are ISSUE 52's. Layer ``l`` on ``x``, every ``N`` an RMS norm
with its own plain weight:

- ``a = N_in(x)``; ``c_q = N_q(a W_qa)``; ``q = c_q W_qb``, a head ``[q_n ;
  q_r]``; ``[c_kv ; k_r] = a W_kva``; ``c = N_kv(c_kv)``; ``q_r`` and ``k_r``
  (ONE head, shared by all) rotated at the token's position; scores ``q_h . k_h
  / sqrt(qk_nope_head_dim + qk_rope_head_dim)``, causal softmax, ``attn =
  concat_h(p v_h) W_o``;
- ``x += N_post_attn(attn)``; ``m = N_pre_mlp(x)``; ``x += N_post_mlp(FF(m))``;
- ``FF``, ``l < first_k_dense_replace``: the gated form at
  ``intermediate_size``; after: ``s = sigmoid(m W_r)`` over ALL published
  experts, the ``num_experts_per_tok`` largest, weights ``s_i / sum of the
  chosen s x routed_scaling_factor``, plus the shared expert;
- ``logits = N_f(x) W_head``;
- the module: ``u_i = W_eh [N_e(Emb(t_{i+1})) ; N_h(x_i)]`` with ``x_i`` the
  main stack's output BEFORE ``N_f``, one expert layer of the form above over
  ``u``, ``logits' = N_mtp(.) W_head``: they score ``t_{i+2}``.

The experts held are ``w_gate.shape[0]`` of the ``n_routed_experts_published``
the router scores, ids 0 on: what the absent experts would add is left out
(the model-configs guide, section 4), and that partial sum goes on to the next
layer; the shared expert is whole.

ASSUMED, none of it in the published config.json (the configuration's
``assumed`` lists each; where the published modeling code differs, the code
wins and the difference is to be written there): sigmoid scoring with NO
selection bias and NO expert groups (the config has no ``scoring_func``,
``n_group``, ``topk_group``: the lineage's form); the sandwich's placement as
above (Pangu Ultra, arXiv:2504.07866; Pangu Ultra MoE, arXiv:2505.04519); the
rotation in the half-split order (with random weights a column permutation of
the interleaved one); no softmax-scale correction (no ``rope_scaling``); the
prediction module in the DeepSeek-V3 form (arXiv:2412.19437, section 2.2) with
the concatenation ``[embedding ; hidden]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
HEAD_BLOCK = 16  # heads whose scores are held at once


def _f32(a):
    return a.astype(jnp.float32)


def _dot(x, w):
    return jnp.dot(x, _f32(w), precision=HIGHEST)


def _norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(weight)


def _rope(x, theta: float, first: int = 0):
    """The half-split rotation of ``x`` ``[T, ..., D]`` at positions ``first`` on."""
    t, d = x.shape[0], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = (first + jnp.arange(t, dtype=jnp.float32))[:, None] * freqs  # [T, D / 2]
    angles = angles.reshape(t, *([1] * (x.ndim - 2)), d // 2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def mla_mixer(lp: dict, shape: dict, a, dot=_dot):
    """``a`` ``[T, E]`` normed. Naive multi-head attention over keys and values
    expanded from the latents, ``HEAD_BLOCK`` heads at a time."""
    h, r = shape["num_attention_heads"], shape["kv_lora_rank"]
    dn, dr, dv = shape["qk_nope_head_dim"], shape["qk_rope_head_dim"], shape["v_head_dim"]
    t, eps, theta = a.shape[0], shape["rms_norm_eps"], float(shape["rope_theta"])
    c_q = _norm(dot(a, lp["w_qa"]), lp["q_norm"], eps)
    q = dot(c_q, lp["w_qb"]).reshape(t, h, dn + dr)
    q_n, q_r = q[..., :dn], _rope(q[..., dn:], theta)
    kva = dot(a, lp["w_kva"])
    c = _norm(kva[:, :r], lp["kv_norm"], eps)
    k_r = _rope(kva[:, r:], theta)  # [T, dr]: one head
    kv = dot(c, lp["w_kvb"]).reshape(t, h, dn + dv)
    causal = jnp.tril(jnp.ones((t, t), bool))
    block = min(HEAD_BLOCK, h)
    assert h % block == 0, (h, block)

    def heads(xs):
        q_n, q_r, k_n, v = xs  # [block, T, .]
        scores = (jnp.einsum("htd,hsd->hts", q_n, k_n, precision=HIGHEST)
                  + jnp.einsum("htd,sd->hts", q_r, k_r, precision=HIGHEST)) * (dn + dr) ** -0.5
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hts,hsd->htd", probs, v, precision=HIGHEST)

    def blocked(x):  # [T, H, D] -> [H / block, block, T, D]
        return jnp.moveaxis(x, 1, 0).reshape(h // block, block, t, x.shape[-1])

    out = jax.lax.map(heads, (blocked(q_n), blocked(q_r), blocked(kv[..., :dn]), blocked(kv[..., dn:])))
    out = jnp.moveaxis(out.reshape(h, t, dv), 0, 1).reshape(t, h * dv)
    return dot(out, lp["wo"])


def swiglu(x, w_gate, w_up, w_down, dot=_dot):
    return dot(jax.nn.silu(dot(x, w_gate)) * dot(x, w_up), w_down)


def route(lp: dict, shape: dict, x):
    """Weights ``[T, experts published]`` float32, zero where a token did not
    choose the expert. Always float32 (the control leaves the router as it is:
    a near-tie decides which expert computes, and a deployment one precision
    down keeps its router)."""
    scores = jax.nn.sigmoid(_dot(x, lp["router"]))
    chosen, ids = jax.lax.top_k(scores, shape["num_experts_per_tok"])
    if shape.get("norm_topk_prob", True):
        chosen = chosen / chosen.sum(axis=-1, keepdims=True)
    chosen = chosen * shape["routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, ids].set(chosen)


def expert_layer(lp: dict, shape: dict, x, dot=_dot, first_expert: int = 0, shared: bool = True):
    """The held experts' part of the routed sum, one expert at a time (ids
    ``first_expert`` on), plus (``shared``) the shared expert."""
    def one(y, xs):
        w_gate, w_up, w_down, weight = xs  # weight: [T]
        return y + weight[:, None] * swiglu(x, w_gate, w_up, w_down, dot), None

    held = lp["w_gate"].shape[0]
    weights = route(lp, shape, x)[:, first_expert:first_expert + held]  # [T, held]
    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (lp["w_gate"], lp["w_up"], lp["w_down"], weights.T))
    if shared:
        y = y + swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"], dot)
    return y


def layer(lp: dict, shape: dict, x, experts: bool, dot=_dot):
    """One sandwich layer over ``x`` ``[T, E]``."""
    eps = shape["rms_norm_eps"]
    x = x + _norm(mla_mixer(lp, shape, _norm(x, lp["in_norm"], eps), dot), lp["post_attn_norm"], eps)
    m = _norm(x, lp["pre_mlp_norm"], eps)
    ff = expert_layer(lp, shape, m, dot) if experts else swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"], dot)
    return x + _norm(ff, lp["post_mlp_norm"], eps)


def hidden(params: dict, shape: dict, tokens, dot=_dot):
    """The main stack's output ``[T, E]`` BEFORE the final norm."""
    assert shape["num_hidden_layers"] == len(params["layers"]), len(params["layers"])
    x = _f32(params["embed"][tokens])
    for i, lp in enumerate(params["layers"]):
        x = layer(lp, shape, x, i >= shape["first_k_dense_replace"], dot)
    return x


def logits(params: dict, shape: dict, tokens, at, dot=_dot) -> jax.Array:
    """Float32 logits ``[len(at), vocab rows held]`` of the next token at the
    positions ``at`` of the sequence ``tokens`` (``[T]`` token ids). ``dot`` is
    the product against a weight matrix; only the control of ``correct``
    (reference_control_openpangu.py) passes another."""
    x = hidden(params, shape, tokens, dot)
    return dot(_norm(x[at], params["final_norm"], shape["rms_norm_eps"]), params["lm_head"])


def draft_logits(params: dict, shape: dict, tokens, at, dot=_dot) -> jax.Array:
    """The prediction module's float32 logits ``[len(at), vocab rows held]`` at
    the positions ``at`` (each under ``len(tokens) - 1``): position ``i`` takes
    the main stack's ``x_i`` and ``tokens[i + 1]``, and scores the token at ``i
    + 2``."""
    eps, mp = shape["rms_norm_eps"], params["mtp"]
    x = hidden(params, shape, tokens, dot)[:-1]
    emb = _f32(params["embed"][tokens[1:]])
    u = dot(jnp.concatenate([_norm(emb, mp["e_norm"], eps), _norm(x, mp["h_norm"], eps)], axis=-1), mp["w_eh"])
    y = layer(mp["layer"], shape, u, True, dot)
    return dot(_norm(y[at], mp["norm"], eps), params["lm_head"])
