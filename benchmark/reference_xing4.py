"""The plain reference of the Xing4.0 decoder (``model_type: xing4_0``): four
residual streams mixed by manifold-constrained hyper-connections (mHC,
arXiv:2512.24880, on Hyper-Connections, arXiv:2409.19606) around every
sublayer, latent attention (MLA) in every layer rotated with YaRN's
frequencies, dense feed-forwards first and sigmoid-routed experts under a
selection bias beside one shared expert after them, and the
multi-token-prediction module behind the last layer.

The yardstick's own: nothing here is imported from the program or from
``dynamo_tpu/ops``. One sequence, the whole prompt at once, no cache, no pages,
no chunks, no slots, no kernel, no sorting of tokens by expert. A token's
state is ONE array ``[T, n, C]`` (the program keeps a tuple of streams and
runs the Sinkhorn sweeps with the tokens in the minor axis: the two share no
layout). Attention is in the NAIVE form: every token's keys and values are
expanded from its latent for every head and attended as any multi-head
attention is, a block of heads at a time (the program takes the absorbed
form). Every expert is computed for EVERY token, one expert at a time, and
weighed by what the router's choice gave the token for it (zero where it was
not chosen). Every product in float32 at the highest precision, over the
weights as the program holds them (``models/xing4.py:init_params`` names the
leaves; matrices ``[in, out]``, the experts ``[X, in, out]``, a sublayer's
``φ`` TRANSPOSED, ``[2n + n², n C]``). The sizes come from the configuration's
published ``config.json`` keys.

The equations are ISSUE 61's (``n`` = ``hc_mult``, ``C`` = ``hidden_size``).
``X⁰`` = ``n`` copies of ``Emb(t)``. Every sublayer ``F`` (``MLA(N_in(.))``,
then ``FF(N_post(.))``) with its own ``φ``, ``b``, ``α``:

    x̂ = vec(X) / sqrt(mean(vec(X)²) + rms_norm_eps);   [p, q, r] = x̂ φ
    H_pre = sigmoid(α_pre p + b_pre);   H_post = 2 sigmoid(α_post q + b_post)
    M = exp(clip(α_res mat(r) + b_res, clamp_min, clamp_max))
    hc_sinkhorn_iters times:  M = M / (colsum(M) + hc_eps);  M = M / (rowsum(M) + hc_eps)
    u = Σ_j H_pre[j] X_j;   y = F(u);   X_i <- Σ_j M[i, j] X_j + H_post[i] y

and after the last layer ``x = Σ_i X_i``, the final norm, the head.

- the mixer: ``c_q = N_q(a W_qa)``; ``q = c_q W_qb``, a head ``[q_n ; q_r]``;
  ``[c_kv ; k_r] = a W_kva``; ``c = N_kv(c_kv)``; ``q_r`` and ``k_r`` (ONE
  head, shared by all) rotated at the token's position with YaRN's
  frequencies (:func:`yarn_inv_freq`: the DeepSeek-V3 form); scores ``q_h .
  k_h x (dn + dr)^-0.5 x (0.1 mscale_all_dim ln(factor) + 1)²``, causal
  softmax, ``attn = concat_h(p v_h) W_o``;
- ``FF``, ``l < first_k_dense_replace``: the gated form at
  ``intermediate_size``; after: ``s = sigmoid(m W_r)``, the
  ``num_experts_per_tok`` largest of ``s + e_bias`` (one group), weights
  ``routed_scaling_factor x s_i / sum of the chosen s``, plus the shared expert;
- the module: ``u_i = W_eh [N_e(Emb(t_{i+1})) ; N_h(x_i)]`` with ``x_i`` the
  collapsed output BEFORE the final norm, ``n`` copies of ``u_i``, one expert
  layer of the form above, collapse by sum, ``logits' = N_mtp(.) W_head``.

ASSUMED (the configuration's ``assumed`` lists each; where the published
modeling code differs, the code wins and the difference is to be written
there): the root mean square over ``vec(X)`` has no weight and uses
``rms_norm_eps``; ``hc_eps`` is added to the Sinkhorn denominators; columns
before rows; the clamp on ``H_res``'s logits before ``exp``; the streams start
as copies and end as a sum; pre-norm layers of two norms; the half-split
rotation; the prediction module's entry and exit as above.
"""

from __future__ import annotations

import math

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


def yarn_of(shape: dict) -> dict:
    """The YaRN group: the published nested ``rope_scaling``, or its flat
    spelling (``rope_scaling_<key>``: the harness writes scalar keys only)."""
    return shape.get("rope_scaling") or {
        k[len("rope_scaling_"):]: v for k, v in shape.items() if k.startswith("rope_scaling_")}


def yarn_inv_freq(shape: dict):
    """``qk_rope_head_dim / 2`` frequencies: ``f_i = theta^(-2i/d)``, and ``f_i
    / factor`` blended in by ``ramp_i = clip((i - low) / (high - low), 0, 1)``,
    ``low, high`` = floor, ceil of ``d ln(original / (β 2π)) / (2 ln theta)`` at
    ``β`` = ``beta_fast``, ``beta_slow``, clipped to ``[0, d - 1]``."""
    y, d, theta = yarn_of(shape), shape["qk_rope_head_dim"], float(shape["rope_theta"])
    f = [theta ** (-2.0 * i / d) for i in range(d // 2)]
    if not y:
        return f

    def dim_of(beta):
        return d * math.log(y["original_max_position_embeddings"] / (beta * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(dim_of(y["beta_fast"])), 0), min(math.ceil(dim_of(y["beta_slow"])), d - 1)
    ramp = [min(max((i - low) / (high - low), 0.0), 1.0) for i in range(d // 2)]
    return [fi / y["factor"] * r + fi * (1.0 - r) for fi, r in zip(f, ramp)]


def score_scale(shape: dict) -> float:
    y = yarn_of(shape)
    scale = (shape["qk_nope_head_dim"] + shape["qk_rope_head_dim"]) ** -0.5
    if not y or not y.get("mscale_all_dim"):
        return scale
    return scale * (0.1 * y["mscale_all_dim"] * math.log(y["factor"]) + 1.0) ** 2


def _rope(x, inv_freq):
    """The half-split rotation of ``x`` ``[T, ..., D]`` at positions 0 on."""
    t, d = x.shape[0], x.shape[-1]
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)  # [T, D / 2]
    angles = angles.reshape(t, *([1] * (x.ndim - 2)), d // 2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def mla_mixer(lp: dict, shape: dict, a, dot=_dot):
    """``a`` ``[T, E]`` normed. Naive multi-head attention over keys and values
    expanded from the latents, ``HEAD_BLOCK`` heads at a time."""
    h, r = shape["num_attention_heads"], shape["kv_lora_rank"]
    dn, dr, dv = shape["qk_nope_head_dim"], shape["qk_rope_head_dim"], shape["v_head_dim"]
    t, eps, inv_freq, scale = a.shape[0], shape["rms_norm_eps"], yarn_inv_freq(shape), score_scale(shape)
    c_q = _norm(dot(a, lp["w_qa"]), lp["q_norm"], eps)
    q = dot(c_q, lp["w_qb"]).reshape(t, h, dn + dr)
    q_n, q_r = q[..., :dn], _rope(q[..., dn:], inv_freq)
    kva = dot(a, lp["w_kva"])
    c = _norm(kva[:, :r], lp["kv_norm"], eps)
    k_r = _rope(kva[:, r:], inv_freq)  # [T, dr]: one head
    kv = dot(c, lp["w_kvb"]).reshape(t, h, dn + dv)
    causal = jnp.tril(jnp.ones((t, t), bool))
    block = min(HEAD_BLOCK, h)
    assert h % block == 0, (h, block)

    def heads(xs):
        q_n, q_r, k_n, v = xs  # [block, T, .]
        scores = (jnp.einsum("htd,hsd->hts", q_n, k_n, precision=HIGHEST)
                  + jnp.einsum("htd,sd->hts", q_r, k_r, precision=HIGHEST)) * scale
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
    """Weights ``[T, experts]`` float32, zero where a token did not choose the
    expert: the ``num_experts_per_tok`` largest of score + bias, weighed by the
    scores alone. Always float32 (the control leaves the router as it is)."""
    scores = jax.nn.sigmoid(_dot(x, lp["router"]))
    _, ids = jax.lax.top_k(scores + lp["e_bias"], shape["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    if shape.get("norm_topk_prob", True):
        chosen = chosen / chosen.sum(axis=-1, keepdims=True)
    chosen = chosen * shape["routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, ids].set(chosen)


def expert_layer(lp: dict, shape: dict, x, dot=_dot):
    """Every expert over every token, one expert at a time, plus the shared
    expert (all ``n_routed_experts`` are held: ``ep_size`` 1)."""
    def one(y, xs):
        w_gate, w_up, w_down, weight = xs  # weight: [T]
        return y + weight[:, None] * swiglu(x, w_gate, w_up, w_down, dot), None

    weights = route(lp, shape, x)
    assert weights.shape[1] == lp["w_gate"].shape[0], (weights.shape, lp["w_gate"].shape)
    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (lp["w_gate"], lp["w_up"], lp["w_down"], weights.T))
    return y + swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"], dot)


def sinkhorn(m, iters: int, eps: float):
    """``m`` ``[..., n, n]`` (row ``i``, column ``j``) positive: ``iters`` times
    the columns over their sums, then the rows over theirs."""
    for _ in range(iters):
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
    return m


def mhc_maps(hp: dict, shape: dict, state):
    """(``H_pre`` ``[T, n]``, ``H_post`` ``[T, n]``, ``H_res`` ``[T, n, n]``) of
    the state ``[T, n, C]``. Float32 whatever ``dot`` is elsewhere: the maps
    decide how every later layer is fed, as a router decides which expert
    computes, and the control keeps them as it keeps the router."""
    t, n, _ = state.shape
    flat = state.reshape(t, -1)
    x_hat = flat * jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + shape["rms_norm_eps"])
    pqr = _dot(x_hat, hp["phi"].T)
    alpha, b = _f32(hp["alpha"]), _f32(hp["b"])
    h_pre = jax.nn.sigmoid(alpha[0] * pqr[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * pqr[:, n:2 * n] + b[n:2 * n])
    logits = (alpha[2] * pqr[:, 2 * n:] + b[2 * n:]).reshape(t, n, n)
    m = jnp.exp(jnp.clip(logits, shape["mhc_h_res_clamp_min"], shape["mhc_h_res_clamp_max"]))
    return h_pre, h_post, sinkhorn(m, shape["hc_sinkhorn_iters"], shape["hc_eps"])


def around(hp: dict, shape: dict, state, sublayer):
    """The residual path around ``sublayer``: ``X_i <- Σ_j H_res[i, j] X_j +
    H_post[i] F(Σ_j H_pre[j] X_j)``."""
    h_pre, h_post, h_res = mhc_maps(hp, shape, state)
    y = sublayer(jnp.einsum("tj,tjc->tc", h_pre, state, precision=HIGHEST))
    return jnp.einsum("tij,tjc->tic", h_res, state, precision=HIGHEST) + h_post[:, :, None] * y[:, None, :]


def layer(lp: dict, shape: dict, state, experts: bool, dot=_dot):
    """One pre-norm layer over the state ``[T, n, C]``."""
    eps = shape["rms_norm_eps"]
    state = around(lp["attn_hc"], shape, state,
                   lambda u: mla_mixer(lp, shape, _norm(u, lp["in_norm"], eps), dot))

    def ff(u):
        m = _norm(u, lp["pre_mlp_norm"], eps)
        return expert_layer(lp, shape, m, dot) if experts else swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"], dot)

    return around(lp["mlp_hc"], shape, state, ff)


def _spread(x, shape: dict):
    return jnp.repeat(x[:, None, :], shape["hc_mult"], axis=1)


def hidden(params: dict, shape: dict, tokens, dot=_dot):
    """The main stack's collapsed output ``[T, C]`` BEFORE the final norm."""
    assert shape["num_hidden_layers"] == len(params["layers"]), len(params["layers"])
    state = _spread(_f32(params["embed"][tokens]), shape)
    for i, lp in enumerate(params["layers"]):
        state = layer(lp, shape, state, i >= shape["first_k_dense_replace"], dot)
    return state.sum(axis=1)


def logits(params: dict, shape: dict, tokens, at, dot=_dot) -> jax.Array:
    """Float32 logits ``[len(at), vocab]`` of the next token at the positions
    ``at`` of the sequence ``tokens`` (``[T]`` token ids). ``dot`` is the
    product against a weight matrix; only the control of ``correct``
    (reference_control_xing4.py) passes another."""
    x = hidden(params, shape, tokens, dot)
    return dot(_norm(x[at], params["final_norm"], shape["rms_norm_eps"]), params["lm_head"])


def draft_logits(params: dict, shape: dict, tokens, at, dot=_dot) -> jax.Array:
    """The prediction module's float32 logits at the positions ``at`` (each
    under ``len(tokens) - 1``): position ``i`` takes the main stack's ``x_i``
    and ``tokens[i + 1]``, and scores the token at ``i + 2``."""
    eps, mp = shape["rms_norm_eps"], params["mtp"]
    x = hidden(params, shape, tokens, dot)[:-1]
    emb = _f32(params["embed"][tokens[1:]])
    u = dot(jnp.concatenate([_norm(emb, mp["e_norm"], eps), _norm(x, mp["h_norm"], eps)], axis=-1), mp["w_eh"])
    y = layer(mp["layer"], shape, _spread(u, shape), True, dot).sum(axis=1)
    return dot(_norm(y[at], mp["norm"], eps), params["lm_head"])
