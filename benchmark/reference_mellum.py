"""The plain reference of the Mellum 2 decoder (``model_type: mellum``): window
attention layers beside full attention layers, three to one, both rotated,
each kind by its own table (a window layer by ``rope_theta``'s plain
frequencies, a full layer by YaRN's blend of them with cosine and sine times
``attention_factor``); a norm on every head of q and k; two RMS norms a layer;
softmax-routed experts in every layer (the ``num_experts_per_tok`` largest of
``num_experts`` probabilities, renormalised); an untied head.

The yardstick's own: nothing here is imported from the program or from
``dynamo_tpu/ops`` (not ``models/mellum.py``, not ``models/xing4.py``'s YaRN
table, not ``ops/ring.py``, not ``ops/moe.py``). One sequence, the whole prompt
at once, no cache, no ring, no pages, no chunks, no slots, no shards, no
sorting of tokens by expert: attention is the naive masked softmax over every
key of the sequence (a block of ``QUERY_BLOCK`` queries at a time; a block's
mathematics is the whole's), and EVERY expert is computed for EVERY token, one
expert at a time, and weighed by what the router gave the token for it (zero
where it was not chosen). Every product in float32 at the highest precision,
over the weights as the program holds them (``models/mellum.py:init_params``
names the leaves: every leaf of ``layers`` stacked ``[periods, layers a
period, ...]``, matrices ``[in, out]``, the experts ``[X, in, out]``). A weight
is widened to float32 where it is multiplied, one matrix at a time. Under the
reference child's mesh the compiler partitions this forward pass over the
program's own parameter tree; the mathematics stays this file's.

The sizes come from the configuration's published ``config.json`` keys; the two
sections of ``rope_parameters`` from the nested group where the card has it,
else from the flat spelling the harness writes (``rope_parameters_<kind>_<key>``).

The equations are ISSUE 68's, from the catalog row's ``config`` and, where that
is silent, the configuration's ``assumed``: the head norms come before the
rotation; the rotation is the half-split form over all of ``head_dim``; a
window layer's query at ``p`` sees keys ``p - sliding_window + 1 .. p``; YaRN's
table is static (every position, not only past the original context), its ramp
runs between ``floor`` and ``ceil`` of the pairs that turn ``beta_fast`` and
``beta_slow`` times over the original context; no scale on the embeddings.
Departure from the published code: the loop over experts is a ``lax.scan`` over
their stacked matrices.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
WINDOW, FULL = "sliding_attention", "full_attention"


def _f32(a):
    return a.astype(jnp.float32)


def _dot(x, w):
    return jnp.dot(x, _f32(w), precision=HIGHEST)


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(weight)


def rope_section(shape: dict, kind: str) -> dict:
    """The ``rope_parameters`` of one kind of layer."""
    nested = shape.get("rope_parameters")
    if nested:
        return nested[kind]
    prefix = f"rope_parameters_{kind}_"
    return {k[len(prefix):]: v for k, v in shape.items() if k.startswith(prefix)}


def table(shape: dict, kind: str):
    """(the ``head_dim / 2`` frequencies, the factor on cosine and sine) of a
    layer of ``kind``, written out from the config's numbers."""
    d, rope = shape["head_dim"], rope_section(shape, kind)
    theta = float(rope["rope_theta"])
    plain = [theta ** (-2.0 * j / d) for j in range(d // 2)]
    if rope.get("rope_type", "default") == "default":
        return plain, 1.0
    assert rope["rope_type"] == "yarn", rope
    factor, original = float(rope["factor"]), float(rope["original_max_position_embeddings"])

    def pair_that_turns(turns: float) -> float:
        return d * math.log(original / (turns * 2.0 * math.pi)) / (2.0 * math.log(theta))

    low = max(math.floor(pair_that_turns(float(rope.get("beta_fast", 32)))), 0)
    high = min(math.ceil(pair_that_turns(float(rope.get("beta_slow", 1)))), d - 1)
    ramp = [min(max((j - low) / max(high - low, 0.001), 0.0), 1.0) for j in range(d // 2)]
    blended = [e * (1.0 - r) + e / factor * r for e, r in zip(plain, ramp)]
    return blended, float(rope.get("attention_factor", 0.1 * math.log(factor) + 1.0))


def _rope(x, freqs, factor: float):
    """The half-split rotation of ``x`` ``[T, H, D]`` at positions 0 .. T-1 by
    ``freqs``, cosine and sine both times ``factor``."""
    t, _, d = x.shape
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(freqs, jnp.float32)  # [T, D/2]
    cos, sin = factor * jnp.cos(angles)[:, None, :], factor * jnp.sin(angles)[:, None, :]
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
    ``D`` for all heads), then rotated by the table of the layer's kind; query
    head ``n`` over key/value head ``n // (heads / kv_heads)``; then the
    out-projection. No bias, no gate."""
    heads, kv_heads, d = shape["num_attention_heads"], shape["num_key_value_heads"], shape["head_dim"]
    t, eps = a.shape[0], shape["rms_norm_eps"]
    q = _rms(dot(a, lp["wq"]).reshape(t, heads, d), lp["q_norm"], eps)
    k = _rms(dot(a, lp["wk"]).reshape(t, kv_heads, d), lp["k_norm"], eps)
    v = dot(a, lp["wv"]).reshape(t, kv_heads, d)
    freqs, factor = table(shape, kind)
    q, k = _rope(q, freqs, factor), _rope(k, freqs, factor)
    k, v = (jnp.repeat(x, heads // kv_heads, axis=1) for x in (k, v))
    out = attend(q, k, v, shape["sliding_window"] if kind == WINDOW else None)
    return dot(out.reshape(t, heads * d), lp["wo"])


def swiglu(x, w_gate, w_up, w_down, dot=_dot):
    return dot(jax.nn.silu(dot(x, w_gate)) * dot(x, w_up), w_down)


def route(lp: dict, shape: dict, x):
    """Weights ``[T, num_experts]`` float32, zero where a token did not choose
    the expert: softmax over all the experts, the ``num_experts_per_tok``
    largest chosen, each weighing its probability over the sum of the chosen
    ones (``norm_topk_prob``). Always float32 (the control leaves the router as
    it is: a near-tie decides which expert computes)."""
    probs = jax.nn.softmax(_dot(x, lp["router"]), axis=-1)
    chosen, ids = jax.lax.top_k(probs, shape["num_experts_per_tok"])
    if shape.get("norm_topk_prob", True):
        chosen = chosen / chosen.sum(axis=-1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, ids].set(chosen)


def experts(lp: dict, shape: dict, x, dot=_dot):
    """``sum_e weight_e * E_e(x)`` over ALL the experts, one at a time."""
    def one(y, xs):
        w_gate, w_up, w_down, weight = xs  # weight: [T]
        return y + weight[:, None] * swiglu(x, w_gate, w_up, w_down, dot), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (lp["w_gate"], lp["w_up"], lp["w_down"], route(lp, shape, x).T))
    return y


def logits(params: dict, shape: dict, tokens, at, dot=_dot) -> jax.Array:
    """Float32 logits ``[len(at), vocab]`` of the next token at the positions
    ``at`` of the sequence ``tokens`` (``[T]`` token ids). ``dot`` is the
    product against a weight matrix; only the control of ``correct``
    (reference_control_mellum.py) passes another."""
    eps, kinds = shape["rms_norm_eps"], shape["layer_types"]
    stack = params["layers"]
    periods, in_period = stack["wq"].shape[:2]
    assert len(kinds) == shape["num_hidden_layers"] == periods * in_period, kinds
    x = _f32(params["embed"][tokens])
    for i, kind in enumerate(kinds):
        lp = {name: leaf[i // in_period, i % in_period] for name, leaf in stack.items()}
        x = x + attention(lp, shape, kind, _rms(x, lp["in_norm"], eps), dot)
        x = x + experts(lp, shape, _rms(x, lp["mlp_norm"], eps), dot)
    return dot(_rms(x[at], params["final_norm"], eps), params["lm_head"])
