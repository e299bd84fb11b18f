"""Bytes and operations the Xing4.0 decoder needs, from its shapes.

``bytes_and_flops.py``'s four functions for ``model_type: xing4_0``
(``configs/xing4.0-29b-a4b.json`` names this module under ``bytes_and_flops``),
and ``mhc_bytes_per_token`` for its residual path. ``shape`` is the
configuration's ``config.json``. bf16 weights, 2 bytes an element; the
routers, their selection biases and the norms are float32 in the program and
counted at 2 bytes with the rest (0.03 % of the weights); the latent pages and
the residual streams are float32.

``param_count`` is the number of elements ``models/xing4.py:init_params``
makes (tests/benchmark holds the two equal, at the cell's shape and at the
published one): every one of the ``n_routed_experts`` of a layer (``ep_size``
1), the whole vocabulary, two mHC sets a layer (``φ`` ``n C x (2n + n²)``, ``b``
``2n + n²``, ``α`` 3) and the multi-token-prediction module, which a deployment
holds whether or not it drafts.

A decode step of this model streams every weight OUTSIDE the routed experts
and outside the prediction module once (the module is held and NOT streamed at
the served default, ``spec_k`` 0; the untied embedding is read by row and left
out), the routed experts its lanes HIT (the configuration's
``experts_hit_share``, the smallest reading of the cell's runs on the chip,
where the file has one: charged low, the roofline share errs low; else what
even routing would hit), and the latent of the live context, 576 float32
values a token and layer (the pool's row is 640 wide, whole registers: the
padding is the layout's and is not counted). ``decode_step_roofline.py`` hands
``decode_step_stream_bytes`` the lanes x their mean context as ONE number, so
the lanes are the ``--max-batch-size`` of the configuration that names this
module. The residual streams of a step's 64 rows (``mhc_bytes_per_token`` x 64
x 10 sublayers = 128 MB of 7.3 GB) are activations and NOT charged: the share
errs low by that too. No Pallas kernel is this model's own: ``grouped_product``
(the expert layer's three products) is the only one in its programs.
"""

from __future__ import annotations

import glob
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
BYTES = 2  # bf16
STATE_BYTES = 4  # the latent pages and the residual streams are float32
LANES = 128  # of a register: the pool's rows are whole registers wide


def mla_mixer_params(shape: dict) -> int:
    """``W_qa``, ``W_qb``, ``W_kva``, ``W_kvb``, ``W_o`` and the two inner norms."""
    h, heads = shape["hidden_size"], shape["num_attention_heads"]
    rq, r = shape["q_lora_rank"], shape["kv_lora_rank"]
    dn, dr, dv = shape["qk_nope_head_dim"], shape["qk_rope_head_dim"], shape["v_head_dim"]
    return (h * rq + rq * heads * (dn + dr) + h * (r + dr) + r * heads * (dn + dv)
            + heads * dv * h + rq + r)


def mhc_params(shape: dict) -> int:
    """One sublayer's set: ``φ``, ``b`` and the three ``α``."""
    n = shape["hc_mult"]
    maps = 2 * n + n * n
    return n * shape["hidden_size"] * maps + maps + 3


def _outside_feed_forward(shape: dict) -> int:
    """What every layer has: the mixer, two norms, two mHC sets."""
    return mla_mixer_params(shape) + 2 * shape["hidden_size"] + 2 * mhc_params(shape)


def expert_params(shape: dict) -> int:
    """One routed expert's three matrices (the shared expert the same)."""
    return 3 * shape["hidden_size"] * shape["moe_intermediate_size"]


def dense_layer_params(shape: dict) -> int:
    return _outside_feed_forward(shape) + 3 * shape["hidden_size"] * shape["intermediate_size"]


def expert_layer_outside_experts_params(shape: dict) -> int:
    """An expert layer without its routed experts: the router with its
    selection bias and the shared expert beside what every layer has."""
    x = shape["n_routed_experts"]
    return _outside_feed_forward(shape) + shape["hidden_size"] * x + x + expert_params(shape)


def expert_layer_params(shape: dict) -> int:
    return expert_layer_outside_experts_params(shape) + shape["n_routed_experts"] * expert_params(shape)


def mtp_params(shape: dict) -> int:
    """The prediction module: ``W_eh``, its three norms, one expert layer."""
    h = shape["hidden_size"]
    return shape.get("num_nextn_predict_layers", 1) * (2 * h * h + 3 * h + expert_layer_params(shape))


def _embedding(shape: dict) -> int:
    return shape["vocab_size"] * shape["hidden_size"]


def _expert_layers(shape: dict) -> int:
    return shape["num_hidden_layers"] - shape["first_k_dense_replace"]


def param_count(shape: dict) -> int:
    return (shape["first_k_dense_replace"] * dense_layer_params(shape)
            + _expert_layers(shape) * expert_layer_params(shape)
            + mtp_params(shape) + 2 * _embedding(shape) + shape["hidden_size"])


def weight_bytes(shape: dict) -> int:
    return param_count(shape) * BYTES


def kv_bytes_per_token(shape: dict) -> int:
    """The latent and the shared key part of one position over the layers:
    what the algorithm reads of a cached token."""
    return shape["num_hidden_layers"] * (shape["kv_lora_rank"] + shape["qk_rope_head_dim"]) * STATE_BYTES


def kv_pool_bytes_per_token(shape: dict) -> int:
    """What the pool HOLDS of a token: a row of whole registers a layer."""
    held = shape["kv_lora_rank"] + shape["qk_rope_head_dim"]
    return shape["num_hidden_layers"] * -(-held // LANES) * LANES * STATE_BYTES


def mhc_bytes_per_token(shape: dict) -> int:
    """What the residual path of ONE sublayer must move of ONE token: the
    ``n`` float32 streams read for the maps and the mixing in, ``u`` written,
    ``y`` read, the streams read again (the sublayer ran between) and written:
    ``(3n + 2) C`` float32 values. ``φ`` is read once a call whatever its rows
    (:func:`mhc_phi_bytes`)."""
    return (3 * shape["hc_mult"] + 2) * shape["hidden_size"] * STATE_BYTES


def mhc_phi_bytes(shape: dict) -> int:
    """``φ`` of one sublayer, bf16: what one call of the maps reads beside its rows."""
    n = shape["hc_mult"]
    return n * shape["hidden_size"] * (2 * n + n * n) * BYTES


def _configuration(shape: dict) -> dict:
    """The configuration whose file names this module and holds this depth and
    these widths (one, today), or {}."""
    for path in sorted(glob.glob(os.path.join(HERE, "configs", "*.json"))):
        with open(path) as f:
            cfg = json.load(f)
        if cfg.get("bytes_and_flops") == __name__.rsplit(".", 1)[-1] and all(
                cfg.get(k) == shape.get(k)
                for k in ("num_hidden_layers", "hidden_size", "vocab_size", "n_routed_experts")):
            return cfg
    return {}


def lanes_of(shape: dict) -> int:
    """``--max-batch-size`` of the configuration that names this module."""
    cfg = _configuration(shape)
    if not cfg:
        raise KeyError("no configuration under configs/ names bytes_and_flops_xing4 at this shape")
    flags = cfg["serving"]["server_flags"]
    return int(flags[flags.index("--max-batch-size") + 1])


def experts_hit_share(shape: dict, lanes: float) -> float:
    """The share of a layer's experts one decode step reads: the
    configuration's ``experts_hit_share`` (the smallest reading on the chip, at
    the configuration's own lanes) where it has one, else what even routing of
    ``lanes x num_experts_per_tok`` pairs over the experts would hit."""
    read = _configuration(shape).get("experts_hit_share")
    if read is not None and lanes == lanes_of(shape):
        return float(read["smallest"])
    return 1.0 - (1.0 - shape["num_experts_per_tok"] / shape["n_routed_experts"]) ** lanes


def decode_step_stream_bytes(shape: dict, live_context_tokens: float, chips: int = 1,
                             lanes: float = None) -> float:
    """Bytes ONE decode step must move: the dense layers and the expert layers
    outside their routed experts once, the routed experts hit, the final norm
    and the head, and the latent of the live context. Neither the embedding
    (read by row) nor the prediction module (not run at ``spec_k`` 0) nor the
    residual streams (activations)."""
    lanes = lanes_of(shape) if lanes is None else lanes
    outside = (shape["first_k_dense_replace"] * dense_layer_params(shape)
               + _expert_layers(shape) * expert_layer_outside_experts_params(shape)
               + shape["hidden_size"] + _embedding(shape))
    experts = (_expert_layers(shape) * shape["n_routed_experts"] * experts_hit_share(shape, lanes)
               * expert_params(shape))
    return ((outside + experts) * BYTES + live_context_tokens * kv_bytes_per_token(shape)) / chips


def prefill_chunk_flops(shape: dict, positions: int, mean_context_tokens: float) -> float:
    """Operations of one prefill chunk over ``positions`` query positions: 2 a
    multiply-add in the matrices a token goes through (the mixer's five, the
    absorbed form taking ``W_kvb`` once; two sublayers' ``φ``; the dense
    feed-forward; the router, the shared expert and ``num_experts_per_tok``
    experts; the embedding lookup and the head left out: the program computes
    the head for the one position a row samples), latent attention against
    ``mean_context_tokens`` keys a query in the absorbed form (``rank + rope`` a
    score and ``rank`` a value, a head), and the residual path's mixing (``n +
    n² + n`` multiply-adds a value of ``C``, a sublayer). The Sinkhorn sweeps
    (``2 x iters x 2 n²`` operations a token) and the prediction module (not run
    at ``spec_k`` 0) are not counted."""
    h, n = shape["hidden_size"], shape["hc_mult"]
    mixer = mla_mixer_params(shape) - shape["q_lora_rank"] - shape["kv_lora_rank"]
    residual = 2 * (n * h * (2 * n + n * n) + (2 * n + n * n) * h)
    matrices = (shape["num_hidden_layers"] * (mixer + residual)
                + shape["first_k_dense_replace"] * 3 * h * shape["intermediate_size"]
                + _expert_layers(shape) * (h * shape["n_routed_experts"]
                                           + (1 + shape["num_experts_per_tok"]) * expert_params(shape)))
    attn = (shape["num_hidden_layers"] * 2 * shape["num_attention_heads"]
            * (2 * shape["kv_lora_rank"] + shape["qk_rope_head_dim"]) * mean_context_tokens)
    return positions * (2.0 * matrices + attn)
