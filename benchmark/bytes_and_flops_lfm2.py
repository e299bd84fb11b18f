"""Bytes and operations the LFM2 expert decoder needs, from its shapes.

``bytes_and_flops.py``'s four functions for ``model_type: lfm2_moe``
(``configs/lfm2-24b-a2b.json`` names this module under ``bytes_and_flops``).
``shape`` is the configuration's ``config.json``. bf16 weights, 2 bytes an
element; the routers, the convolutions' taps and the norms are float32 in the
program and counted at 2 bytes with the rest (0.02 % of the weights); the
pages and the convolution layers' per-slot tails are float32.

``param_count`` is the number of elements ``models/lfm2.py:init_params``
makes (tests/benchmark holds the two equal).

A decode step of this model streams every weight OUTSIDE the experts once,
the routers, the experts its lanes HIT (not the experts held: 9.66 of the
10.53 GB are experts, and 64 lanes x 4 choices leave some of the 64 unread in
every layer), every lane's convolution tails there and back, and the live K
and V. ``decode_step_roofline.py`` hands ``decode_step_stream_bytes`` the
lanes x their mean context as ONE number and no counter, so the share of the
experts a step reads comes from the configuration's file, ``experts_hit_share``:
the SMALLEST ``moe_experts_hit / (moe_layer_calls x num_experts)`` of the cell's
runs on the chip (the file says when and how it was read). Charged low, the
roofline share errs low: it can never pass 100 % for what this file
miscounted. Without the key the even-routing expectation stands in (a
configuration that has not been read yet, a test's small shape). The lanes
are the ``--max-batch-size`` of the configuration that names this module, as
``bytes_and_flops_jamba`` takes them.
"""

from __future__ import annotations

import glob
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
BYTES = 2  # bf16
STATE_BYTES = 4  # the pages and the convolutions' tails are float32


def _kinds(shape: dict) -> list:
    kinds = list(shape["layer_types"])
    assert len(kinds) == shape["num_hidden_layers"], (len(kinds), shape["num_hidden_layers"])
    return kinds


def _head_dim(shape: dict) -> int:
    return shape.get("head_dim") or shape["hidden_size"] // shape["num_attention_heads"]


def conv_mixer_params(shape: dict) -> int:
    h = shape["hidden_size"]
    return h * 3 * h + shape["conv_L_cache"] * h + h * h  # in-projection (B, C, x), taps, out-projection


def attention_mixer_params(shape: dict) -> int:
    h, d = shape["hidden_size"], _head_dim(shape)
    q, kv = shape["num_attention_heads"] * d, shape["num_key_value_heads"] * d
    return h * q + 2 * h * kv + q * h + 2 * d  # no bias; one q and one k norm of D


def dense_ffn_params(shape: dict) -> int:
    return 3 * shape["hidden_size"] * shape["intermediate_size"]


def expert_params(shape: dict) -> int:
    """One expert's three matrices."""
    return 3 * shape["hidden_size"] * shape["moe_intermediate_size"]


def router_params(shape: dict) -> int:
    return shape["hidden_size"] * shape["num_experts"] + shape["num_experts"]  # and the selection bias


def _embedding(shape: dict) -> int:
    return shape["vocab_size"] * shape["hidden_size"]


def _expert_layers(shape: dict) -> int:
    return shape["num_hidden_layers"] - shape["num_dense_layers"]


def _outside_experts(shape: dict) -> int:
    """Every parameter but the experts' matrices: the mixers, the dense
    feed-forwards, the routers, two norms a layer, the final norm, the tables."""
    kinds, h = _kinds(shape), shape["hidden_size"]
    tables = 1 if shape.get("tie_word_embeddings", True) else 2
    return (kinds.count("conv") * conv_mixer_params(shape)
            + kinds.count("full_attention") * attention_mixer_params(shape)
            + shape["num_dense_layers"] * dense_ffn_params(shape)
            + _expert_layers(shape) * router_params(shape)
            + len(kinds) * 2 * h + h + tables * _embedding(shape))


def param_count(shape: dict) -> int:
    return (_outside_experts(shape)
            + _expert_layers(shape) * shape["num_experts"] * expert_params(shape))


def weight_bytes(shape: dict) -> int:
    return param_count(shape) * BYTES


def kv_bytes_per_token(shape: dict) -> int:
    """K and V of one position over the attention layers."""
    return (_kinds(shape).count("full_attention") * 2 * shape["num_key_value_heads"]
            * _head_dim(shape) * STATE_BYTES)


def slot_state_bytes(shape: dict) -> int:
    """One slot's convolution tails over the conv layers."""
    return (_kinds(shape).count("conv") * (shape["conv_L_cache"] - 1) * shape["hidden_size"]
            * STATE_BYTES)


def _configuration(shape: dict) -> dict:
    """The configuration whose file names this module and holds this depth and
    these widths (one, today), or {}."""
    for path in sorted(glob.glob(os.path.join(HERE, "configs", "*.json"))):
        with open(path) as f:
            cfg = json.load(f)
        if cfg.get("bytes_and_flops") == __name__.rsplit(".", 1)[-1] and all(
                cfg.get(k) == shape.get(k) for k in ("num_hidden_layers", "hidden_size", "vocab_size")):
            return cfg
    return {}


def lanes_of(shape: dict) -> int:
    """``--max-batch-size`` of the configuration that names this module."""
    cfg = _configuration(shape)
    if not cfg:
        raise KeyError("no configuration under configs/ names bytes_and_flops_lfm2 at this shape")
    flags = cfg["serving"]["server_flags"]
    return int(flags[flags.index("--max-batch-size") + 1])


def experts_hit_share(shape: dict, lanes: float) -> float:
    """The share of a layer's experts one decode step reads: the
    configuration's ``experts_hit_share`` (the smallest reading on the chip,
    at the configuration's own lanes) where it has one, else what even routing
    of ``lanes x num_experts_per_tok`` pairs would hit."""
    read = _configuration(shape).get("experts_hit_share")
    if read is not None and lanes == lanes_of(shape):
        return float(read["smallest"])
    x = shape["num_experts"]
    return 1.0 - (1.0 - shape["num_experts_per_tok"] / x) ** lanes


def decode_step_stream_bytes(shape: dict, live_context_tokens: float, chips: int = 1,
                             lanes: float = None) -> float:
    """Bytes ONE decode step must move: every weight outside the experts once
    (a tied embedding once, as the head; the lookup reads ``lanes`` rows of
    it, left out; an untied table is read by row and left out), the experts
    hit, every lane's convolution tails read and written, and the K and V of
    the live context."""
    lanes = lanes_of(shape) if lanes is None else lanes
    outside = _outside_experts(shape) - (0 if shape.get("tie_word_embeddings", True) else _embedding(shape))
    experts = (_expert_layers(shape) * shape["num_experts"] * experts_hit_share(shape, lanes)
               * expert_params(shape))
    return ((outside + experts) * BYTES + 2 * lanes * slot_state_bytes(shape)
            + live_context_tokens * kv_bytes_per_token(shape)) / chips


def prefill_chunk_flops(shape: dict, positions: int, mean_context_tokens: float) -> float:
    """Operations of one prefill chunk over ``positions`` query positions: 2 a
    multiply-add in the matrices a token goes through (``num_experts_per_tok``
    experts of the ``num_experts``, and the router; the embedding lookup and the
    head left out: the program computes the head for the one position a row
    samples), the convolution's taps and gates, and attention's scores and
    values against ``mean_context_tokens`` keys a query."""
    kinds, h = _kinds(shape), shape["hidden_size"]
    conv = h * 3 * h + h * h
    matrices = (kinds.count("conv") * conv
                + kinds.count("full_attention") * (attention_mixer_params(shape) - 2 * _head_dim(shape))
                + shape["num_dense_layers"] * dense_ffn_params(shape)
                + _expert_layers(shape) * (shape["num_experts_per_tok"] * expert_params(shape)
                                           + h * shape["num_experts"]))
    taps = kinds.count("conv") * h * (2 * shape["conv_L_cache"] + 2)
    attn = (kinds.count("full_attention") * 2 * 2 * shape["num_attention_heads"] * _head_dim(shape)
            * mean_context_tokens)
    return positions * (2.0 * matrices + taps + attn)
