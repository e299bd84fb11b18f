"""Bytes and operations the Qwen3-Next decoder needs, from its shapes.

``bytes_and_flops.py``'s four functions for ``model_type: qwen3_next``
(``configs/qwen3-next-80b-a3b.json`` names this module under
``bytes_and_flops``). ``shape`` is the configuration's ``config.json``. bf16
weights, 2 bytes an element; the routers, the convolutions' taps, ``a_log``,
``dt_bias`` and the norms are float32 in the program and counted at 2 bytes
with the rest (0.1 % of the weights); the pages, the DeltaNet layers' per-slot
state and the convolutions' tails are float32.

``param_count`` is the number of elements ``models/qwen3_next.py:init_params``
makes (tests/benchmark holds the two equal): the experts HELD here
(``num_experts``), of the ``num_experts_published`` the router scores, and the
vocabulary rows held.

A decode step of this model streams every weight OUTSIDE the routed experts
once (the shared expert and the routers among them; the head's rows held; the
embedding is read by row and left out), the routed experts its lanes HIT (not
the experts held: 6.44 of the 7.33 GB are routed experts, and 64 lanes x 10
choices, a quarter of them to the 128 held, leave some unread in every layer),
every lane's DeltaNet state and tails there and back, and the live K and V.
``decode_step_roofline.py`` hands ``decode_step_stream_bytes`` the lanes x
their mean context as ONE number and no counter, so the share of the experts a
step reads comes from the configuration's file, ``experts_hit_share``: the
SMALLEST reading of the cell's runs on the chip (the file says when and how it
was read). Charged low, the roofline share errs low: it can never pass 100 %
for what this file miscounted. Without the key the even-routing expectation
stands in (a configuration that has not been read yet, a test's small shape).
The lanes are the ``--max-batch-size`` of the configuration that names this
module, as ``bytes_and_flops_lfm2`` takes them.
"""

from __future__ import annotations

import glob
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
BYTES = 2  # bf16
STATE_BYTES = 4  # the pages, the DeltaNet state and the convolutions' tails are float32
# what ``ops/pallas/kda_scan.py`` does an element of a head's [d_k, d_v] state
# and token: the decay (1), k * S and its sum over the key channels (2), the
# rank-one update's multiply and add (2), q * S and its sum (2)
RECURRENCE_OPS = 7


def _kinds(shape: dict) -> list:
    """``True`` for an attention layer, layer by layer."""
    kinds = shape.get("layer_types")
    if kinds:
        assert len(kinds) == shape["num_hidden_layers"], (len(kinds), shape["num_hidden_layers"])
        return [k == "full_attention" for k in kinds]
    return [(i + 1) % shape["full_attention_interval"] == 0 for i in range(shape["num_hidden_layers"])]


def _gdn_layers(shape: dict) -> int:
    return _kinds(shape).count(False)


def _attn_layers(shape: dict) -> int:
    return _kinds(shape).count(True)


def _gdn_dims(shape: dict):
    """(key channels, value channels, the convolution's channels)."""
    key = shape["linear_num_key_heads"] * shape["linear_key_head_dim"]
    value = shape["linear_num_value_heads"] * shape["linear_value_head_dim"]
    return key, value, 2 * key + value


def gdn_mixer_params(shape: dict) -> int:
    h, hv = shape["hidden_size"], shape["linear_num_value_heads"]
    _, value, conv = _gdn_dims(shape)
    return (h * (conv + value) + h * 2 * hv  # in (q, k, v, z), in (b, a)
            + shape["linear_conv_kernel_dim"] * conv  # the taps
            + 2 * hv + shape["linear_value_head_dim"]  # a_log, dt_bias, the gated norm
            + value * h)  # out


def attention_mixer_params(shape: dict) -> int:
    h, d = shape["hidden_size"], shape["head_dim"]
    q, kv = shape["num_attention_heads"] * d, shape["num_key_value_heads"] * d
    return h * 2 * q + 2 * h * kv + q * h + 2 * d  # [q | gate], k, v, o; the q and the k norm of D


def expert_params(shape: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * shape["hidden_size"] * shape["moe_intermediate_size"]


def ffn_outside_experts_params(shape: dict) -> int:
    """A layer's feed-forward outside the routed experts: the router over
    every published expert, the shared expert, its gate."""
    h = shape["hidden_size"]
    return (h * shape.get("num_experts_published", shape["num_experts"])
            + 3 * h * shape["shared_expert_intermediate_size"] + h)


def _embedding(shape: dict) -> int:
    return shape["vocab_size"] * shape["hidden_size"]


def _outside_experts(shape: dict) -> int:
    """Every parameter but the routed experts' matrices: the mixers, the
    routers and shared experts, two norms a layer, the final norm, the tables."""
    h, layers = shape["hidden_size"], shape["num_hidden_layers"]
    tables = 1 if shape.get("tie_word_embeddings", False) else 2
    return (_gdn_layers(shape) * gdn_mixer_params(shape)
            + _attn_layers(shape) * attention_mixer_params(shape)
            + layers * (ffn_outside_experts_params(shape) + 2 * h) + h + tables * _embedding(shape))


def param_count(shape: dict) -> int:
    return (_outside_experts(shape)
            + shape["num_hidden_layers"] * shape["num_experts"] * expert_params(shape))


def weight_bytes(shape: dict) -> int:
    return param_count(shape) * BYTES


def kv_bytes_per_token(shape: dict) -> int:
    """K and V of one position over the attention layers."""
    return _attn_layers(shape) * 2 * shape["num_key_value_heads"] * shape["head_dim"] * STATE_BYTES


def slot_state_bytes(shape: dict) -> int:
    """One slot's DeltaNet state and convolution tails over the DeltaNet layers."""
    _, _, conv = _gdn_dims(shape)
    state = (shape["linear_num_value_heads"] * shape["linear_key_head_dim"]
             * shape["linear_value_head_dim"])
    return _gdn_layers(shape) * (state + (shape["linear_conv_kernel_dim"] - 1) * conv) * STATE_BYTES


def _configuration(shape: dict) -> dict:
    """The configuration whose file names this module and holds this depth and
    these widths (one, today), or {}."""
    for path in sorted(glob.glob(os.path.join(HERE, "configs", "*.json"))):
        with open(path) as f:
            cfg = json.load(f)
        if cfg.get("bytes_and_flops") == __name__.rsplit(".", 1)[-1] and all(
                cfg.get(k) == shape.get(k)
                for k in ("num_hidden_layers", "hidden_size", "vocab_size", "num_experts")):
            return cfg
    return {}


def lanes_of(shape: dict) -> int:
    """``--max-batch-size`` of the configuration that names this module."""
    cfg = _configuration(shape)
    if not cfg:
        raise KeyError("no configuration under configs/ names bytes_and_flops_qwen3_next at this shape")
    flags = cfg["serving"]["server_flags"]
    return int(flags[flags.index("--max-batch-size") + 1])


def experts_hit_share(shape: dict, lanes: float) -> float:
    """The share of a layer's HELD experts one decode step reads: the
    configuration's ``experts_hit_share`` (the smallest reading on the chip, at
    the configuration's own lanes) where it has one, else what even routing of
    ``lanes x num_experts_per_tok`` pairs over every published expert would
    hit."""
    read = _configuration(shape).get("experts_hit_share")
    if read is not None and lanes == lanes_of(shape):
        return float(read["smallest"])
    published = shape.get("num_experts_published", shape["num_experts"])
    return 1.0 - (1.0 - shape["num_experts_per_tok"] / published) ** lanes


def decode_step_stream_bytes(shape: dict, live_context_tokens: float, chips: int = 1,
                             lanes: float = None) -> float:
    """Bytes ONE decode step must move: every weight outside the routed
    experts once (an untied embedding is read by row and left out; the head's
    rows held count), the routed experts hit, every lane's DeltaNet state and
    tails read and written, and the K and V of the live context."""
    lanes = lanes_of(shape) if lanes is None else lanes
    outside = _outside_experts(shape) - (0 if shape.get("tie_word_embeddings", False) else _embedding(shape))
    experts = (shape["num_hidden_layers"] * shape["num_experts"] * experts_hit_share(shape, lanes)
               * expert_params(shape))
    return ((outside + experts) * BYTES + 2 * lanes * slot_state_bytes(shape)
            + live_context_tokens * kv_bytes_per_token(shape)) / chips


def prefill_chunk_flops(shape: dict, positions: int, mean_context_tokens: float) -> float:
    """Operations of one prefill chunk over ``positions`` query positions: 2 a
    multiply-add in the matrices a token goes through (the HELD share of its
    ``num_experts_per_tok`` experts, the shared expert and its gate, the
    router; the embedding lookup and the head left out: the program computes
    the head for the one position a row samples), the convolution's taps,
    ``RECURRENCE_OPS`` an element of every value head's ``[d_k, d_v]`` state a
    DeltaNet layer, and attention's scores and values against
    ``mean_context_tokens`` keys a query."""
    h = shape["hidden_size"]
    _, value, conv = _gdn_dims(shape)
    held = shape["num_experts"] / shape.get("num_experts_published", shape["num_experts"])
    matrices = (_gdn_layers(shape) * (h * (conv + value) + h * 2 * shape["linear_num_value_heads"] + value * h)
                + _attn_layers(shape) * (attention_mixer_params(shape) - 2 * shape["head_dim"])
                + shape["num_hidden_layers"] * (
                    shape["num_experts_per_tok"] * held * expert_params(shape)
                    + ffn_outside_experts_params(shape)))
    taps = _gdn_layers(shape) * 2 * shape["linear_conv_kernel_dim"] * conv
    recurrence = (_gdn_layers(shape) * RECURRENCE_OPS * shape["linear_num_value_heads"]
                  * shape["linear_key_head_dim"] * shape["linear_value_head_dim"])
    attn = (_attn_layers(shape) * 2 * 2 * shape["num_attention_heads"] * shape["head_dim"]
            * mean_context_tokens)
    return positions * (2.0 * matrices + taps + recurrence + attn)
