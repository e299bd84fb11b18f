"""Bytes and operations the Jamba decoder needs, from its shapes.

``bytes_and_flops.py``'s four functions for ``model_type: jamba``
(``configs/jamba2-3b.json`` names this module under ``bytes_and_flops``).
``shape`` is the configuration's ``config.json``. bf16 weights and pages, 2
bytes an element; the Mamba layers' per-slot state (the ``[N, D]`` matrix and
the convolution's last ``K - 1`` inputs) is float32.

``param_count`` is the number of elements ``models/jamba.py:init_params``
makes (tests/benchmark holds the two equal).

A decode step of this model streams the weights once and, whatever the lanes'
context, every lane's recurrent state there and back; the two attention
layers add the live K and V. ``decode_step_roofline.py`` hands
``decode_step_stream_bytes`` the lanes x their mean context as ONE number and
not the lane count, so this module takes the lanes from the
``--max-batch-size`` of the configuration that names it (the closed loop keeps
98 % of the slots busy: ``batch_occupancy``), as ``bytes_and_flops_kimi_linear``
does.
"""

from __future__ import annotations

import glob
import json
import os

from benchmark.reference_jamba import sizes

HERE = os.path.dirname(os.path.abspath(__file__))
BYTES = 2  # bf16
STATE_BYTES = 4  # the Mamba state and the convolution's tail are float32
# what one token costs an element of a layer's [N, D] state: the step's
# product with A, its exp, the decay of the state, the input's product with B,
# the sum of the two, the product with C, the sum over N
SCAN_OPS_PER_ELEMENT = 7


def mamba_mixer_params(shape: dict) -> int:
    z, h = sizes(shape), shape["hidden_size"]
    d, n, r, k = z["d_inner"], z["d_state"], z["dt_rank"], z["d_conv"]
    return (h * 2 * d  # in-projection: x and the gate
            + d * k + d  # the depthwise convolution and its bias
            + d * (r + 2 * n)  # x_proj: dt, B, C
            + r + 2 * n  # Jamba's three inner norms
            + r * d + d  # dt_proj and its bias
            + d * n + d  # A_log, D
            + d * h)  # out-projection


def attention_mixer_params(shape: dict) -> int:
    h, d = shape["hidden_size"], sizes(shape)["head_dim"]
    q, kv = shape["num_attention_heads"] * d, shape["num_key_value_heads"] * d
    return h * q + 2 * h * kv + q * h  # no bias anywhere


def mlp_params(shape: dict) -> int:
    return 3 * shape["hidden_size"] * shape["intermediate_size"]


def _embedding(shape: dict) -> int:
    return shape["vocab_size"] * shape["hidden_size"]


def param_count(shape: dict) -> int:
    kinds, h = sizes(shape)["kinds"], shape["hidden_size"]
    layers = (kinds.count("mamba") * mamba_mixer_params(shape)
              + kinds.count("attn") * attention_mixer_params(shape)
              + len(kinds) * (mlp_params(shape) + 2 * h))
    tables = 1 if shape.get("tie_word_embeddings") else 2
    return layers + tables * _embedding(shape) + h  # the final norm


def weight_bytes(shape: dict) -> int:
    return param_count(shape) * BYTES


def kv_bytes_per_token(shape: dict) -> int:
    """K and V of one position over the attention layers."""
    z = sizes(shape)
    return z["kinds"].count("attn") * 2 * shape["num_key_value_heads"] * z["head_dim"] * BYTES


def slot_state_bytes(shape: dict) -> int:
    """One slot's recurrent state over the Mamba layers."""
    z = sizes(shape)
    return z["kinds"].count("mamba") * (z["d_state"] + z["d_conv"] - 1) * z["d_inner"] * STATE_BYTES


def lanes_of(shape: dict) -> int:
    """``--max-batch-size`` of the configuration whose file names this module
    and holds this depth and these widths (one, today)."""
    for path in sorted(glob.glob(os.path.join(HERE, "configs", "*.json"))):
        with open(path) as f:
            cfg = json.load(f)
        if cfg.get("bytes_and_flops") == __name__.rsplit(".", 1)[-1] and all(
                cfg.get(k) == shape.get(k) for k in ("num_hidden_layers", "hidden_size", "vocab_size")):
            flags = cfg["serving"]["server_flags"]
            return int(flags[flags.index("--max-batch-size") + 1])
    raise KeyError("no configuration under configs/ names bytes_and_flops_jamba at this shape")


def decode_step_stream_bytes(shape: dict, live_context_tokens: float, chips: int = 1,
                             lanes: float = None) -> float:
    """Bytes ONE decode step must move: every weight once (a tied embedding
    once, as the head; the lookup reads ``lanes`` rows of it, left out; an
    untied table is read by row and left out), every lane's recurrent state
    read and written, and the K and V of the live context."""
    lanes = lanes_of(shape) if lanes is None else lanes
    streamed = param_count(shape) - (0 if shape.get("tie_word_embeddings") else _embedding(shape))
    return (streamed * BYTES + 2 * lanes * slot_state_bytes(shape)
            + live_context_tokens * kv_bytes_per_token(shape)) / chips


def prefill_chunk_flops(shape: dict, positions: int, mean_context_tokens: float) -> float:
    """Operations of one prefill chunk over ``positions`` query positions: 2 a
    multiply-add in the matrices a token goes through (the embedding lookup
    and the head left out: the program computes the head for the one position
    a row samples), the selective scan (``SCAN_OPS_PER_ELEMENT`` an element of
    a layer's state a token, on the vector unit), the convolution, and
    attention's scores and values against ``mean_context_tokens`` keys a
    query."""
    z = sizes(shape)
    kinds, d = z["kinds"], z["d_inner"]
    mamba = (shape["hidden_size"] * 2 * d + d * (z["dt_rank"] + 2 * z["d_state"])
             + z["dt_rank"] * d + d * shape["hidden_size"])
    matrices = (kinds.count("mamba") * mamba + kinds.count("attn") * attention_mixer_params(shape)
                + len(kinds) * mlp_params(shape))
    scan = kinds.count("mamba") * d * (SCAN_OPS_PER_ELEMENT * z["d_state"] + 2 * z["d_conv"])
    attn = (kinds.count("attn") * 2 * 2 * shape["num_attention_heads"] * z["head_dim"]
            * mean_context_tokens)
    return positions * (2.0 * matrices + scan + attn)
