"""Bytes and operations the algorithm needs, from a configuration's shapes.

Kept with the benchmark so that no PR that claims a gain can move the
denominator. ``shape`` is the published config.json (the ``shape`` group of
``configs/<name>.json``). bf16 everywhere: 2 bytes per element.

The functions below count a Llama/Qwen2 dense decoder. Another architecture
brings a module of its own under ``benchmark/`` with the same
``param_count``, ``kv_bytes_per_token``, ``decode_step_stream_bytes`` and
``prefill_chunk_flops``, and its configuration's file names it under the key
``bytes_and_flops`` (as ``reference`` names its plain reference); readers
take the functions from ``for_config(config)``.
"""

from __future__ import annotations

import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BYTES = 2  # bf16


def for_config(config: dict):
    """The module that counts this configuration: the one its file names
    under ``bytes_and_flops``, or this one."""
    name = config.get("bytes_and_flops")
    return importlib.import_module(f"benchmark.{name}") if name else sys.modules[__name__]


def head_dim(shape: dict) -> int:
    return shape.get("head_dim") or shape["hidden_size"] // shape["num_attention_heads"]


def kv_bytes_per_token(shape: dict) -> int:
    """K and V of one position over all layers."""
    return (shape["num_hidden_layers"] * 2 * shape["num_key_value_heads"]
            * head_dim(shape) * BYTES)


def param_count(shape: dict) -> int:
    h, i, v = shape["hidden_size"], shape["intermediate_size"], shape["vocab_size"]
    q = shape["num_attention_heads"] * head_dim(shape)
    kv = shape["num_key_value_heads"] * head_dim(shape)
    attn = h * q + 2 * h * kv + q * h + q + 2 * kv  # q, k, v, o and the qkv biases
    mlp = 3 * h * i
    norms = 2 * h
    layers = shape["num_hidden_layers"] * (attn + mlp + norms)
    embed = v * h * (1 if shape.get("tie_word_embeddings") else 2)
    return layers + embed + h


def weight_bytes(shape: dict) -> int:
    return param_count(shape) * BYTES


def decode_step_stream_bytes(shape: dict, live_context_tokens: float, chips: int = 1) -> float:
    """Bytes ONE decode step must read on each chip: every weight once (the
    embedding table is read by row, so an untied table is left out and a tied
    one counts once, as the head), plus the K and V of the live context of all
    lanes. The padded part of a dense history buffer is not counted: the
    algorithm does not need it."""
    h, v = shape["hidden_size"], shape["vocab_size"]
    streamed = param_count(shape) - (0 if shape.get("tie_word_embeddings") else v * h)
    return (streamed * BYTES + live_context_tokens * kv_bytes_per_token(shape)) / chips


def prefill_chunk_flops(shape: dict, positions: int, mean_context_tokens: float) -> float:
    """Operations of one prefill chunk over ``positions`` query positions:
    2 per multiply-add in the matrices (embedding lookups left out, the head
    counted once per position), plus attention scores and values against
    ``mean_context_tokens`` keys per query."""
    h, v = shape["hidden_size"], shape["vocab_size"]
    matrices = param_count(shape) - v * h * (1 if shape.get("tie_word_embeddings") else 2) + v * h
    attn = (shape["num_hidden_layers"] * 2 * 2 * shape["num_attention_heads"]
            * head_dim(shape) * mean_context_tokens)
    return positions * (2.0 * matrices + attn)


def load_peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if device_kind not in peaks or device_kind.startswith("_"):
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} in peaks.json"
        )
    return peaks[device_kind]
