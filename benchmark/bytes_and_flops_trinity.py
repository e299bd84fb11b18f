"""Bytes and operations the Trinity decoder needs, from its shapes.

``bytes_and_flops.py``'s four functions for ``model_type: afmoe``
(``configs/trinity-large-preview.json`` names this module under
``bytes_and_flops``). ``shape`` is the configuration's ``config.json``: its
``num_experts`` is what is HELD here of ``num_experts_published``. bf16
weights, 2 bytes an element; the routers and the norms are float32 in the
program and counted at 2 bytes with the rest (0.04 % of the weights); the full
layers' pages and the window layers' rings are float32.

``param_count`` is the number of elements ``models/trinity.py:init_params``
makes (tests/benchmark holds the two equal).

Two lifetimes: a FULL layer keeps every position of a lane (the pool's pages:
``kv_bytes_per_token``), a WINDOW layer the last ``sliding_window`` and one
block more, a ring a slot (``ring_bytes_per_slot``), however long the lane.

A decode step streams every weight OUTSIDE the routed experts once (the shared
expert and the routers among them), the routed experts its lanes HIT, each
lane's rings as far as the window reaches into its history, and its full-layer
pages. ``decode_step_roofline.py`` hands ``decode_step_stream_bytes`` the
OCCUPIED lanes x their mean context as ONE number and no counter, so the lanes
that share it are the ``--max-batch-size`` of the configuration that names this
module (as ``bytes_and_flops_lfm2`` takes them). The routed experts are charged
LOW, for ONE lane's ``num_experts_per_tok`` pairs over ``num_experts_published``
(in ``long.trinity-large-preview`` two or three of the eight lanes decode in a
step and the others prefill: a step hits 0.5-1.5 of a layer's 32 held experts),
so that the share cannot pass 100 % for what this file miscounted. The caches
are charged as they are handed over, the lanes that prefill among them: the
program does read every slot's ring in place, so in a cell whose lanes mostly
prefill the share reads HIGH by the rings of the lanes that do not decode
(PERF.md 7 has the arithmetic).
"""

from __future__ import annotations

import glob
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
BYTES = 2  # bf16
STATE_BYTES = 4  # the pages and the rings are float32
RING_BLOCK = 16  # a ring is the window's positions and one block of this many
WINDOW, FULL = "sliding_attention", "full_attention"


def _kinds(shape: dict) -> list:
    kinds = list(shape["layer_types"])
    assert len(kinds) == shape["num_hidden_layers"], (len(kinds), shape["num_hidden_layers"])
    return kinds


def attention_params(shape: dict) -> int:
    h, d = shape["hidden_size"], shape["head_dim"]
    q, kv = shape["num_attention_heads"] * d, shape["num_key_value_heads"] * d
    return 3 * h * q + 2 * h * kv + 2 * d  # q, gate, o; k, v; one q and one k norm of D; no bias


def dense_ffn_params(shape: dict) -> int:
    return 3 * shape["hidden_size"] * shape["intermediate_size"]


def expert_params(shape: dict) -> int:
    """One expert's three matrices (a routed one's, and the shared one's)."""
    return 3 * shape["hidden_size"] * shape["moe_intermediate_size"]


def _published(shape: dict) -> int:
    return shape.get("num_experts_published", shape["num_experts"])


def router_params(shape: dict) -> int:
    return shape["hidden_size"] * _published(shape) + _published(shape)  # and the selection bias


def _embedding(shape: dict) -> int:
    return shape["vocab_size"] * shape["hidden_size"]


def _expert_layers(shape: dict) -> int:
    return shape["num_hidden_layers"] - shape["num_dense_layers"]


def _outside_experts(shape: dict) -> int:
    """Every parameter but the routed experts' matrices: attention, the dense
    feed-forwards, the shared experts, the routers, four norms a layer, the
    final norm, the embedding and the untied head."""
    h = shape["hidden_size"]
    return (shape["num_hidden_layers"] * (attention_params(shape) + 4 * h)
            + shape["num_dense_layers"] * dense_ffn_params(shape)
            + _expert_layers(shape) * (expert_params(shape) + router_params(shape))
            + h + 2 * _embedding(shape))


def param_count(shape: dict) -> int:
    return (_outside_experts(shape)
            + _expert_layers(shape) * shape["num_experts"] * expert_params(shape))


def weight_bytes(shape: dict) -> int:
    return param_count(shape) * BYTES


def _kv_bytes_a_layer(shape: dict) -> int:
    return 2 * shape["num_key_value_heads"] * shape["head_dim"] * STATE_BYTES


def kv_bytes_per_token(shape: dict) -> int:
    """K and V of one position over the FULL layers: what a token costs the
    pool. The window layers keep nothing by the token."""
    return _kinds(shape).count(FULL) * _kv_bytes_a_layer(shape)


def ring_positions(shape: dict) -> int:
    return shape["sliding_window"] + RING_BLOCK


def ring_bytes_per_slot(shape: dict) -> int:
    """One slot's rings over the WINDOW layers, whatever the lane's length."""
    return _kinds(shape).count(WINDOW) * ring_positions(shape) * _kv_bytes_a_layer(shape)


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
        raise KeyError("no configuration under configs/ names bytes_and_flops_trinity at this shape")
    flags = cfg["serving"]["server_flags"]
    return int(flags[flags.index("--max-batch-size") + 1])


def experts_hit_share(shape: dict, lanes: float = 1.0) -> float:
    """The share of the held experts one decode step reads: what even routing
    of ``lanes x num_experts_per_tok`` pairs over all the published experts
    hits of any one of them. ONE lane where the caller knows no better: the
    least a step that decodes at all can hit."""
    return 1.0 - (1.0 - shape["num_experts_per_tok"] / _published(shape)) ** lanes


def windowed_context(shape: dict, mean_context_tokens: float) -> float:
    """The keys a window layer's query sees on average, where a full layer's
    sees ``mean_context_tokens``: queries spread evenly over a sequence of
    twice that (the mean of ``min(p, window)`` over ``p`` in ``[0, 2 m]``)."""
    w, m = float(shape["sliding_window"]), float(mean_context_tokens)
    return m if 2.0 * m <= w else w - w * w / (4.0 * m)


def decode_step_stream_bytes(shape: dict, live_context_tokens: float, chips: int = 1,
                             lanes: float = None) -> float:
    """Bytes ONE decode step must read: every weight outside the routed experts
    once (the untied embedding is read by row and left out), the routed experts
    ONE lane hits, each lane's rings capped at the window and its full-layer
    pages."""
    lanes = lanes_of(shape) if lanes is None else lanes
    kinds = _kinds(shape)
    outside = _outside_experts(shape) - _embedding(shape)
    experts = _expert_layers(shape) * shape["num_experts"] * experts_hit_share(shape) * expert_params(shape)
    in_window = lanes * min(live_context_tokens / max(lanes, 1e-9), float(shape["sliding_window"]))
    cache = (kinds.count(WINDOW) * in_window + kinds.count(FULL) * live_context_tokens) \
        * _kv_bytes_a_layer(shape)
    return ((outside + experts) * BYTES + cache) / chips


def prefill_chunk_flops(shape: dict, positions: int, mean_context_tokens: float) -> float:
    """Operations of one prefill chunk over ``positions`` query positions: 2 a
    multiply-add in the matrices a token goes through, each product counted
    ONCE whatever the parts the program takes it in (the attention layers' five
    projections, the dense feed-forward, the shared expert, the router at its
    published width and the share of a token's ``num_experts_per_tok`` experts
    that is held here; the embedding lookup and the head left out: the program
    computes the head for the one position a row samples), and attention's
    scores and values against ``mean_context_tokens`` keys a query in a full
    layer and :func:`windowed_context` of them in a window layer."""
    kinds, h = _kinds(shape), shape["hidden_size"]
    held = shape["num_experts_per_tok"] * shape["num_experts"] / _published(shape)
    matrices = (len(kinds) * (attention_params(shape) - 2 * shape["head_dim"])
                + shape["num_dense_layers"] * dense_ffn_params(shape)
                + _expert_layers(shape) * ((1 + held) * expert_params(shape) + h * _published(shape)))
    keys = (kinds.count(FULL) * mean_context_tokens
            + kinds.count(WINDOW) * windowed_context(shape, mean_context_tokens))
    attn = 2 * 2 * shape["num_attention_heads"] * shape["head_dim"] * keys
    return positions * (2.0 * matrices + attn)
