"""Bytes and operations the Mellum 2 decoder needs, from its shapes.

``bytes_and_flops.py``'s four functions for ``model_type: mellum``
(``configs/mellum2-12b-a2.5b-tp4.json`` names this module under
``bytes_and_flops``). ``shape`` is the configuration's ``config.json``: the
model WHOLE (nothing is cut); what ONE chip of the deployment streams is the
whole's share, ``chips`` ways. bf16 matrices, 2 bytes an element; the routers
and the norms float32, 4 bytes, and on every chip; the full layers' pages and
the window layers' rings float32.

``param_count`` is the number of elements ``models/mellum.py:init_params``
makes and ``weight_bytes`` the bytes of its tree (tests/benchmark holds both
to the element).

Two lifetimes: a FULL layer keeps every position of a lane (the pool's pages:
``kv_bytes_per_token``), a WINDOW layer the last ``sliding_window`` and one
block more, a ring a slot (``ring_bytes_per_slot``), however long the lane;
both lie one KV head a chip on four chips.

A decode step on a chip streams its share of every matrix OUTSIDE the experts
once (the untied embedding is read by row and left out), the routers and norms
whole, the share of its experts that the step's lanes HIT, its KV head of each
lane's rings as far as the window reaches into the lane's history, and its KV
head of the full layers' pages. ``decode_step_roofline.py`` hands
``decode_step_stream_bytes`` the OCCUPIED lanes x their mean context as ONE
number and no counter of the lanes that decode. The experts are charged as
ISSUE 68 set them: what even routing of the deployment's ``slots`` lanes x
``num_experts_per_tok`` pairs over ``num_experts`` hits (16 lanes: 88 % of a
chip's experts a layer). In ``code.mellum2-12b-a2.5b-tp4`` about half the
occupied lanes prefill at any step, so the charge is the step's when every slot
decodes and HIGH by the experts the prefilling lanes would have hit (one lane
hits an eighth; PERF.md 7 brackets the share between the two); ``lanes=`` is
there for a caller that knows the number, which the module's counters
``moe_experts_hit`` / ``moe_expert_reads`` give once a reader hands them over
(ROADMAP B11). The caches are charged as they are handed over, the lanes that
prefill among them (``bytes_and_flops_trinity`` says what that does to the
share).
"""

from __future__ import annotations

BYTES = 2  # bf16
FLOAT32 = 4  # routers, norms, pages and rings
RING_BLOCK = 16  # a ring is the window's positions and one block of this many
WINDOW, FULL = "sliding_attention", "full_attention"


def _kinds(shape: dict) -> list:
    kinds = list(shape["layer_types"])
    assert len(kinds) == shape["num_hidden_layers"], (len(kinds), shape["num_hidden_layers"])
    return kinds


def attention_matrices(shape: dict) -> int:
    h, d = shape["hidden_size"], shape["head_dim"]
    q, kv = shape["num_attention_heads"] * d, shape["num_key_value_heads"] * d
    return 2 * h * q + 2 * h * kv  # q and o; k and v; no bias, no gate


def expert_params(shape: dict) -> int:
    """One expert's three matrices."""
    return 3 * shape["hidden_size"] * shape["moe_intermediate_size"]


def replicated_params(shape: dict) -> int:
    """The float32 leaves, which every chip holds whole: a router, two layer
    norms and the two head norms a layer, and the final norm."""
    h = shape["hidden_size"]
    return shape["num_hidden_layers"] * (h * shape["num_experts"] + 2 * h + 2 * shape["head_dim"]) + h


def _embedding(shape: dict) -> int:
    return shape["vocab_size"] * shape["hidden_size"]


def _experts(shape: dict) -> int:
    return shape["num_hidden_layers"] * shape["num_experts"] * expert_params(shape)


def param_count(shape: dict) -> int:
    return (shape["num_hidden_layers"] * attention_matrices(shape) + _experts(shape)
            + replicated_params(shape) + 2 * _embedding(shape))


def weight_bytes(shape: dict) -> int:
    """The tree's bytes: every matrix bf16, the routers and norms float32."""
    return (param_count(shape) - replicated_params(shape)) * BYTES + replicated_params(shape) * FLOAT32


def weight_bytes_per_chip(shape: dict, chips: int) -> int:
    """What ``param_shardings`` leaves on one of ``chips`` chips: a share of
    every matrix, the routers and norms whole."""
    return (param_count(shape) - replicated_params(shape)) * BYTES // chips + replicated_params(shape) * FLOAT32


def _kv_bytes_a_layer(shape: dict) -> int:
    return 2 * shape["num_key_value_heads"] * shape["head_dim"] * FLOAT32


def kv_bytes_per_token(shape: dict) -> int:
    """K and V of one position over the FULL layers: what a token costs the
    pool (all chips together). The window layers keep nothing by the token."""
    return _kinds(shape).count(FULL) * _kv_bytes_a_layer(shape)


def ring_positions(shape: dict) -> int:
    return shape["sliding_window"] + RING_BLOCK


def ring_bytes_per_slot(shape: dict) -> int:
    """One slot's rings over the WINDOW layers, whatever the lane's length."""
    return _kinds(shape).count(WINDOW) * ring_positions(shape) * _kv_bytes_a_layer(shape)


def experts_hit_share(shape: dict, lanes: float = 1.0) -> float:
    """The share of a layer's experts one decode step reads: what even routing
    of ``lanes x num_experts_per_tok`` pairs over the experts hits of any one."""
    return 1.0 - (1.0 - shape["num_experts_per_tok"] / shape["num_experts"]) ** lanes


def windowed_context(shape: dict, mean_context_tokens: float) -> float:
    """The keys a window layer's query sees on average, where a full layer's
    sees ``mean_context_tokens``: queries spread evenly over a sequence of
    twice that (the mean of ``min(p, window)`` over ``p`` in ``[0, 2 m]``)."""
    w, m = float(shape["sliding_window"]), float(mean_context_tokens)
    return m if 2.0 * m <= w else w - w * w / (4.0 * m)


def decode_step_stream_bytes(shape: dict, live_context_tokens: float, chips: int = 1,
                             lanes: float = None, slots: float = 16.0) -> float:
    """Bytes ONE decode step must read on ONE of ``chips`` chips: its share of
    every matrix outside the experts once (the embedding by row: left out), the
    routers and norms whole, the share of its experts that ``lanes`` decoding
    lanes hit (every one of the ``slots``, where the caller knows no better),
    and of the ``live_context_tokens`` (``slots`` lanes' at the most: a lane's
    ring reaches ``sliding_window`` back) its KV head."""
    lanes = slots if lanes is None else lanes
    kinds = _kinds(shape)
    outside = shape["num_hidden_layers"] * attention_matrices(shape) + _embedding(shape)
    experts = _experts(shape) * experts_hit_share(shape, lanes)
    in_window = slots * min(live_context_tokens / slots, float(shape["sliding_window"]))
    cache = (kinds.count(WINDOW) * in_window + kinds.count(FULL) * live_context_tokens) \
        * _kv_bytes_a_layer(shape)
    return ((outside + experts) * BYTES + cache) / chips + replicated_params(shape) * FLOAT32


def prefill_chunk_flops(shape: dict, positions: int, mean_context_tokens: float) -> float:
    """Operations of one prefill chunk over ``positions`` query positions, the
    WHOLE model's (all chips together): 2 a multiply-add in the matrices a
    token goes through, each product counted ONCE whatever the parts the
    program takes it in (the four attention projections, the router, a token's
    ``num_experts_per_tok`` experts; the embedding lookup and the head left out:
    the program computes the head for the one position a row samples), and
    attention's scores and values against ``mean_context_tokens`` keys a query
    in a full layer and :func:`windowed_context` of them in a window layer."""
    kinds, h = _kinds(shape), shape["hidden_size"]
    matrices = len(kinds) * (attention_matrices(shape) + h * shape["num_experts"]
                             + shape["num_experts_per_tok"] * expert_params(shape))
    keys = (kinds.count(FULL) * mean_context_tokens
            + kinds.count(WINDOW) * windowed_context(shape, mean_context_tokens))
    attn = 2 * 2 * shape["num_attention_heads"] * shape["head_dim"] * keys
    return positions * (2.0 * matrices + attn)
