"""Bytes and operations the Kimi-Linear decoder needs, from its shapes.

``bytes_and_flops.py``'s four functions for ``model_type: kimi_linear``
(``configs/kimi-linear-48b-a3b.json`` names this module under
``bytes_and_flops``). ``shape`` is the configuration's ``config.json``, the
KDA group in either spelling (``reference_kimi_linear.sizes``). bf16 weights,
2 bytes an element; the program's activations are float32, and with them the
latent pages, the KDA layers' per-slot state and the convolutions' tails.

``param_count`` is the number of elements ``models/kimi_linear.py:init_params``
makes (tests/benchmark holds the two equal): the experts HELD here
(``num_experts``), of the ``num_experts_published`` the router scores.

A decode step of this model streams what depends on the lanes, not on their
context: the experts a step's tokens hit, and the lanes' recurrent state.
``decode_step_roofline.py`` hands ``decode_step_stream_bytes`` the lanes x
their mean context as ONE number and not the lane count, so this module takes
the lanes from the ``--max-batch-size`` of the configuration that names it
(the closed loop keeps 98 % of the slots busy: ``batch_occupancy``).
"""

from __future__ import annotations

import glob
import json
import os

from benchmark.reference_kimi_linear import sizes

HERE = os.path.dirname(os.path.abspath(__file__))
BYTES = 2  # bf16
STATE_BYTES = 4  # latent pages, KDA state and convolution tails are float32


def _kinds(shape: dict) -> list:
    full = sizes(shape)["full_attn_layers"]
    return ["mla" if i in full else "kda" for i in range(1, shape["num_hidden_layers"] + 1)]


def _kda_dim(shape: dict) -> int:
    z = sizes(shape)
    return z["kda_heads"] * z["kda_head_dim"]


def kda_mixer_params(shape: dict) -> int:
    z, h, d = sizes(shape), shape["hidden_size"], _kda_dim(shape)
    rank = z["kda_head_dim"]  # of the decay gate and the output gate
    return (3 * h * d + d * h  # q, k, v, o
            + 3 * z["conv_kernel"] * d  # the short convolutions
            + 2 * (h * rank + rank * d)  # the two low-rank gates
            + h * z["kda_heads"]  # beta
            + z["kda_heads"] + d + z["kda_head_dim"])  # a_log, dt_bias, the output norm


def mla_mixer_params(shape: dict) -> int:
    h, heads, r = shape["hidden_size"], shape["num_attention_heads"], shape["kv_lora_rank"]
    dn, dr, dv = shape["qk_nope_head_dim"], shape["qk_rope_head_dim"], shape["v_head_dim"]
    return h * heads * (dn + dr) + h * (r + dr) + r + r * heads * (dn + dv) + heads * dv * h


def expert_params(shape: dict) -> int:
    """One routed expert."""
    return 3 * shape["hidden_size"] * shape["moe_intermediate_size"]


def expert_layer_fixed_params(shape: dict) -> int:
    """What an expert layer's feed-forward holds beside its routed experts:
    the router with its selection bias, and the shared expert."""
    total = sizes(shape)["experts_total"]
    return (shape["hidden_size"] * total + total
            + shape.get("num_shared_experts", 1) * expert_params(shape))


def param_count(shape: dict) -> int:
    h, v = shape["hidden_size"], shape["vocab_size"]
    n = 0
    for i, kind in enumerate(_kinds(shape)):
        n += 2 * h + (mla_mixer_params if kind == "mla" else kda_mixer_params)(shape)
        if i < shape["first_k_dense_replace"]:
            n += 3 * h * shape["intermediate_size"]
        else:
            n += shape["num_experts"] * expert_params(shape) + expert_layer_fixed_params(shape)
    return n + 2 * v * h + h  # embedding, head, final norm


def weight_bytes(shape: dict) -> int:
    return param_count(shape) * BYTES


def kv_bytes_per_token(shape: dict) -> int:
    """The latent and the shared key part of one position, over the MLA layers."""
    return _kinds(shape).count("mla") * (shape["kv_lora_rank"] + shape["qk_rope_head_dim"]) * STATE_BYTES


def slot_state_bytes(shape: dict) -> int:
    """One slot's recurrent state over the KDA layers: the float32 matrix of
    every head and the convolutions' tails."""
    z = sizes(shape)
    per_layer = (z["kda_heads"] * z["kda_head_dim"] ** 2 * STATE_BYTES
                 + (z["conv_kernel"] - 1) * 3 * _kda_dim(shape) * STATE_BYTES)
    return _kinds(shape).count("kda") * per_layer


def experts_hit(shape: dict, lanes: float) -> float:
    """Held experts that a step of ``lanes`` tokens is expected to hit, under
    even routing: ``held * (1 - (1 - k / total) ** lanes)``."""
    total = sizes(shape)["experts_total"]
    return shape["num_experts"] * (1.0 - (1.0 - shape["num_experts_per_token"] / total) ** lanes)


def lanes_of(shape: dict) -> int:
    """``--max-batch-size`` of the configuration whose file names this module
    and holds this depth and these experts (one, today)."""
    for path in sorted(glob.glob(os.path.join(HERE, "configs", "*.json"))):
        with open(path) as f:
            cfg = json.load(f)
        if cfg.get("bytes_and_flops") == __name__.rsplit(".", 1)[-1] and all(
                cfg.get(k) == shape.get(k) for k in ("num_hidden_layers", "num_experts", "vocab_size")):
            flags = cfg["serving"]["server_flags"]
            return int(flags[flags.index("--max-batch-size") + 1])
    raise KeyError("no configuration under configs/ names bytes_and_flops_kimi_linear at this shape")


def decode_step_stream_bytes(shape: dict, live_context_tokens: float, chips: int = 1,
                             lanes: float = None) -> float:
    """Bytes ONE decode step must move: the weights outside the routed experts
    once (the embedding table is read by row and left out), the held experts
    the step's tokens are expected to hit, every lane's recurrent state read
    and written, and the latent of the live context."""
    lanes = lanes_of(shape) if lanes is None else lanes
    expert_layers = shape["num_hidden_layers"] - shape["first_k_dense_replace"]
    routed = expert_layers * shape["num_experts"] * expert_params(shape)
    fixed = param_count(shape) - routed - shape["vocab_size"] * shape["hidden_size"]
    hit = expert_layers * experts_hit(shape, lanes) * expert_params(shape)
    return ((fixed + hit) * BYTES + 2 * lanes * slot_state_bytes(shape)
            + live_context_tokens * kv_bytes_per_token(shape)) / chips


def prefill_chunk_flops(shape: dict, positions: int, mean_context_tokens: float) -> float:
    """Operations of one prefill chunk over ``positions`` query positions: 2 a
    multiply-add in the matrices a token goes through (the experts it is
    routed to that are held here, in expectation: ``k * held / total``; the
    head once a position; embedding lookups left out), the delta rule (three
    passes over a head's d_k x d_v state a token), and latent attention
    against ``mean_context_tokens`` keys a query in the absorbed form."""
    z, h, v = sizes(shape), shape["hidden_size"], shape["vocab_size"]
    expert_layers = shape["num_hidden_layers"] - shape["first_k_dense_replace"]
    routed_all = expert_layers * shape["num_experts"] * expert_params(shape)
    routed_token = (expert_layers * expert_params(shape) * shape["num_experts_per_token"]
                    * shape["num_experts"] / z["experts_total"])
    matrices = param_count(shape) - routed_all - 2 * v * h + v * h + routed_token
    kinds = _kinds(shape)
    delta = kinds.count("kda") * 3 * 2 * z["kda_heads"] * z["kda_head_dim"] ** 2
    attn = (kinds.count("mla") * 2 * shape["num_attention_heads"]
            * (2 * shape["kv_lora_rank"] + shape["qk_rope_head_dim"]) * mean_context_tokens)
    return positions * (2.0 * matrices + delta + attn)
