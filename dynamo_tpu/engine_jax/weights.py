"""Model config resolution and weight loading for the JAX engine.

Maps an HF-layout model directory (config.json + *.safetensors) onto the
framework's stacked-layer parameter pytree (models/llama.py). Directories
without weight files get deterministic random init — enough for echo-free
serving-path tests and synthetic benchmarks.

Reference analogue: model resolution in launch/dynamo-run (hub.rs,
model_card/create.rs:41-143); actual weight loading lives in the delegated
engines there — here it is framework-native.
"""

from __future__ import annotations

import glob
import logging
import math
import os
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.llm.model_card import ModelDeploymentCard
from dynamo_tpu.models import module_for
from dynamo_tpu.models.llama import LlamaConfig

logger = logging.getLogger(__name__)


def kimi_linear_config(mc: Dict[str, Any], dtype: Any = jnp.bfloat16):
    """``model_type: kimi_linear``. The KDA group is read from the published
    nested ``linear_attn_config`` where the card has it, else from its flat
    spelling (``linear_attn_num_heads``, ``linear_attn_head_dim``,
    ``short_conv_kernel_size``, ``kda_layers``, ``full_attn_layers``: a
    harness that writes only scalar and list keys). ``num_experts`` is the
    count held here; ``num_experts_published``, where given, the router's."""
    from dynamo_tpu.models.kimi_linear import KimiLinearConfig

    group = mc.get("linear_attn_config") or {}

    def kda(nested: str, flat: str):
        return group[nested] if nested in group else mc[flat]

    experts = int(mc["num_experts"])
    return KimiLinearConfig(
        vocab_size=int(mc["vocab_size"]),
        hidden_size=int(mc["hidden_size"]),
        intermediate_size=int(mc["intermediate_size"]),
        num_layers=int(mc["num_hidden_layers"]),
        num_heads=int(mc["num_attention_heads"]),
        kv_lora_rank=int(mc["kv_lora_rank"]),
        qk_nope_head_dim=int(mc["qk_nope_head_dim"]),
        qk_rope_head_dim=int(mc["qk_rope_head_dim"]),
        v_head_dim=int(mc["v_head_dim"]),
        kda_heads=int(kda("num_heads", "linear_attn_num_heads")),
        kda_head_dim=int(kda("head_dim", "linear_attn_head_dim")),
        conv_kernel=int(kda("short_conv_kernel_size", "short_conv_kernel_size")),
        kda_layers=tuple(int(i) for i in kda("kda_layers", "kda_layers")),
        full_attn_layers=tuple(int(i) for i in kda("full_attn_layers", "full_attn_layers")),
        first_k_dense=int(mc.get("first_k_dense_replace", 1)),
        moe_intermediate_size=int(mc["moe_intermediate_size"]),
        num_experts=experts,
        num_experts_published=int(mc.get("num_experts_published", experts)),
        num_experts_per_tok=int(mc["num_experts_per_token"]),
        num_shared_experts=int(mc.get("num_shared_experts", 1)),
        routed_scaling_factor=float(mc.get("routed_scaling_factor", 1.0)),
        moe_renormalize=bool(mc.get("moe_renormalize", True)),
        rms_norm_eps=float(mc.get("rms_norm_eps", 1e-5)),
        tie_embeddings=bool(mc.get("tie_word_embeddings", False)),
        dtype=dtype,
    )


def jamba_config(mc: Dict[str, Any], dtype: Any = jnp.bfloat16):
    """``model_type: jamba``: Mamba layers beside attention layers, every
    feed-forward dense. A card with experts is refused by name: no module
    here runs an expert Jamba."""
    from dynamo_tpu.models.jamba import JambaConfig

    if int(mc.get("num_experts", 1)) > 1:
        raise ValueError(
            f"model_type 'jamba' with num_experts = {mc['num_experts']}: "
            "models/jamba.py runs the dense feed-forward only (num_experts 1)"
        )
    hidden, heads = int(mc["hidden_size"]), int(mc["num_attention_heads"])
    return JambaConfig(
        vocab_size=int(mc["vocab_size"]),
        hidden_size=hidden,
        intermediate_size=int(mc["intermediate_size"]),
        num_layers=int(mc["num_hidden_layers"]),
        num_heads=heads,
        num_kv_heads=int(mc.get("num_key_value_heads", heads)),
        head_dim=int(mc.get("head_dim") or hidden // heads),
        attn_layer_period=int(mc["attn_layer_period"]),
        attn_layer_offset=int(mc["attn_layer_offset"]),
        mamba_expand=int(mc.get("mamba_expand", 2)),
        mamba_d_state=int(mc.get("mamba_d_state", 16)),
        mamba_dt_rank=int(mc["mamba_dt_rank"]),
        mamba_d_conv=int(mc.get("mamba_d_conv", 4)),
        rms_norm_eps=float(mc.get("rms_norm_eps", 1e-6)),
        tie_embeddings=bool(mc.get("tie_word_embeddings", True)),
        dtype=dtype,
    )


def lfm2_config(mc: Dict[str, Any], dtype: Any = jnp.bfloat16):
    """``model_type: lfm2_moe``: gated short convolutions beside rotary
    attention layers, dense feed-forwards first and experts after. The
    rotation's base is read from the published ``rope_parameters`` group where
    the card has it, else from a flat ``rope_theta`` (a harness that writes
    only scalar and list keys)."""
    from dynamo_tpu.models.lfm2 import Lfm2Config

    if mc.get("conv_bias", False):
        raise ValueError("model_type 'lfm2_moe' with conv_bias: models/lfm2.py has no bias anywhere")
    hidden, heads = int(mc["hidden_size"]), int(mc["num_attention_heads"])
    rope = mc.get("rope_parameters") or {}
    return Lfm2Config(
        vocab_size=int(mc["vocab_size"]),
        hidden_size=hidden,
        intermediate_size=int(mc["intermediate_size"]),
        num_layers=int(mc["num_hidden_layers"]),
        num_heads=heads,
        num_kv_heads=int(mc.get("num_key_value_heads", heads)),
        head_dim=int(mc.get("head_dim") or hidden // heads),
        layer_types=tuple(str(kind) for kind in mc["layer_types"]),
        num_dense_layers=int(mc.get("num_dense_layers", 2)),
        conv_kernel=int(mc.get("conv_L_cache", 3)),
        moe_intermediate_size=int(mc["moe_intermediate_size"]),
        num_experts=int(mc["num_experts"]),
        num_experts_per_tok=int(mc["num_experts_per_tok"]),
        norm_topk_prob=bool(mc.get("norm_topk_prob", True)),
        use_expert_bias=bool(mc.get("use_expert_bias", True)),
        routed_scaling_factor=float(mc.get("routed_scaling_factor", 1.0)),
        norm_eps=float(mc.get("norm_eps", 1e-5)),
        rope_theta=float(rope["rope_theta"] if "rope_theta" in rope else mc.get("rope_theta", 1000000.0)),
        tie_embeddings=bool(mc.get("tie_word_embeddings", True)),
        dtype=dtype,
    )


def qwen3_next_config(mc: Dict[str, Any], dtype: Any = jnp.bfloat16):
    """``model_type: qwen3_next``: Gated DeltaNet layers beside gated attention
    layers (``layer_types`` where the card has the list, else every
    ``full_attention_interval``-th layer attends), an expert layer after every
    mixer. ``num_experts`` is what is HELD here of the ``num_experts_published``
    the router scores (a card without the second holds them all). What
    ``models/qwen3_next.py`` does not run is refused by name."""
    from dynamo_tpu.models.qwen3_next import Qwen3NextConfig, layer_kinds

    if mc.get("mlp_only_layers"):
        raise ValueError(
            f"model_type 'qwen3_next' with mlp_only_layers = {mc['mlp_only_layers']}: "
            "models/qwen3_next.py runs an expert layer after every mixer")
    if int(mc.get("decoder_sparse_step", 1)) != 1:
        raise ValueError(
            f"model_type 'qwen3_next' with decoder_sparse_step = {mc['decoder_sparse_step']}: "
            "models/qwen3_next.py runs an expert layer after every mixer (1)")
    if mc.get("rope_scaling"):
        raise ValueError(
            f"model_type 'qwen3_next' with rope_scaling = {mc['rope_scaling']}: "
            "models/qwen3_next.py rotates by rope_theta alone")
    layers, experts = int(mc["num_hidden_layers"]), int(mc["num_experts"])
    kinds = mc.get("layer_types") or layer_kinds(layers, int(mc.get("full_attention_interval", 4)))
    return Qwen3NextConfig(
        vocab_size=int(mc["vocab_size"]),
        hidden_size=int(mc["hidden_size"]),
        num_layers=layers,
        layer_types=tuple(str(kind) for kind in kinds),
        num_heads=int(mc["num_attention_heads"]),
        num_kv_heads=int(mc["num_key_value_heads"]),
        head_dim=int(mc["head_dim"]),
        partial_rotary_factor=float(mc.get("partial_rotary_factor", 0.25)),
        rope_theta=float(mc.get("rope_theta", 10000000.0)),
        linear_num_key_heads=int(mc["linear_num_key_heads"]),
        linear_num_value_heads=int(mc["linear_num_value_heads"]),
        linear_key_head_dim=int(mc["linear_key_head_dim"]),
        linear_value_head_dim=int(mc["linear_value_head_dim"]),
        linear_conv_kernel_dim=int(mc.get("linear_conv_kernel_dim", 4)),
        moe_intermediate_size=int(mc["moe_intermediate_size"]),
        shared_expert_intermediate_size=int(mc["shared_expert_intermediate_size"]),
        num_experts=experts,
        num_experts_published=int(mc.get("num_experts_published", experts)),
        num_experts_per_tok=int(mc["num_experts_per_tok"]),
        norm_topk_prob=bool(mc.get("norm_topk_prob", True)),
        rms_norm_eps=float(mc.get("rms_norm_eps", 1e-6)),
        tie_embeddings=bool(mc.get("tie_word_embeddings", False)),
        dtype=dtype,
    )


def openpangu_config(mc: Dict[str, Any], dtype: Any = jnp.bfloat16):
    """``model_type: pangu_ultra_moe``: latent attention in every layer with a
    compressed query and a rotated key part, the sandwich norm, dense
    feed-forwards in the first ``first_k_dense_replace`` layers and
    sigmoid-routed experts beside one shared expert after them, one
    multi-token-prediction module. ``n_routed_experts`` is what is HELD here of
    the ``n_routed_experts_published`` the router scores (a card without the
    second holds them all). What ``models/openpangu.py`` does not run is
    refused by name."""
    from dynamo_tpu.models.openpangu import OpenPanguConfig

    def refuse(key: str, why: str):
        raise ValueError(f"model_type 'pangu_ultra_moe' with {key} = {mc[key]!r}: models/openpangu.py {why}")

    if mc.get("rope_scaling"):
        refuse("rope_scaling", "rotates by rope_theta alone, with no softmax-scale correction")
    if mc.get("attention_bias"):
        refuse("attention_bias", "has no bias in its attention projections")
    if not mc.get("sandwich_norm", True):
        refuse("sandwich_norm", "runs four norms a layer (the sandwich)")
    if int(mc.get("n_shared_experts", 1)) != 1:
        refuse("n_shared_experts", "runs one shared expert beside the routed ones")
    if int(mc.get("num_nextn_predict_layers", 1)) > 1:
        refuse("num_nextn_predict_layers", "holds one multi-token-prediction module at most")
    heads = int(mc["num_attention_heads"])
    if int(mc.get("num_key_value_heads", heads)) != heads:
        refuse("num_key_value_heads", "expands every head's keys from the one latent (= num_attention_heads)")
    experts = int(mc["n_routed_experts"])
    return OpenPanguConfig(
        vocab_size=int(mc["vocab_size"]),
        hidden_size=int(mc["hidden_size"]),
        intermediate_size=int(mc["intermediate_size"]),
        num_layers=int(mc["num_hidden_layers"]),
        num_heads=heads,
        q_lora_rank=int(mc["q_lora_rank"]),
        kv_lora_rank=int(mc["kv_lora_rank"]),
        qk_nope_head_dim=int(mc["qk_nope_head_dim"]),
        qk_rope_head_dim=int(mc["qk_rope_head_dim"]),
        v_head_dim=int(mc["v_head_dim"]),
        rope_theta=float(mc.get("rope_theta", 25600000.0)),
        first_k_dense=int(mc.get("first_k_dense_replace", 3)),
        moe_intermediate_size=int(mc["moe_intermediate_size"]),
        num_experts=experts,
        num_experts_published=int(mc.get("n_routed_experts_published", experts)),
        num_experts_per_tok=int(mc["num_experts_per_tok"]),
        routed_scaling_factor=float(mc.get("routed_scaling_factor", 2.5)),
        moe_renormalize=bool(mc.get("norm_topk_prob", True)),
        num_mtp_layers=int(mc.get("num_nextn_predict_layers", 1)),
        rms_norm_eps=float(mc.get("rms_norm_eps", 1e-5)),
        dtype=dtype,
    )


def xing4_config(mc: Dict[str, Any], dtype: Any = jnp.bfloat16):
    """``model_type: xing4_0``: openPangu's block at other widths (latent
    attention in every layer, dense feed-forwards first and sigmoid-routed
    experts beside one shared expert after them, one multi-token-prediction
    module), pre-norm, under ``hc_mult`` residual streams mixed by
    manifold-constrained hyper-connections, rotated with YaRN's frequencies,
    its experts selected under a bias (``noaux_tc``, one group). The YaRN group
    is read from the published nested ``rope_scaling`` where the card has it,
    else from its flat spelling (``rope_scaling_type``, ``rope_scaling_factor``,
    ...: a harness that writes only scalar and list keys); a card with neither
    rotates by ``rope_theta`` alone. What ``models/xing4.py`` does not run is
    refused by name."""
    from dynamo_tpu.models.xing4 import Xing4Config

    def refuse(key: str, why: str, value=None):
        raise ValueError(f"model_type 'xing4_0' with {key} = {mc.get(key, value)!r}: models/xing4.py {why}")

    yarn = mc.get("rope_scaling") or {
        k[len("rope_scaling_"):]: v for k, v in mc.items() if k.startswith("rope_scaling_")}
    if yarn and yarn.get("type", yarn.get("rope_type")) != "yarn":
        refuse("rope_scaling", "scales its rotary frequencies by YaRN (type yarn) or not at all", yarn)
    if yarn and float(yarn.get("mscale", 1.0)) != float(yarn.get("mscale_all_dim", 0.0)):
        refuse("rope_scaling", "multiplies cosine and sine by 1 (mscale = mscale_all_dim)", yarn)
    for key in ("n_group", "topk_group"):
        if int(mc.get(key, 1)) != 1:
            refuse(key, "chooses its experts in one group (1)")
    if mc.get("scoring_func", "sigmoid") != "sigmoid":
        refuse("scoring_func", "scores its experts by a sigmoid")
    if mc.get("topk_method", "noaux_tc") != "noaux_tc":
        refuse("topk_method", "chooses under a selection bias (noaux_tc)")
    if int(mc.get("n_shared_experts", 1)) != 1:
        refuse("n_shared_experts", "runs one shared expert beside the routed ones")
    if int(mc.get("num_nextn_predict_layers", 1)) > 1:
        refuse("num_nextn_predict_layers", "holds one multi-token-prediction module at most")
    if mc.get("attention_bias"):
        refuse("attention_bias", "has no bias in its attention projections")
    if int(mc.get("hc_mult", 4)) < 2:
        refuse("hc_mult", "mixes two residual streams at least (a one-stream card is another module's)")
    heads = int(mc["num_attention_heads"])
    if int(mc.get("num_key_value_heads", heads)) != heads:
        refuse("num_key_value_heads", "expands every head's keys from the one latent (= num_attention_heads)")
    experts = int(mc["n_routed_experts"])
    return Xing4Config(
        vocab_size=int(mc["vocab_size"]),
        hidden_size=int(mc["hidden_size"]),
        intermediate_size=int(mc["intermediate_size"]),
        num_layers=int(mc["num_hidden_layers"]),
        num_heads=heads,
        q_lora_rank=int(mc["q_lora_rank"]),
        kv_lora_rank=int(mc["kv_lora_rank"]),
        qk_nope_head_dim=int(mc["qk_nope_head_dim"]),
        qk_rope_head_dim=int(mc["qk_rope_head_dim"]),
        v_head_dim=int(mc["v_head_dim"]),
        rope_theta=float(mc.get("rope_theta", 10000.0)),
        first_k_dense=int(mc.get("first_k_dense_replace", 2)),
        moe_intermediate_size=int(mc["moe_intermediate_size"]),
        num_experts=experts,
        num_experts_published=int(mc.get("n_routed_experts_published", experts)),
        num_experts_per_tok=int(mc["num_experts_per_tok"]),
        routed_scaling_factor=float(mc.get("routed_scaling_factor", 2.0)),
        moe_renormalize=bool(mc.get("norm_topk_prob", True)),
        num_mtp_layers=int(mc.get("num_nextn_predict_layers", 1)),
        rms_norm_eps=float(mc.get("rms_norm_eps", 1e-6)),
        hc_mult=int(mc.get("hc_mult", 4)),
        hc_sinkhorn_iters=int(mc.get("hc_sinkhorn_iters", 20)),
        hc_eps=float(mc.get("hc_eps", 1e-6)),
        hc_clamp=(float(mc.get("mhc_h_res_clamp_min", -30.0)), float(mc.get("mhc_h_res_clamp_max", 30.0))),
        yarn_factor=float(yarn["factor"]) if yarn else None,
        yarn_original_positions=int(yarn.get("original_max_position_embeddings", 4096)),
        yarn_beta_fast=float(yarn.get("beta_fast", 32.0)),
        yarn_beta_slow=float(yarn.get("beta_slow", 1.0)),
        yarn_mscale_all_dim=float(yarn.get("mscale_all_dim", 0.0)),
        dtype=dtype,
    )


def afmoe_config(mc: Dict[str, Any], dtype: Any = jnp.bfloat16):
    """``model_type: afmoe`` (Trinity): window attention layers (rotary,
    ``sliding_window`` positions) beside full attention layers (no positional
    encoding) as ``layer_types`` names them, both with q/k norms and a sigmoid
    gate; a dense feed-forward in the first ``num_dense_layers`` layers and
    sigmoid-routed experts beside one shared expert after them.
    ``num_experts`` is what is HELD here, ids ``first_expert`` onwards, of the
    ``num_experts_published`` the router scores (a card without the second
    holds them all). What ``models/trinity.py`` does not run is refused by
    name."""
    from dynamo_tpu.models.trinity import FULL, WINDOW, TrinityConfig

    def refuse(key: str, why: str):
        raise ValueError(f"model_type 'afmoe' with {key} = {mc.get(key)!r}: models/trinity.py {why}")

    if mc.get("score_func", "sigmoid") != "sigmoid":
        refuse("score_func", "scores its experts by a sigmoid")
    for key in ("n_group", "topk_group", "num_expert_groups", "num_limited_groups"):
        if int(mc.get(key, 1)) != 1:
            refuse(key, "chooses its experts in one group (1)")
    if int(mc.get("num_shared_experts", 1)) != 1:
        refuse("num_shared_experts", "runs one shared expert beside the routed ones")
    if mc.get("rope_scaling"):
        refuse("rope_scaling", "rotates its window layers by rope_theta alone")
    if mc.get("tie_word_embeddings"):
        refuse("tie_word_embeddings", "has a head of its own (untied)")
    layers = int(mc["num_hidden_layers"])
    kinds = tuple(str(kind) for kind in mc.get("layer_types") or ())
    if len(kinds) != layers or set(kinds) - {WINDOW, FULL}:
        refuse("layer_types", f"wants {layers} (num_hidden_layers) of {WINDOW!r} / {FULL!r}")
    heads = int(mc["num_attention_heads"])
    if heads % int(mc.get("num_key_value_heads", heads)):
        refuse("num_key_value_heads", f"groups whole numbers of its {heads} query heads over a KV head")
    experts = int(mc["num_experts"])
    return TrinityConfig(
        vocab_size=int(mc["vocab_size"]),
        hidden_size=int(mc["hidden_size"]),
        intermediate_size=int(mc["intermediate_size"]),
        num_layers=layers,
        num_heads=heads,
        num_kv_heads=int(mc.get("num_key_value_heads", heads)),
        head_dim=int(mc["head_dim"]),
        layer_types=kinds,
        sliding_window=int(mc["sliding_window"]),
        rope_theta=float(mc.get("rope_theta", 10000.0)),
        num_dense_layers=int(mc.get("num_dense_layers", 0)),
        moe_intermediate_size=int(mc["moe_intermediate_size"]),
        num_experts=experts,
        num_experts_published=int(mc.get("num_experts_published", experts)),
        first_expert=int(mc.get("first_expert", 0)),
        num_experts_per_tok=int(mc["num_experts_per_tok"]),
        moe_renormalize=bool(mc.get("route_norm", True)),
        routed_scaling_factor=float(mc.get("route_scale", 1.0)),
        mup_enabled=bool(mc.get("mup_enabled", False)),
        rms_norm_eps=float(mc.get("rms_norm_eps", 1e-5)),
        dtype=dtype,
    )


def config_from_card(card: ModelDeploymentCard, dtype: Any = jnp.bfloat16):
    """Derive the model's config from the card's HF config.json contents: a
    LlamaConfig, or by ``model_type`` another module's (models.module_for),
    imported in its branch: serving one model loads no other's module."""
    mc = card.model_config or {}
    if mc.get("model_type") == "kimi_linear":
        return kimi_linear_config(mc, dtype)
    if mc.get("model_type") == "jamba":
        return jamba_config(mc, dtype)
    if mc.get("model_type") == "lfm2_moe":
        return lfm2_config(mc, dtype)
    if mc.get("model_type") == "qwen3_next":
        return qwen3_next_config(mc, dtype)
    if mc.get("model_type") == "pangu_ultra_moe":
        return openpangu_config(mc, dtype)
    if mc.get("model_type") == "xing4_0":
        return xing4_config(mc, dtype)
    if mc.get("model_type") == "afmoe":
        return afmoe_config(mc, dtype)
    if mc.get("model_type") == "mellum":
        return mellum_config(mc, dtype)
    if "num_experts" in mc and "num_local_experts" not in mc:
        # an expert model of a family this tree has no module for: a
        # LlamaConfig of it would be a dense impostor under its name
        raise ValueError(
            f"model_type {mc.get('model_type')!r} declares num_experts = "
            f"{mc['num_experts']} and no module here runs it"
        )
    hidden = int(mc.get("hidden_size", 4096))
    heads = int(mc.get("num_attention_heads", 32))
    return LlamaConfig(
        vocab_size=int(mc.get("vocab_size", 128256)),
        hidden_size=hidden,
        intermediate_size=int(mc.get("intermediate_size", 4 * hidden)),
        num_layers=int(mc.get("num_hidden_layers", 32)),
        num_heads=heads,
        num_kv_heads=int(mc.get("num_key_value_heads", heads)),
        head_dim=int(mc.get("head_dim", hidden // heads)),
        rope_theta=float(mc.get("rope_theta", 500000.0)),
        rms_norm_eps=float(mc.get("rms_norm_eps", 1e-5)),
        tie_embeddings=bool(mc.get("tie_word_embeddings", False)),
        # qwen2 attention carries q/k/v biases (HF config doesn't flag it;
        # the architecture implies it)
        qkv_bias=mc.get("model_type") == "qwen2",
        # mixtral family: sparse MoE MLP, experts over the ep mesh axis
        num_experts=int(mc.get("num_local_experts", 0)),
        num_experts_per_tok=int(mc.get("num_experts_per_tok", 2)),
        dtype=dtype,
    )


def _hf_tensors(model_path: str) -> Optional[Dict[str, np.ndarray]]:
    files = sorted(glob.glob(os.path.join(model_path, "*.safetensors")))
    if not files:
        return None
    from safetensors import safe_open

    out: Dict[str, np.ndarray] = {}
    for f in files:
        with safe_open(f, framework="np") as sf:
            for name in sf.keys():
                out[name] = sf.get_tensor(name)
    return out


def mellum_config(mc: Dict[str, Any], dtype: Any = jnp.bfloat16):
    """``model_type: mellum`` (Mellum 2): window attention layers beside full
    ones in whole periods, both rotated, each kind by its own section of
    ``rope_parameters`` (read from the published nested group where the card
    has it, else from its flat spelling ``rope_parameters_<kind>_<key>``: a
    harness that writes only scalar and list keys); softmax-routed experts in
    every layer, renormalised, all of them served (over the shards of a mesh:
    what the mesh axis has to divide is held at engine build, by name, in
    ``models/mellum.py``). What that module does not compute is refused by
    name."""
    from dynamo_tpu.models.mellum import FULL, WINDOW, MellumConfig

    def refuse(key: str, why: str, value=None):
        raise ValueError(f"model_type 'mellum' with {key} = {mc.get(key, value)!r}: models/mellum.py {why}")

    layers = int(mc["num_hidden_layers"])
    if list(mc.get("mlp_layer_types") or ["sparse"] * layers) != ["sparse"] * layers:
        refuse("mlp_layer_types", f"runs {layers} (num_hidden_layers) 'sparse' expert layers and no dense one")
    kinds = tuple(str(kind) for kind in mc.get("layer_types") or ())
    period = kinds[:kinds.index(FULL) + 1] if FULL in kinds else ()
    if (len(kinds) != layers or set(kinds) - {WINDOW, FULL} or len(period) < 2
            or kinds != period * (layers // len(period))):
        refuse("layer_types", f"wants {layers} (num_hidden_layers) in whole periods of one or more "
                              f"{WINDOW!r} and then one {FULL!r}")
    rope = mc.get("rope_parameters") or {kind: {
        k[len(f"rope_parameters_{kind}_"):]: v for k, v in mc.items()
        if k.startswith(f"rope_parameters_{kind}_")} for kind in (WINDOW, FULL)}
    if not rope.get(WINDOW) or not rope.get(FULL):
        refuse("rope_parameters", f"rotates by a section for {WINDOW!r} and one for {FULL!r}", rope)
    if rope[WINDOW].get("rope_type", "default") != "default":
        refuse("rope_parameters", f"rotates its {WINDOW!r} layers by rope_theta alone (rope_type default)", rope)
    if rope[FULL].get("rope_type") != "yarn":
        refuse("rope_parameters", f"rotates its {FULL!r} layers by YaRN's table (rope_type yarn)", rope)
    if float(rope[WINDOW]["rope_theta"]) != float(rope[FULL]["rope_theta"]):
        refuse("rope_parameters", "keeps one rope_theta for both kinds of layer", rope)
    if not mc.get("norm_topk_prob", True):
        refuse("norm_topk_prob", "renormalises the chosen experts' probabilities (true)")
    if mc.get("attention_bias"):
        refuse("attention_bias", "has no bias in its attention projections")
    if mc.get("tie_word_embeddings"):
        refuse("tie_word_embeddings", "has a head of its own (untied)")
    if not mc.get("use_sliding_window", True):
        refuse("use_sliding_window", f"holds its {WINDOW!r} layers to sliding_window (true)")
    heads = int(mc["num_attention_heads"])
    if heads % int(mc.get("num_key_value_heads", heads)):
        refuse("num_key_value_heads", f"groups whole numbers of its {heads} query heads over a KV head")
    yarn = rope[FULL]
    factor = float(yarn["factor"])
    return MellumConfig(
        vocab_size=int(mc["vocab_size"]),
        hidden_size=int(mc["hidden_size"]),
        num_layers=layers,
        num_heads=heads,
        num_kv_heads=int(mc.get("num_key_value_heads", heads)),
        head_dim=int(mc["head_dim"]),
        layer_types=kinds,
        sliding_window=int(mc["sliding_window"]),
        rope_theta=float(yarn["rope_theta"]),
        yarn_factor=factor,
        yarn_original_positions=int(yarn["original_max_position_embeddings"]),
        yarn_beta_fast=float(yarn.get("beta_fast", 32.0)),
        yarn_beta_slow=float(yarn.get("beta_slow", 1.0)),
        attention_factor=float(yarn.get("attention_factor", 0.1 * math.log(factor) + 1.0)),
        moe_intermediate_size=int(mc["moe_intermediate_size"]),
        num_experts=int(mc["num_experts"]),
        num_experts_per_tok=int(mc["num_experts_per_tok"]),
        rms_norm_eps=float(mc.get("rms_norm_eps", 1e-6)),
        dtype=dtype,
    )


def load_params(
    card: ModelDeploymentCard, config, seed: int = 0,
    shardings=None,
):
    """Load llama weights (safetensors or GGUF) into the stacked pytree,
    or random-init when the card has no weight artifacts.

    ``shardings`` (a NamedSharding pytree, models/llama.py param_shardings)
    places every leaf DIRECTLY into its mesh sharding: random init runs
    under jit with ``out_shardings`` (each device generates only its
    shards), file weights go host → device leaf by leaf (each device
    receives only its shards). The whole tree never exists on one device —
    a model that needs the mesh to fit (qwen2.5-7b bf16 is 15.2 GB against
    16 GB of HBM) could not load otherwise."""
    if card.gguf_path:
        from dynamo_tpu.llm.gguf import gguf_params, read_gguf

        return jax.device_put(
            gguf_params(read_gguf(card.gguf_path), config), shardings
        )
    tensors = _hf_tensors(card.model_path) if card.model_path else None
    if tensors is not None and not isinstance(config, LlamaConfig):
        raise NotImplementedError(
            f"no checkpoint mapping for {type(config).__name__}: random weights only"
        )
    if tensors is None:
        logger.info("no safetensors found for %s: random-initializing", card.display_name)
        # always under jit, sharded or not: every process of a deployment
        # (decode workers, prefill workers, a mesh engine and its one-chip
        # comparison) then runs the same program and holds the same values
        init = jax.jit(
            lambda: module_for(config).init_params(jax.random.PRNGKey(seed), config),
            out_shardings=shardings,
        )
        return init()
    # host → device leaf by leaf (shardings=None: the default device)
    return jax.device_put(params_from_hf(tensors, config), shardings)


def _mlp_weights(c: LlamaConfig, stack, lin) -> Dict[str, Any]:
    """Dense llama/qwen2 MLP or mixtral sparse-MoE expert weights, stacked
    [L, ...] (and [L, X, ...] over experts), on the host; ``stack``/``lin``
    are params_from_hf's helpers. HF mixtral names: block_sparse_moe.gate
    (router) + experts.M.{w1,w3,w2} = gate/up/down."""
    if c.num_experts > 1:
        def experts(fmt: str) -> np.ndarray:
            return np.stack([
                np.stack([lin(fmt.format(i, x)) for x in range(c.num_experts)])
                for i in range(c.num_layers)
            ]).astype(c.dtype)

        return {
            "moe_router": stack(
                "model.layers.{}.block_sparse_moe.gate.weight", lin, np.float32
            ),
            "w_gate": experts("model.layers.{}.block_sparse_moe.experts.{}.w1.weight"),
            "w_up": experts("model.layers.{}.block_sparse_moe.experts.{}.w3.weight"),
            "w_down": experts("model.layers.{}.block_sparse_moe.experts.{}.w2.weight"),
        }
    return {
        "w_gate": stack("model.layers.{}.mlp.gate_proj.weight", lin),
        "w_up": stack("model.layers.{}.mlp.up_proj.weight", lin),
        "w_down": stack("model.layers.{}.mlp.down_proj.weight", lin),
    }


def params_from_hf(tensors: Dict[str, np.ndarray], config: LlamaConfig):
    """HF llama naming → framework pytree (transposed to [in, out] layout)
    of HOST arrays in their final dtypes; load_params places the leaves."""
    c = config
    dt = c.dtype

    def get(name: str) -> np.ndarray:
        return tensors[name]

    def lin(name: str) -> np.ndarray:
        # HF nn.Linear stores [out, in]; we use [in, out]
        return np.ascontiguousarray(get(name).T)

    def stack(fmt: str, transform=get, dtype=dt) -> np.ndarray:
        return np.stack(
            [transform(fmt.format(i)) for i in range(c.num_layers)]
        ).astype(dtype)

    params = {
        "embed": get("model.embed_tokens.weight").astype(dt),
        "final_norm": get("model.norm.weight").astype(np.float32),
        "layers": {
            "attn_norm": stack(
                "model.layers.{}.input_layernorm.weight", dtype=np.float32
            ),
            "wq": stack("model.layers.{}.self_attn.q_proj.weight", lin),
            "wk": stack("model.layers.{}.self_attn.k_proj.weight", lin),
            "wv": stack("model.layers.{}.self_attn.v_proj.weight", lin),
            "wo": stack("model.layers.{}.self_attn.o_proj.weight", lin),
            **(
                {
                    b: stack(
                        "model.layers.{}.self_attn.%s_proj.bias" % b[1],
                        dtype=np.float32,
                    )
                    for b in ("bq", "bk", "bv")
                }
                if c.qkv_bias
                else {}
            ),
            "mlp_norm": stack(
                "model.layers.{}.post_attention_layernorm.weight",
                dtype=np.float32,
            ),
            **_mlp_weights(c, stack, lin),
        },
    }
    if not c.tie_embeddings:
        params["lm_head"] = lin("lm_head.weight").astype(dt)
    return params
