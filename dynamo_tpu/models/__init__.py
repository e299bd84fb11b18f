"""JAX model implementations for TPU serving.

Models are pure functions over explicit parameter pytrees — no framework
module state — so they jit/shard cleanly and the serving engine controls
every buffer. Llama covers the reference's flagship family (the reference
serves Llama-70B-class models through vLLM; here the model IS the framework's,
SURVEY.md §6 north star).
"""

from dynamo_tpu.models.llama import (
    LlamaConfig,
    LLAMA_PRESETS,
    init_params,
    forward,
    make_kv_cache,
    param_shardings,
)



def module_for(model_config):
    """The model module whose programs run ``model_config``: the ONE place
    where the engine, the weight loader and the benchmark's reference child
    pick a module. Each exposes ``init_params``, ``make_kv_cache``,
    ``param_shardings`` and ``lm_head``; a module whose layers keep state per
    slot beside the pages (``make_slot_state``) also ``forward_chunk`` and
    ``decode`` in the form ``engine_jax/engine.py`` calls them with the
    state."""
    from dynamo_tpu.models import kimi_linear, llama

    if isinstance(model_config, kimi_linear.KimiLinearConfig):
        return kimi_linear
    return llama


__all__ = [
    "module_for",
    "LlamaConfig",
    "LLAMA_PRESETS",
    "init_params",
    "forward",
    "make_kv_cache",
    "param_shardings",
]
