"""JAX model implementations for TPU serving.

Models are pure functions over explicit parameter pytrees — no framework
module state — so they jit/shard cleanly and the serving engine controls
every buffer. Llama covers the reference's flagship family (the reference
serves Llama-70B-class models through vLLM; here the model IS the framework's,
SURVEY.md §6 north star).
"""

import sys

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
    ``param_shardings``, ``lm_head``, and ``chunk_history_tiles`` /
    ``decode_history_tiles`` (what its step programs read of a block table,
    for the host's count), and beside them says whether a lane may fill
    several rows of one chunk dispatch (``LANE_TAKES_ROWS = True``: its chunk
    program takes the rows' lanes and lets a row attend the fresh keys of the
    earlier rows of its lane, out of its own hands or out of the pool; a
    module that says nothing keeps one row a lane). A module that brings its
    OWN step programs has ``COUNTERS`` and
    ``forward_chunk`` / ``decode`` in the form ``engine_jax/engine.py`` calls
    them, with the slots' state in and out; a module whose layers keep state
    per slot beside the pages ALSO has ``make_slot_state``, and only such a
    module is refused whatever hands pages over without that state
    (docs/kv_cache_manager.md, "State per slot": the two facts and the table).
    ``models/openpangu.py`` has the first and not the second (latent pages and
    nothing else: ``state`` is ``None``), and a prediction module of its own,
    which ``draft_chunk`` and ``decode(..., draft=True)`` run where the engine
    drafts; ``models/xing4.py`` is on the same contract (its four residual
    streams live inside a dispatch: what it hands the engine is one stream).
    A module WITHOUT state may set ``LANE_TAKES_ROWS`` when a later row of a
    lane finds the earlier rows' fresh keys: ``models/llama.py`` hands them
    over inside the program; ``openpangu`` and ``xing4`` (the fifth and sixth
    modules that set it) let the rows meet through the pool: a layer writes
    every row's latents of a group before any row attends, a row reads its
    block table out of the pool under a causal mask by position, and the
    groups run in order over one pool. Of the four conditions below (1) and
    (2) are then empty (nothing is kept by lane), (3) is one read of the
    table, and (4) holds because ``lanes`` is read by no equation: the program
    is the same at every rung. A module with state
    may set ``LANE_TAKES_ROWS`` once its chunk program, under the full width,
    (1) starts a row whose lane is that of the row above it from what that
    row leaves and not from the slot's stored state, (2) lets a lane's LAST
    row alone write the slot's state back, (3) ends a row's pool history
    where its lane's first row of the dispatch starts and attends the rows
    between as fresh keys, and (4) is, at ``rows == slots``, the program it
    was. ``models/lfm2.py`` does: its state is a convolution's tail, which a
    row's own inputs make. ``models/jamba.py`` does: its recurrence is a
    KERNEL, which for (1) walks a lane's rows in order and keeps the state on
    the chip from the lane's first row to its last
    (``ops/pallas/selective_scan.py:selective_scan(..., continues)``), so that
    the lane's FIRST row holds the state after its last and writes it, the
    last row the tail. ``kimi_linear`` and ``qwen3_next`` do (the seventh and
    eighth, and the last: every module sets it): their recurrence is ONE
    kernel too, and ``ops/pallas/kda_scan.py:kda_scan(..., continues)`` hands
    the delta-rule state from row to row in ``selective_scan``'s form. For
    (3) Kimi's rows meet through the pool as openPangu's do (a layer writes
    every row's latents before any row gathers its table, under a causal mask
    by position); Qwen3-Next's attention layers read pages that are written
    after the loop over its groups of 8 rows, so it has ``lfm2``'s form: a
    lane's later row attends its earlier rows' fresh K and V in hand, and
    what a later group needs of an earlier one (those K and V, the last row's
    tails, and the last row's sequence STATE of each DeltaNet layer, which
    ``lfm2``'s tails do not need) rides the loop's carry. A module whose
    chunk program is the SAME program at ``rows == slots`` (it reads ``lanes``
    at every width, so (4) asks nothing of it) says so beside it:
    ``FULL_WIDTH_TAKES_ROWS = True``, and the engine then deals the rows of a
    full-width dispatch that no lane's first piece fills to further pieces
    (``engine_jax/engine.py:chunk_rows_of``), where they would be computed as
    padding. ``models/trinity.py`` says so, alone: ``llama``'s full-width
    program is the one without lanes; ``jamba``, ``lfm2`` and ``qwen3_next``
    read their lanes under the full width only (``rows < slots``); and
    ``openpangu``, ``xing4`` and ``kimi_linear``, the same at every rung, serve
    64 slots, where the full width is an admission wave with no spare row, so
    no cell could show or check it (ROADMAP S1 (e)). A module that can keep
    only so many rows of one lane apart in ONE dispatch states the number,
    ``lane_rows_most(config, width) -> int``, one at the least (``trinity``:
    what a window layer's ring holds), and the engine deals a lane no more at
    any rung.

    A module with its own programs runs on ONE device unless it says it serves
    on a mesh: ``SERVES_ON_MESH = True`` (``models/mellum.py``, alone). The
    engine then hands it the mesh by keyword, ``mesh=``, where it makes the pool
    and the slots' state (``make_kv_cache``, ``make_slot_state``: every leaf
    created in its sharding) and where it calls ``forward_chunk`` and
    ``decode``; ``param_shardings(config, mesh)`` returns real shardings; how
    the module lays itself over the mesh, and every collective, is the
    module's own (there: one ``shard_map`` a step program over the mesh's one
    axis larger than 1). The other modules' calls carry no such keyword, and
    they are refused a mesh before anything is made on a device.

    A config that is no ``LlamaConfig`` was made by its own module's class
    (``engine_jax/weights.py:config_from_card`` imports that module in its
    branch), so the module is the one already loaded: serving one model
    imports no other's module."""
    from dynamo_tpu.models import llama

    if isinstance(model_config, llama.LlamaConfig):
        return llama
    return sys.modules[type(model_config).__module__]


__all__ = [
    "module_for",
    "LlamaConfig",
    "LLAMA_PRESETS",
    "init_params",
    "forward",
    "make_kv_cache",
    "param_shardings",
]
