"""Persistent XLA compilation cache.

Compiling the serving step functions takes seconds each on the chip; the
persistent cache makes every compile after the first process launch a
disk load. Mirrors the reference's philosophy of keeping startup cost off
the request path (its engines load prebuilt CUDA binaries; XLA's unit of
reuse is the compiled executable).
"""

from __future__ import annotations

import os
import sys
import threading

_DEFAULT = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(__file__))), ".jax_cache")

# process-global count of jitted-program builds (engine step-fn variants,
# counts syncs). A steady-state engine compiles a handful
# at boot and then NEVER again — a climbing count mid-traffic means some
# shape leaked into a jit signature and every bump stalled decode for a
# full compile. Surfaced live as ForwardPassMetrics.jit_recompiles.
_COMPILE_LOCK = threading.Lock()
_COMPILES: dict[str, int] = {}


def record_compile(kind: str = "step", detail: str = "") -> str:
    """Count one jitted-program build (called where engines create a new
    compiled variant — cache misses in their per-shape fn tables) and
    return its key, ``kind`` and ``detail`` in one string.
    ``detail`` carries the triggering variant key / abstract shapes; it
    lands on the profiling timeline (docs/observability.md §Profiling) as
    a ``jit_compile`` event when that plane is armed — a recompile storm
    mid-traffic then shows up ON the capture that measured the stall."""
    with _COMPILE_LOCK:
        _COMPILES[kind] = _COMPILES.get(kind, 0) + 1
    # lazy + constructor-free: processes that never armed DYN_TPU_PROFILE
    # never even import the profiling module from here
    key = f"{kind} {detail}".strip()
    prof = sys.modules.get("dynamo_tpu.runtime.profiling")
    if prof is not None:
        prof.note_event("jit_compile", detail=key, phase=kind)
    return key


def compile_count() -> int:
    with _COMPILE_LOCK:
        return sum(_COMPILES.values())


def compile_counts() -> dict[str, int]:
    with _COMPILE_LOCK:
        return dict(_COMPILES)


def enable_compile_cache() -> str:
    """Switch on JAX's persistent compilation cache; returns its directory.

    Call before the first jit dispatch. Where ``JAX_COMPILATION_CACHE_DIR``
    is set, JAX reads it itself and no directory is set in code (the path is
    part of the cache key, so whoever places the cache from outside must be
    the only one naming it); where it is not, the cache lives at the fixed
    ``<checkout>/.jax_cache``. ``JAX_ENABLE_COMPILATION_CACHE=false`` is
    JAX's own off switch.
    """
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    target = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if target:
        return target
    os.makedirs(_DEFAULT, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", _DEFAULT)
    return _DEFAULT
