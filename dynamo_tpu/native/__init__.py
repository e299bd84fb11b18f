"""Native (C++) tier: the framework's equivalents of the reference's Rust/
CUDA hot paths, built with g++ on first use and loaded via ctypes.

Components:
- ``radix_tree.cc``  — the KV router's prefix tree (reference
  `lib/llm/src/kv_router/indexer.rs`, 1.4k LoC Rust): every routed request
  probes it, every KV event mutates it.
- ``kv_events.cc``   — C ABI KV-event publisher (reference
  `lib/bindings/c/src/lib.rs:51-342`): external engines publish
  stored/removed block events without touching Python.
- ``codec_core.cc``  — two-part framed codec pack/verify (reference
  `codec/two_part.rs`): length-prefixed header+body frames with checksums.

Build model: ``load(name)`` compiles ``{name}.cc`` →
``_lib/{name}.<sha256 of the source>.so`` (g++ -O2 -shared -fPIC), then
ctypes-loads it. The key is the source's CONTENT: a binary copied along
with a tree (mtimes do not survive a copy in any useful order) can only be
loaded by the source it was built from. Pure-Python twins keep every
feature working when no toolchain exists; callers treat ``load() is None``
as "use the portable path", and ``loaded()`` says which one is in use.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

from dynamo_tpu.runtime.envknobs import env_flag

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_DIR = os.path.join(_DIR, "_lib")
_lock = threading.Lock()
_cache: dict = {}


def load(name: str) -> Optional[ctypes.CDLL]:
    """Compile (if not built from this source yet) and load the named
    native component.

    Returns None — and logs once — when the toolchain or source is missing
    or compilation fails; callers fall back to the Python implementation.
    Set DYN_TPU_NO_NATIVE=1 to force the fallbacks (used in tests to cover
    both paths).
    """
    if env_flag("DYN_TPU_NO_NATIVE", False):
        return None
    with _lock:
        if name in _cache:
            return _cache[name]
    # build OUTSIDE the lock: the compile can run for two minutes, and the
    # output path is already safe against concurrent builders (per-pid tmp
    # + atomic os.replace below) — a lost race costs one redundant compile,
    # while holding the lock would stall every other component's load()
    # behind this one's g++
    lib = _build_and_load(name)
    with _lock:
        return _cache.setdefault(name, lib)


def loaded() -> dict:
    """name → True (native library in use) / False (Python twin), for every
    component this process has asked for."""
    with _lock:
        return {name: lib is not None for name, lib in _cache.items()}


def _build_and_load(name: str) -> Optional[ctypes.CDLL]:
    src = os.path.join(_DIR, f"{name}.cc")
    if not os.path.exists(src):
        logger.warning("native source %s missing", src)
        return None
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so = os.path.join(_LIB_DIR, f"{name}.{digest}.so")
    try:
        if not os.path.exists(so):
            os.makedirs(_LIB_DIR, exist_ok=True)
            # per-process tmp: concurrent builders must not clobber each
            # other's half-written output (os.replace is atomic)
            tmp = f"{so}.{os.getpid()}.tmp"
            subprocess.run(
                ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-o", tmp, src],
                check=True,
                capture_output=True,
                timeout=120,
            )
            os.replace(tmp, so)
            logger.info("built native %s", so)
        return ctypes.CDLL(so)
    except FileNotFoundError:
        logger.warning("g++ not available; using Python fallback for %s", name)
    except subprocess.CalledProcessError as e:
        logger.warning(
            "native build of %s failed:\n%s", name, e.stderr.decode(errors="replace")
        )
    except OSError as e:
        logger.warning("loading native %s failed: %s", name, e)
    return None
