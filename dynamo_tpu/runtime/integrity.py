"""End-to-end KV/output integrity: silent-corruption defense.

Hyperscaler fleets document silent data corruption (SDC) from defective
cores as a routine production event ("Cores that don't count", Hochschild
et al. HotOS'21; Meta's "Silent Data Corruptions at Scale"). This system
*amplifies* one bad host: KV pages are a cluster resource — host-tier
rehits, disagg transfers, prefix reads, and drain-time live migration all
replay pages long after the wire CRC (``runtime/codec.py``, transport-scope
only) stopped vouching for them. One SDC-afflicted worker can poison every
stream that ever touches its cache.

This module is the shared vocabulary of the integrity plane
(docs/resilience.md §Silent corruption):

- **Block content checksums**: a per-KV-block crc32 computed when the block
  is sealed (``allocator.note_tokens_computed``) that travels *with* the
  block through every tier — host-pool offload/rehit, disagg
  ``kv_blocks``/``read_blocks``/``migrate`` frames (header extension;
  checksum-less frames from old peers still parse), and migration staging —
  and is verified on every injection/adoption. A mismatch is a typed
  :class:`KvIntegrityError`: the block is dropped as a prefix miss and
  recomputed — never served, never a torn pool.
- **Trip accounting + quarantine**: every verification failure (and every
  output-watchdog trip) is a *trip* against this worker. ``trip_threshold``
  trips within ``trip_window`` seconds flip the process into **quarantine**:
  the health plane reports ``quarantined``, routers exclude the worker, the
  drain that follows must NOT migrate its (untrusted) pages — the migration
  coordinator degrades to resume directives — and only an operator
  (``llmctl worker unquarantine``) re-admits it.

``DYN_TPU_KV_INTEGRITY=0`` is THE zero-overhead gate: no checksum is ever
computed, no tracker or policy object is ever constructed, and the engine's
jitted step functions compile exactly the pre-integrity programs (tests
monkeypatch the constructors to prove it).

Threat model honesty: checksums are computed *at seal* by the worker that
computed the KV. They catch corruption that happens **after** the seal —
in HBM between seal and reuse, in host RAM in the spill tier, and on every
wire hop. A core that computes wrong values *before* the seal produces a
self-consistent checksum; that failure mode is what the output watchdog
(non-finite / exploding logits) and downstream byte-equality cover.
"""

from __future__ import annotations

import logging
import sys
import threading
import time
import zlib
from collections import deque
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, Optional, Sequence

import numpy as np

logger = logging.getLogger(__name__)

ENV_KV_INTEGRITY = "DYN_TPU_KV_INTEGRITY"
ENV_TRIPS = "DYN_TPU_INTEGRITY_TRIPS"
ENV_WINDOW = "DYN_TPU_INTEGRITY_WINDOW"
ENV_LOGIT_LIMIT = "DYN_TPU_INTEGRITY_LOGIT_LIMIT"

# sentinel the jitted step functions substitute for a sampled token when the
# output watchdog flags a lane (non-finite or exploding logits): real token
# ids are always >= 0, so the host loop can detect a tripped lane from the
# fetched tokens alone — no extra device output, no extra transfer
WATCHDOG_TOKEN = -2


class KvIntegrityError(ValueError):
    """KV page bytes failed their content checksum: the page was corrupted
    after it was sealed (bad HBM/host RAM on the owner, or a bad wire hop).
    Raised *instead of* serving or injecting the bytes — the caller drops
    the block as a prefix miss and recomputes. The transfer plane maps it
    to a typed nack so the *sender* learns its pages are rotten and counts
    the trip against itself (the quarantine signal)."""


# PR3 clamping helpers live in the one shared home (runtime/envknobs.py);
# the local names are kept for the modules that historically imported the
# clamping contract from here (the tracing-imports-admission precedent)
from dynamo_tpu.runtime.envknobs import (  # noqa: E402
    env_clamped_float as _env_clamped_float,
    env_clamped_int as _env_clamped_int,
    env_flag as _env_flag,
)


@dataclass(frozen=True)
class IntegrityPolicy:
    """Knob bundle for the integrity plane (PR3 clamping contract:
    malformed / non-positive values fall back to defaults, in-range values
    clamp into the documented bounds).

    ``enabled``         DYN_TPU_KV_INTEGRITY (0 = zero-overhead gate: no
                        checksum ever computed, no watchdog variant built,
                        no tracker constructed).
    ``trip_threshold``  integrity trips within the window that flip this
                        worker into quarantine (clamped to [1, 1000]).
    ``trip_window``     seconds the trip window spans (clamped to
                        [1, 3600]).
    ``logit_limit``     |logit| above this marks a lane's output as
                        exploding even when finite (clamped to [10, 1e9]).
    """

    enabled: bool = True
    trip_threshold: int = 3
    trip_window: float = 60.0
    logit_limit: float = 1e4

    @classmethod
    def from_env(cls) -> "IntegrityPolicy":
        d = cls()
        return cls(
            enabled=_env_flag(ENV_KV_INTEGRITY, d.enabled),
            trip_threshold=_env_clamped_int(
                ENV_TRIPS, d.trip_threshold, 1, 1000
            ),
            trip_window=_env_clamped_float(
                ENV_WINDOW, d.trip_window, 1.0, 3600.0
            ),
            logit_limit=_env_clamped_float(
                ENV_LOGIT_LIMIT, d.logit_limit, 10.0, 1e9
            ),
        )


def maybe_from_env() -> Optional[IntegrityPolicy]:
    """The gate every integration point None-checks: ``None`` unless the
    integrity plane is enabled — with ``DYN_TPU_KV_INTEGRITY=0`` no policy
    object is ever constructed (the PR9/PR12 zero-overhead pattern)."""
    if not _env_flag(ENV_KV_INTEGRITY, True):
        return None
    return IntegrityPolicy.from_env()


def enabled() -> bool:
    """Cheap boolean form of the gate (one env read, no object)."""
    return _env_flag(ENV_KV_INTEGRITY, True)


# ---------------------------------------------------------------------------
# block content checksums
# ---------------------------------------------------------------------------


def checksum(arrays: Iterable[Any]) -> int:
    """crc32 chained over the bytes of ``arrays``, in the order given. What
    the arrays are (which members a KV block has, and in what order) is
    ``kv/pages.py``'s to say."""
    crc = 0
    for arr in arrays:
        # tobytes() on an ascontiguousarray: works for every dtype in the KV
        # tiers (bf16 via ml_dtypes has no stable buffer protocol everywhere)
        crc = zlib.crc32(np.ascontiguousarray(arr).tobytes(), crc)
    return crc


def verify(checksum_of: Callable[[int], int], n_blocks: int,
           crcs: Optional[Sequence[Optional[int]]], where: str = "") -> None:
    """Hold ``n_blocks`` received blocks to their travelling checksums;
    ``checksum_of(i)`` hashes block ``i`` and is asked only where the sender
    vouched for it.

    ``crcs`` of ``None``, and entries of ``None``/negative, mean "sender had
    no checksum for this block" (partial block, pre-integrity peer) and are
    skipped — a checksum-less frame always parses. Raises
    :class:`KvIntegrityError` at the first mismatching block, BEFORE any
    byte can land in a pool."""
    for i, want in zip(range(n_blocks), crcs or ()):
        if want is None or (isinstance(want, int) and want < 0):
            continue
        got = checksum_of(i)
        if got != int(want):
            raise KvIntegrityError(
                f"KV block {i} failed its content checksum"
                f"{' (' + where + ')' if where else ''}: "
                f"expected {int(want):#010x}, bytes hash to {got:#010x}"
            )


# ---------------------------------------------------------------------------
# trip accounting + quarantine (process-global, thread-safe)
# ---------------------------------------------------------------------------


class IntegrityTracker:
    """Process-global integrity outcome accounting + the quarantine latch.

    Constructed lazily on the FIRST trip/quarantine operation — with the
    integrity plane disabled nothing ever constructs it (the zero-overhead
    guard monkeypatches this constructor to prove it). Quarantine is a
    *source set* like drain sources: ``trips`` (self-detected corruption
    crossed the threshold) and ``store`` (``llmctl worker quarantine``)
    latch independently; an explicit operator unquarantine clears both and
    resets the trip window (the operator is vouching for the host)."""

    def __init__(self, policy: Optional[IntegrityPolicy] = None,
                 clock=time.monotonic):
        self._lock = threading.Lock()
        self._clock = clock
        self._policy = policy
        # kind → cumulative count; "kv" = checksum mismatches attributable
        # to THIS process's pages, "watchdog" = output-watchdog lane trips,
        # "remote" = corrupt pages OBSERVED from a peer (not self-blame)
        self.kv_failures_total = 0
        self.watchdog_trips_total = 0
        self.remote_failures_total = 0
        self._trips: deque = deque(maxlen=1024)  # (monotonic t, kind, where)
        self._quarantine_sources: set = set()
        self.quarantine_reason = ""
        self.quarantines_total = 0

    def _pol(self) -> IntegrityPolicy:
        # env read per trip, not per token: trips are failure events
        return self._policy or IntegrityPolicy.from_env()

    # -- trips -------------------------------------------------------------

    def note_trip(self, kind: str, where: str = "") -> bool:
        """Record one self-attributable integrity trip ("kv" | "watchdog").
        Returns True when this trip crossed the threshold and latched
        quarantine."""
        pol = self._pol()
        now = self._clock()
        with self._lock:
            if kind == "watchdog":
                self.watchdog_trips_total += 1
            else:
                self.kv_failures_total += 1
            self._trips.append((now, kind, where))
            in_window = sum(
                1 for t, _, _ in self._trips
                if now - t <= pol.trip_window
            )
            if (
                in_window >= pol.trip_threshold
                and "trips" not in self._quarantine_sources
            ):
                self._quarantine_sources.add("trips")
                self.quarantine_reason = (
                    f"{in_window} integrity trips within "
                    f"{pol.trip_window:.0f}s (last: {kind}"
                    f"{' @' + where if where else ''})"
                )
                self.quarantines_total += 1
                logger.error(
                    "worker QUARANTINED: %s — serving stops, pages are "
                    "untrusted (drain will resume, not migrate); "
                    "`llmctl worker unquarantine` re-admits after repair",
                    self.quarantine_reason,
                )
                return True
        logger.error(
            "integrity trip (%s%s): %d/%d within the window", kind,
            " @" + where if where else "", in_window, pol.trip_threshold,
        )
        return False

    def note_remote_failure(self, where: str = "") -> None:
        """A peer's pages failed verification HERE: observability only —
        the blame (and the quarantine trip) belongs to the sender, which
        learns via the typed nack."""
        with self._lock:
            self.remote_failures_total += 1
        logger.warning("rejected corrupt KV pages from a peer (%s)", where)

    # -- quarantine latch --------------------------------------------------

    @property
    def quarantined(self) -> bool:
        with self._lock:
            return bool(self._quarantine_sources)

    def quarantine(self, source: str = "store", reason: str = "") -> None:
        with self._lock:
            fresh = not self._quarantine_sources
            self._quarantine_sources.add(source)
            if reason or fresh:
                self.quarantine_reason = reason or f"ordered via {source}"
            if fresh:
                self.quarantines_total += 1
        # chaos-plane observation hook (docs/chaos.md): one dict-get unless
        # runtime/chaos.py is imported and armed; outside _lock (the
        # observer locks itself)
        ch = sys.modules.get("dynamo_tpu.runtime.chaos")
        if ch is not None:
            ch.note_event("quarantine", latched=True, source=source,
                          reason=reason)

    def clear_quarantine(self, source: Optional[str] = None) -> None:
        """``source=None`` is the operator unquarantine: every source is
        cleared AND the trip window is reset (without the reset the very
        next health check would re-latch off the old trips)."""
        with self._lock:
            if source is None:
                self._quarantine_sources.clear()
                self._trips.clear()
                self.quarantine_reason = ""
            else:
                self._quarantine_sources.discard(source)
                if not self._quarantine_sources:
                    self.quarantine_reason = ""
            still = bool(self._quarantine_sources)
        ch = sys.modules.get("dynamo_tpu.runtime.chaos")
        if ch is not None:
            ch.note_event("quarantine", latched=still, source=source or "*")

    def counters(self) -> Dict[str, int]:
        with self._lock:
            return {
                "kv_integrity_failures_total": self.kv_failures_total,
                "watchdog_trips_total": self.watchdog_trips_total,
                "kv_integrity_remote_failures_total":
                    self.remote_failures_total,
                "quarantined": int(bool(self._quarantine_sources)),
            }


_TRACKER: Optional[IntegrityTracker] = None
_TRACKER_LOCK = threading.Lock()


def tracker() -> IntegrityTracker:
    """The process-global tracker, constructed on first use (never with the
    plane disabled — callers sit behind the :func:`maybe_from_env` gate)."""
    global _TRACKER
    if _TRACKER is None:
        with _TRACKER_LOCK:
            if _TRACKER is None:
                _TRACKER = IntegrityTracker()
    return _TRACKER


def note_trip(kind: str, where: str = "") -> bool:
    return tracker().note_trip(kind, where)


def note_remote_failure(where: str = "") -> None:
    tracker().note_remote_failure(where)


def clear_quarantine(source: Optional[str] = None) -> None:
    """Constructor-free clear: a no-op until something actually latched
    (the store control loop syncs an absent key without building state)."""
    t = _TRACKER
    if t is not None:
        t.clear_quarantine(source)


def quarantined() -> bool:
    """Constructor-free read: False until something actually built the
    tracker (the health monitor polls this every check tick)."""
    t = _TRACKER
    return t is not None and t.quarantined


def quarantine_reason() -> str:
    t = _TRACKER
    return t.quarantine_reason if t is not None else ""


def counters() -> Dict[str, int]:
    """Constructor-free counters for the metrics publisher: zeros until a
    trip/quarantine ever happened in this process."""
    t = _TRACKER
    if t is None:
        return {
            "kv_integrity_failures_total": 0,
            "watchdog_trips_total": 0,
            "kv_integrity_remote_failures_total": 0,
            "quarantined": 0,
        }
    return t.counters()


def reset_for_tests() -> None:
    """Drop the process-global tracker (conftest autouse reset: one test's
    trips/quarantine must not bleed into another's health assertions)."""
    global _TRACKER
    with _TRACKER_LOCK:
        _TRACKER = None
