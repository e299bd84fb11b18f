"""Composition chaos plane: seeded fault schedules, cluster invariants,
replay/shrink (docs/chaos.md).

PR10-18 each ship a hand-written chaos test for ONE failure defense (or one
chosen pair). The combinatorial space where production actually fails — a
straggler convicted mid-migration during a bus outage, a quarantine latch
racing a rolling restart — is what this module executes:

- :class:`ChaosSchedule` draws a timeline of disruptions over the existing
  fault vocabulary from ONE seed (worker kill/restart, ``slow``,
  ``corrupt``, ``poison``, ``delay``, ``migrate_stall``, control-plane
  blackout, drain/undrain, quarantine/unquarantine) under composition
  constraints (at least one worker stays serving at every instant, at most
  one blackout, no kill inside a blackout, every durative action releases
  before the horizon). Serialization is canonical — the same seed emits
  byte-identical JSON forever, which is what makes ``--replay`` a contract
  rather than a hope.
- :class:`ChaosRunner` stands up an N-worker mini-cluster (real tiny
  engines or the deterministic token-mock fallback) under 2x streaming
  load, applies the schedule through :mod:`dynamo_tpu.runtime.faults`, and
  hands the aftermath to the :class:`InvariantSuite`.
- :class:`InvariantSuite` checks safety (delivered bytes equal the
  undisturbed control or end in a typed in-band error; no migration
  completes while a quarantine latch is held), liveness (no stream stuck
  past its deadline; the fleet reconverges within a bound after the last
  fault), and conservation (allocator pages balance, no staged-migration
  leaks, the client's journal ledger matches its stats ledger exactly —
  the equations live in docs/chaos.md).
- a violating run dumps ``schedule.json`` (replayable byte-identically via
  ``tools/chaos.py --replay``) + ``result.json`` + the flight recorder's
  pinned traces; :func:`shrink_schedule` greedily minimizes a violating
  schedule while the violation persists.

Activation: the serving-path hook (:func:`note_event`) is armed only when
``DYN_TPU_CHAOS=1`` — with the knob unset no chaos object is ever
constructed on any serving path (the PR13/PR14/PR18 monkeypatched-ctor
guard), and callers reach it via ``sys.modules.get`` so this module is not
even imported by serving code.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import logging
import random
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from dynamo_tpu.runtime.envknobs import (
    env_clamped_float,
    env_clamped_int,
    env_flag,
    env_nonneg_int,
    env_raw,
)

logger = logging.getLogger(__name__)

SCHEDULE_VERSION = 1

# the full disruption vocabulary a schedule draws from; every kind maps
# onto an existing runtime/faults.py action or control verb — the chaos
# plane composes defenses, it does not invent new failure physics
KINDS = (
    "kill",           # ungraceful worker death + timed restart
    "slow",           # fail-slow dispatch delay on one worker (timed)
    "corrupt",        # one-shot KV page bit-flip on the transfer plane
    "poison",         # one-shot NaN'd logits lane (output watchdog leg)
    "delay",          # transient rpc frame delays
    "migrate_stall",  # park one in-flight page ship until release
    "blackout",       # statestore+bus down (timed)
    "drain",          # drain/undrain one worker (timed)
    "quarantine",     # integrity latch/clear (timed)
)

# kinds that take a worker out of serving rotation: the generator keeps at
# least one worker free of these at every instant (liveness would be
# vacuous otherwise — a fleet with nobody serving reconverges to nothing)
DISABLING = ("kill", "drain", "quarantine")

# per-kind duration draw bounds (seconds); 0 = instantaneous one-shot
_DURATIONS: Dict[str, Tuple[float, float]] = {
    "kill": (0.3, 1.0),
    "slow": (0.5, 1.5),
    "corrupt": (0.0, 0.0),
    "poison": (0.0, 0.0),
    "delay": (0.0, 0.0),
    "migrate_stall": (0.3, 0.8),
    "blackout": (0.4, 1.0),
    "drain": (0.5, 1.5),
    "quarantine": (0.5, 1.5),
}

DEFAULT_WEIGHTS: Dict[str, float] = {
    "kill": 2.0,
    "slow": 2.0,
    "corrupt": 2.0,
    "poison": 1.0,
    "delay": 2.0,
    "migrate_stall": 1.0,
    "blackout": 1.0,
    "drain": 3.0,
    "quarantine": 1.0,
}

# drain source the runner uses so its undrain never clears an operator's
# (or the straggler plane's) independent drain order
CHAOS_DRAIN_SOURCE = "chaos"
CHAOS_QUARANTINE_SOURCE = "chaos"

# observer timeline bound: a soak run emits thousands of events; the
# invariant checks only need the recent window (PR8 decision-ring pattern)
CHAOS_LOG_MAX = 4096

# grace at a quarantine window's leading edge: a ship whose frame cleared
# the receiver's latch check a scheduling beat before the latch landed may
# legitimately note its completion just after (docs/chaos.md §Invariants)
QUARANTINE_EDGE_GRACE = 0.05


# =========================================================================
# policy knobs (PR3 clamping contract via envknobs)
# =========================================================================


@dataclass(frozen=True)
class ChaosPolicy:
    """Knob bundle for env-driven chaos runs (``tools/chaos.py`` and the
    soak leg). ``enabled`` gates the serving-path observer hook; the rest
    parameterize schedule generation."""

    enabled: bool = False
    seed: int = 0
    duration: float = 8.0
    max_events: int = 12
    weights: Dict[str, float] = field(default_factory=lambda: dict(DEFAULT_WEIGHTS))

    @classmethod
    def from_env(cls) -> "ChaosPolicy":
        d = cls()
        return cls(
            enabled=env_flag("DYN_TPU_CHAOS", d.enabled),
            seed=env_nonneg_int("DYN_TPU_CHAOS_SEED", d.seed),
            duration=env_clamped_float(
                "DYN_TPU_CHAOS_DURATION", d.duration, 1.0, 3600.0
            ),
            max_events=env_clamped_int(
                "DYN_TPU_CHAOS_EVENTS", d.max_events, 1, 500
            ),
            weights=_parse_weights(env_raw("DYN_TPU_CHAOS_WEIGHTS")),
        )


def _parse_weights(raw: Optional[str]) -> Dict[str, float]:
    """``DYN_TPU_CHAOS_WEIGHTS`` is a JSON object kind→weight; malformed
    input, unknown kinds, and negative weights degrade to the defaults /
    are dropped / clamp to 0 — never to a surprise schedule."""
    weights = dict(DEFAULT_WEIGHTS)
    if not raw:
        return weights
    try:
        parsed = json.loads(raw)
        if not isinstance(parsed, dict):
            raise ValueError("weights must be a JSON object")
    except (ValueError, TypeError):
        logger.warning("malformed DYN_TPU_CHAOS_WEIGHTS ignored: %r", raw)
        return weights
    for kind, w in parsed.items():
        if kind not in KINDS:
            logger.warning("unknown chaos kind %r in weights ignored", kind)
            continue
        try:
            weights[kind] = max(float(w), 0.0)
        except (TypeError, ValueError):
            logger.warning("non-numeric weight for %r ignored", kind)
    return weights


def maybe_from_env() -> Optional[ChaosPolicy]:
    """The zero-overhead gate: None unless ``DYN_TPU_CHAOS=1`` — serving
    paths behind this never construct a chaos object."""
    if not env_flag("DYN_TPU_CHAOS", False):
        return None
    return ChaosPolicy.from_env()


# =========================================================================
# schedule: one seed → one timeline, canonically serialized
# =========================================================================


@dataclass(frozen=True)
class ChaosEvent:
    """One disruption. ``t`` is seconds from load start; durative kinds
    hold until ``t + duration`` (restart, un-slow, blackout end, undrain,
    unquarantine, stall release); ``worker`` indexes the mini-cluster
    (ignored by ``blackout``, which takes out the control plane fleetwide).
    """

    t: float
    kind: str
    worker: int = 0
    duration: float = 0.0

    def to_dict(self) -> dict:
        return {
            "t": self.t, "kind": self.kind, "worker": self.worker,
            "duration": self.duration,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "ChaosEvent":
        kind = str(d["kind"])
        if kind not in KINDS:
            raise ValueError(f"unknown chaos kind {kind!r}")
        return cls(
            t=float(d["t"]), kind=kind, worker=int(d.get("worker", 0)),
            duration=float(d.get("duration", 0.0)),
        )

    def end(self) -> float:
        return self.t + self.duration


@dataclass(frozen=True)
class ChaosSchedule:
    """A seeded timeline of :class:`ChaosEvent`, sorted by ``t``.

    :meth:`generate` is a pure function of its arguments — no wall clock,
    no global RNG — so the same seed yields the same schedule on any host,
    and :meth:`to_json` is canonical (sorted keys, fixed separators,
    4-decimal times fixed at generation) so two runs of
    ``tools/chaos.py run --seed N`` emit byte-identical files.
    """

    seed: int
    n_workers: int
    horizon: float
    events: Tuple[ChaosEvent, ...]

    # -- generation --------------------------------------------------------

    @classmethod
    def generate(
        cls,
        seed: int,
        n_workers: int = 3,
        horizon: float = 8.0,
        max_events: int = 12,
        weights: Optional[Dict[str, float]] = None,
    ) -> "ChaosSchedule":
        if n_workers < 2:
            raise ValueError("chaos needs >= 2 workers (failover must have "
                             "somewhere to go)")
        rng = random.Random(seed)
        weights = {
            k: max(float((weights or DEFAULT_WEIGHTS).get(k, 0.0)), 0.0)
            for k in KINDS
        }
        kinds = [k for k in KINDS if weights[k] > 0.0]
        if not kinds:
            raise ValueError("all chaos weights are zero")
        wlist = [weights[k] for k in kinds]
        target = 1 + rng.randrange(max_events)
        accepted: List[ChaosEvent] = []
        # rejection sampling under the composition constraints: bounded
        # tries keep generation total even for over-constrained draws
        for _ in range(max_events * 40):
            if len(accepted) >= target:
                break
            kind = rng.choices(kinds, weights=wlist)[0]
            lo, hi = _DURATIONS[kind]
            duration = round(rng.uniform(lo, hi), 4) if hi > 0 else 0.0
            latest = horizon * 0.85 - duration
            if latest <= 0.2:
                continue
            t = round(rng.uniform(0.2, latest), 4)
            ev = ChaosEvent(
                t=t, kind=kind, worker=rng.randrange(n_workers),
                duration=duration,
            )
            if _admissible(ev, accepted, n_workers):
                accepted.append(ev)
        events = tuple(sorted(accepted, key=lambda e: (e.t, e.kind, e.worker)))
        return cls(seed=seed, n_workers=n_workers,
                   horizon=round(float(horizon), 4), events=events)

    def replace_events(self, events) -> "ChaosSchedule":
        return ChaosSchedule(
            seed=self.seed, n_workers=self.n_workers, horizon=self.horizon,
            events=tuple(events),
        )

    # -- canonical serialization ------------------------------------------

    def to_json(self) -> str:
        return json.dumps(
            {
                "version": SCHEDULE_VERSION,
                "seed": self.seed,
                "n_workers": self.n_workers,
                "horizon": self.horizon,
                "events": [e.to_dict() for e in self.events],
            },
            sort_keys=True, separators=(",", ":"),
        )

    @classmethod
    def from_json(cls, text: str) -> "ChaosSchedule":
        d = json.loads(text)
        if d.get("version") != SCHEDULE_VERSION:
            raise ValueError(
                f"unsupported schedule version {d.get('version')!r}"
            )
        return cls(
            seed=int(d["seed"]), n_workers=int(d["n_workers"]),
            horizon=float(d["horizon"]),
            events=tuple(ChaosEvent.from_dict(e) for e in d["events"]),
        )


def _overlaps(a0: float, a1: float, b0: float, b1: float) -> bool:
    return a0 < b1 and b0 < a1


def _admissible(ev: ChaosEvent, accepted: List[ChaosEvent],
                n_workers: int) -> bool:
    """The composition constraints (docs/chaos.md §Schedule grammar):

    - at every instant at least one worker is free of kill/drain/
      quarantine (someone must be able to absorb migrations/failovers);
    - a worker carries at most one disabling action at a time (a drain
      order against a dead process is noise, not composition);
    - at most one blackout at a time, and no kill overlapping a blackout
      (a restarted worker re-registers through the statestore — with the
      store dark the restart cannot complete within the liveness bound).
    """
    if ev.kind == "blackout":
        for o in accepted:
            if o.kind == "blackout" and _overlaps(
                ev.t, ev.end(), o.t, o.end()
            ):
                return False
            if o.kind == "kill" and _overlaps(ev.t, ev.end(), o.t, o.end()):
                return False
        return True
    if ev.kind == "kill":
        for o in accepted:
            if o.kind == "blackout" and _overlaps(
                ev.t, ev.end(), o.t, o.end()
            ):
                return False
    if ev.kind in DISABLING:
        disabled = set()
        for o in accepted:
            if o.kind in DISABLING and _overlaps(
                ev.t, ev.end(), o.t, o.end()
            ):
                if o.worker == ev.worker:
                    return False
                disabled.add(o.worker)
        if len(disabled) + 1 >= n_workers:
            return False
    return True


# =========================================================================
# shrink: greedy 1-minimal reduction of a violating schedule
# =========================================================================


def shrink_schedule(
    schedule: ChaosSchedule,
    check: Callable[[ChaosSchedule], bool],
    log: Optional[Callable[[str], None]] = None,
) -> ChaosSchedule:
    """Greedily drop events while ``check`` (True = still violating) holds:
    repeatedly try removing each event; keep any removal that preserves the
    violation; stop at a 1-minimal schedule (removing any single remaining
    event loses the violation). Event count decreases monotonically; the
    result is strictly smaller whenever any event was removable."""
    if not check(schedule):
        raise ValueError("schedule does not violate; nothing to shrink")
    events = list(schedule.events)
    changed = True
    while changed and len(events) > 1:
        changed = False
        i = 0
        while i < len(events) and len(events) > 1:
            candidate = schedule.replace_events(
                events[:i] + events[i + 1:]
            )
            if check(candidate):
                dropped = events.pop(i)
                changed = True
                if log:
                    log(f"shrink: dropped t={dropped.t} {dropped.kind} "
                        f"w{dropped.worker} ({len(events)} left)")
            else:
                i += 1
    return schedule.replace_events(events)


# =========================================================================
# observer: the serving-path hook (constructor-free when the knob is off)
# =========================================================================


class ChaosObserver:
    """Bounded process-global event recorder the invariant suite reads:
    migration completions, drain flips, and quarantine latches land here
    via :func:`note_event` (fed by lazy ``sys.modules.get`` hooks in
    migration/distributed/integrity — no serving module imports chaos).
    Thread-safe: engine threads note migrations, the loop notes drains."""

    def __init__(self, maxlen: int = CHAOS_LOG_MAX):
        self._lock = threading.Lock()
        self._events: deque = deque(maxlen=maxlen)

    def note(self, kind: str, fields: Dict[str, Any]) -> None:
        with self._lock:
            self._events.append((time.monotonic(), kind, dict(fields)))

    def events(self, kind: Optional[str] = None) -> List[tuple]:
        with self._lock:
            out = list(self._events)
        if kind is not None:
            out = [e for e in out if e[1] == kind]
        return out

    def clear(self) -> None:
        with self._lock:
            self._events.clear()


_observer: Optional[ChaosObserver] = None
_env_checked = False
_OBSERVER_LOCK = threading.Lock()


def note_event(kind: str, **fields: Any) -> None:
    """Serving-path hook: record one event into the process observer.

    Zero-overhead contract: with ``DYN_TPU_CHAOS`` unset this is one
    None-check after a once-only env probe — no object is constructed
    (the monkeypatched-ctor guard in tests/test_chaos_plane.py proves it).
    """
    obs = _observer
    if obs is None:
        if _env_checked:
            return
        obs = _arm_from_env()
        if obs is None:
            return
    obs.note(kind, fields)


def _arm_from_env() -> Optional[ChaosObserver]:
    global _observer, _env_checked
    with _OBSERVER_LOCK:
        if _observer is not None:
            return _observer
        if _env_checked:
            return None
        _env_checked = True
        if maybe_from_env() is None:
            return None
        _observer = ChaosObserver()
        logger.warning("chaos observer ARMED from DYN_TPU_CHAOS")
        return _observer


def observer() -> Optional[ChaosObserver]:
    return _observer


def install_observer(obs: Optional[ChaosObserver]) -> None:
    """Explicit arm (the ChaosRunner, tests); env state is not consulted
    again until :func:`reset_for_tests`."""
    global _observer, _env_checked
    with _OBSERVER_LOCK:
        _observer = obs
        _env_checked = True


def reset_for_tests() -> None:
    """Drop the process observer and the once-only env probe (conftest
    autouse reset: one test's chaos events must not bleed into another's
    invariant or zero-overhead assertions)."""
    global _observer, _env_checked
    with _OBSERVER_LOCK:
        _observer = None
        _env_checked = False


# =========================================================================
# invariants
# =========================================================================


INVARIANTS = (
    "safety.bytes",
    "safety.typed_errors",
    "safety.quarantine_no_ship",
    "liveness.streams",
    "liveness.reconverge",
    "conservation.pages",
    "conservation.staged",
    "conservation.disruptions",
)


@dataclass(frozen=True)
class Violation:
    invariant: str
    detail: str

    def to_dict(self) -> dict:
        return {"invariant": self.invariant, "detail": self.detail}


@dataclass
class StreamResult:
    index: int
    prompt: List[int]
    golden: List[int]
    toks: List[int] = field(default_factory=list)
    errs: List[str] = field(default_factory=list)
    done: bool = False
    journal_migrations: int = 0
    journal_resumes: int = 0


@dataclass
class ChaosContext:
    """Everything the invariant suite judges — assembled by the runner,
    constructible by hand in unit tests (injected-violation coverage)."""

    streams: List[StreamResult] = field(default_factory=list)
    engine_snapshots: List[Dict[str, Any]] = field(default_factory=list)
    live_requests: List[int] = field(default_factory=list)
    client_stats: Dict[str, int] = field(default_factory=dict)
    migration_counters: Tuple[int, int, int] = (0, 0, 0)
    # [(start, end)] monotonic quarantine windows + migration completion
    # timestamps (monotonic, ok-only) from the observer
    quarantine_windows: List[Tuple[float, float]] = field(default_factory=list)
    migration_times: List[float] = field(default_factory=list)
    reconverged: bool = True
    reconverge_detail: str = ""
    stuck_streams: List[int] = field(default_factory=list)


class InvariantSuite:
    """The standing cluster invariants (docs/chaos.md §Invariant catalog).
    :meth:`evaluate` returns every violation; :meth:`table` additionally
    reports per-invariant pass/fail for the llmctl rendering."""

    def evaluate(self, ctx: ChaosContext) -> List[Violation]:
        return [v for vs in self.table(ctx).values() for v in vs]

    def table(self, ctx: ChaosContext) -> Dict[str, List[Violation]]:
        out: Dict[str, List[Violation]] = {name: [] for name in INVARIANTS}

        # -- safety: every delivered byte is either equal to the
        # undisturbed control or precedes a typed in-band error ------------
        for s in ctx.streams:
            if s.errs:
                # typed in-band failure: the bytes delivered BEFORE it must
                # still be a control prefix (no wrong bytes, ever)
                if s.toks != s.golden[: len(s.toks)]:
                    out["safety.bytes"].append(Violation(
                        "safety.bytes",
                        f"stream {s.index}: delivered bytes before typed "
                        f"error diverge from control at token "
                        f"{_first_divergence(s.toks, s.golden)}",
                    ))
                continue
            if s.done and s.toks != s.golden:
                out["safety.bytes"].append(Violation(
                    "safety.bytes",
                    f"stream {s.index}: wrong bytes — diverges from "
                    f"control at token {_first_divergence(s.toks, s.golden)}"
                    f" ({len(s.toks)}/{len(s.golden)} delivered)",
                ))
            if not s.done and not s.errs and s.index not in ctx.stuck_streams:
                out["safety.typed_errors"].append(Violation(
                    "safety.typed_errors",
                    f"stream {s.index}: ended incomplete with neither a "
                    f"finish nor a typed in-band error",
                ))

        # -- safety: quarantined processes never donate pages --------------
        # (single-process harness note: the latch is process-global, so
        # this degrades to "no migration completes while ANY quarantine is
        # latched" — documented in docs/chaos.md)
        for t in ctx.migration_times:
            for (q0, q1) in ctx.quarantine_windows:
                if q0 + QUARANTINE_EDGE_GRACE <= t <= q1:
                    out["safety.quarantine_no_ship"].append(Violation(
                        "safety.quarantine_no_ship",
                        f"migration completed at t={t:.3f} inside "
                        f"quarantine window [{q0:.3f}, {q1:.3f}] — "
                        f"untrusted pages were donated",
                    ))

        # -- liveness ------------------------------------------------------
        for i in ctx.stuck_streams:
            out["liveness.streams"].append(Violation(
                "liveness.streams",
                f"stream {i}: stuck past the reaper+deadline bound",
            ))
        if not ctx.reconverged:
            out["liveness.reconverge"].append(Violation(
                "liveness.reconverge",
                ctx.reconverge_detail or "fleet did not reconverge within "
                "the bound after the last fault",
            ))

        # -- conservation --------------------------------------------------
        for w, snap in enumerate(ctx.engine_snapshots):
            blocks = snap.get("kv_active_blocks")
            if blocks:
                out["conservation.pages"].append(Violation(
                    "conservation.pages",
                    f"worker {w}: {blocks} KV blocks still allocated after "
                    f"the fleet settled (leak or unfreed stream)",
                ))
            staged = snap.get("migrate_staged")
            if staged:
                out["conservation.staged"].append(Violation(
                    "conservation.staged",
                    f"worker {w}: {staged} staged migration(s) leaked past "
                    f"settle (TTL sweep or abort failed to free them)",
                ))
        for w, live in enumerate(ctx.live_requests):
            if live:
                out["conservation.pages"].append(Violation(
                    "conservation.pages",
                    f"worker {w}: {live} live request(s) after settle",
                ))

        # ledger equations (exact; docs/chaos.md §Conservation): the
        # client's per-stream journals and its stats counters are two
        # ledgers over the same disruptions and must agree token-for-token
        stats = ctx.client_stats
        if stats:
            j_mig = sum(s.journal_migrations for s in ctx.streams)
            j_res = sum(s.journal_resumes for s in ctx.streams)
            c_mig = stats.get("migrations", 0) + stats.get(
                "migration_resumes", 0
            )
            c_res = stats.get("resumes", 0)
            if j_mig != c_mig:
                out["conservation.disruptions"].append(Violation(
                    "conservation.disruptions",
                    f"journal migrations {j_mig} != client "
                    f"migrations+migration_resumes {c_mig}",
                ))
            if j_res != c_res:
                out["conservation.disruptions"].append(Violation(
                    "conservation.disruptions",
                    f"journal resumes {j_res} != client resumes {c_res}",
                ))
            m_ok = ctx.migration_counters[0]
            if m_ok < stats.get("migrations", 0):
                out["conservation.disruptions"].append(Violation(
                    "conservation.disruptions",
                    f"client followed {stats.get('migrations', 0)} "
                    f"migrations but coordinators shipped only {m_ok}",
                ))
        return out


def _first_divergence(got: List[int], want: List[int]) -> int:
    for i, (a, b) in enumerate(zip(got, want)):
        if a != b:
            return i
    return min(len(got), len(want))


# =========================================================================
# report
# =========================================================================


@dataclass
class ChaosReport:
    schedule: ChaosSchedule
    violations: List[Violation]
    invariants: Dict[str, bool]          # name → passed
    stats: Dict[str, Any]
    decision_log: List[dict]
    traces: List[dict] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_dict(self) -> dict:
        return {
            "ok": self.ok,
            "seed": self.schedule.seed,
            "violations": [v.to_dict() for v in self.violations],
            "invariants": dict(self.invariants),
            "stats": dict(self.stats),
            "decision_log": list(self.decision_log),
        }

    def write(self, run_dir: str) -> None:
        """Dump the replay artifact set: ``schedule.json`` (canonical —
        feed it to ``tools/chaos.py --replay``), ``result.json``, and the
        flight recorder's pinned traces as ``traces.jsonl``."""
        import os

        os.makedirs(run_dir, exist_ok=True)
        with open(os.path.join(run_dir, "schedule.json"), "w") as f:
            f.write(self.schedule.to_json())
        with open(os.path.join(run_dir, "result.json"), "w") as f:
            f.write(json.dumps(self.to_dict(), sort_keys=True, indent=2))
        if self.traces:
            with open(os.path.join(run_dir, "traces.jsonl"), "w") as f:
                for t in self.traces:
                    f.write(json.dumps(t, sort_keys=True) + "\n")


# =========================================================================
# the runner
# =========================================================================


def _next_token(toks: List[int]) -> int:
    """Pure function of the full context — the greedy-decode stand-in for
    the mock fleet (the tests/test_resume.py idiom): any two workers
    continue an identical prefix identically, so resumed output byte-
    compares against an undisturbed control."""
    return (toks[-1] * 31 + len(toks) * 7 + 13) % 50021


def mock_expected_stream(prompt: List[int], max_tokens: int) -> List[int]:
    toks = list(prompt)
    out = []
    for _ in range(max_tokens):
        nxt = _next_token(toks)
        toks.append(nxt)
        out.append(nxt)
    return out


class MockChaosWorker:
    """Deterministic token mock for the no-accelerator fallback: supports
    the kill / delay / blackout / drain legs (no dispatch or pages planes,
    so slow/corrupt/poison/migrate_stall compositions need real engines).
    Duck-types the engine surface the runner's conservation checks read."""

    def __init__(self, tag: str, delay: float = 0.01):
        self.tag = tag
        self.delay = delay
        self._live = 0
        self._fault_addr = "engine"  # serve() rewrites to the worker id

    async def generate(self, request):
        from dynamo_tpu.runtime.annotated import Annotated

        req = request.data
        toks = list(req["token_ids"])
        max_t = int(req["stop_conditions"]["max_tokens"])
        self._live += 1
        try:
            for _ in range(max_t):
                if request.context.is_stopped:
                    return
                nxt = _next_token(toks)
                toks.append(nxt)
                yield Annotated.from_data({"token_ids": [nxt]})
                await asyncio.sleep(self.delay)
            yield Annotated.from_data(
                {"token_ids": [], "finish_reason": "length"}
            )
        finally:
            self._live -= 1

    def live_request_count(self) -> int:
        return self._live

    def metrics_snapshot(self) -> Dict[str, Any]:
        return {"kv_active_blocks": 0, "migrate_staged": 0}

    def close(self) -> None:
        pass


class ChaosRunner:
    """Stand up an N-worker mini-cluster, drive 2x streaming load, apply a
    :class:`ChaosSchedule` through :mod:`runtime.faults` and the control
    verbs, then judge the aftermath with the :class:`InvariantSuite`.

    ``engine_factory(i)`` builds worker ``i``'s engine (real tiny engines
    in the gate; None → the :class:`MockChaosWorker` fallback). Pass
    ``engines`` to reuse pre-built engines across runs (the pairwise smoke
    shares three tiny engines over its whole matrix) — reused engines are
    not closed on exit.
    """

    def __init__(
        self,
        schedule: ChaosSchedule,
        engine_factory: Optional[Callable[[int], Any]] = None,
        engines: Optional[List[Any]] = None,
        policy: Optional[Any] = None,   # ResiliencePolicy
        streams_per_worker: int = 2,
        prompt_len: int = 16,
        max_tokens: int = 20,
        stream_deadline: float = 60.0,
        reconverge_bound: float = 20.0,
        settle_bound: float = 15.0,
        namespace: str = "chaos",
    ):
        self.schedule = schedule
        self.engine_factory = engine_factory
        self._shared_engines = engines
        self.policy = policy
        self.streams_per_worker = streams_per_worker
        self.prompt_len = prompt_len
        self.max_tokens = max_tokens
        self.stream_deadline = stream_deadline
        self.reconverge_bound = reconverge_bound
        self.settle_bound = settle_bound
        self.namespace = namespace
        self.mock = engine_factory is None and engines is None

    # -- cluster plumbing --------------------------------------------------

    def _payload(self, prompt: List[int]) -> dict:
        return {
            "token_ids": list(prompt),
            "stop_conditions": {
                "max_tokens": self.max_tokens, "ignore_eos": True,
            },
            "sampling_options": {"temperature": 0.0},
        }

    def _prompt(self, i: int) -> List[int]:
        return list(range(3 + i, 3 + i + self.prompt_len))

    def _default_policy(self):
        from dynamo_tpu.runtime.resilience import ResiliencePolicy

        return ResiliencePolicy(
            request_timeout=self.stream_deadline,
            connect_timeout=2.0,
            max_attempts=6,
            backoff_base=0.01,
            backoff_max=0.05,
            breaker_threshold=3,
            breaker_cooldown=2.0,
            resume_attempts=4,
            seed=self.schedule.seed,
        )

    def _pace(self) -> float:
        """Seconds a token: the load has to span the schedule horizon —
        otherwise every stream finishes before the first fault lands and
        the run exercises nothing."""
        return max(self.schedule.horizon * 0.7 / self.max_tokens, 0.005)

    async def _build_engine(self, i: int):
        if self._shared_engines is not None:
            return self._shared_engines[i]
        if self.engine_factory is not None:
            return await asyncio.to_thread(self.engine_factory, i)
        return MockChaosWorker(f"w{i}", delay=self._pace())

    def _pace_engines(self, on: bool) -> None:
        """Hold real engines to the mock's pace while the schedule runs (a
        tiny engine on an idle machine decodes its 20-30 tokens in a tenth
        of a second): one sleep ahead of the engine's own per-dispatch
        ``slow`` fault gate, on the engine thread. The goldens, computed
        before, run unpaced."""
        delay = self._pace()
        for e in self._engines:
            if not on:
                e.__dict__.pop("_slow_fault", None)  # the class's again
                continue

            def paced(gate=e._slow_fault) -> None:
                time.sleep(delay)
                gate()

            e._slow_fault = paced

    async def _golden(self, engine, prompt: List[int]) -> List[int]:
        if self.mock:
            return mock_expected_stream(prompt, self.max_tokens)
        from dynamo_tpu.runtime.engine import Context

        out: List[int] = []
        async for item in engine.generate(Context(self._payload(prompt))):
            if item.is_error:
                raise RuntimeError(
                    f"control stream errored: {item.error_message()}"
                )
            out.extend((item.data or {}).get("token_ids", []))
        return out

    async def _serve_worker(self, i: int, ss_url: str):
        from dynamo_tpu.disagg.migration import attach_migration
        from dynamo_tpu.runtime.distributed import DistributedRuntime

        rt = await DistributedRuntime.create(ss_url, "127.0.0.1:1")
        ep = rt.namespace(self.namespace).component("w").endpoint("generate")
        await ep.serve(self._engines[i])
        coord = None
        if not self.mock:
            coord = await attach_migration(ep, self._engines[i])
        return rt, coord

    # -- event application -------------------------------------------------

    async def _apply_start(self, ev: ChaosEvent, inj) -> None:
        from dynamo_tpu.runtime import integrity
        from dynamo_tpu.runtime.faults import FaultRule

        w = ev.worker % len(self._engines)
        if ev.kind == "kill":
            rt = self._rts[w]
            self._rts[w] = None
            with contextlib.suppress(Exception):
                await rt._rpc_server.stop(drain_timeout=0.05)
            with contextlib.suppress(Exception):
                await rt.shutdown()
        elif ev.kind == "blackout":
            inj.begin_blackout()
        elif ev.kind == "drain":
            if self._rts[w] is not None:
                self._rts[w].set_draining(True, source=CHAOS_DRAIN_SOURCE)
        elif ev.kind == "quarantine":
            t0 = time.monotonic()
            integrity.tracker().quarantine(
                source=CHAOS_QUARANTINE_SOURCE,
                reason=f"chaos schedule seed={self.schedule.seed}",
            )
            self._quarantine_open = t0
        elif ev.kind == "slow":
            rule = FaultRule(
                plane="engine", point="dispatch", action="slow",
                match_addr=self._addr_of(w), delay=0.03, jitter=0.03,
            )
            self._timed_rules[id(ev)] = rule
            inj.add_rule(rule)
        elif ev.kind == "corrupt":
            inj.add_rule(FaultRule(
                plane="transfer", point="pages", action="corrupt",
                max_fires=1,
            ))
        elif ev.kind == "poison":
            inj.add_rule(FaultRule(
                plane="engine", point="dispatch", action="poison",
                match_addr=self._addr_of(w), max_fires=1,
            ))
        elif ev.kind == "delay":
            inj.add_rule(FaultRule(
                plane="rpc", point="read", action="delay", delay=0.05,
                max_fires=3,
            ))
        elif ev.kind == "migrate_stall":
            inj.add_rule(FaultRule(
                plane="transfer", point="migrate", action="migrate_stall",
                max_fires=1,
            ))

    async def _apply_end(self, ev: ChaosEvent, inj) -> None:
        from dynamo_tpu.runtime import integrity

        w = ev.worker % len(self._engines)
        if ev.kind == "kill":
            rt, coord = await self._serve_worker(w, self._ss.url)
            self._rts[w] = rt
            self._coords[w] = coord
        elif ev.kind == "blackout":
            inj.end_blackout()
        elif ev.kind == "drain":
            if self._rts[w] is not None:
                self._rts[w].set_draining(False, source=CHAOS_DRAIN_SOURCE)
        elif ev.kind == "quarantine":
            integrity.clear_quarantine(CHAOS_QUARANTINE_SOURCE)
            if self._quarantine_open is not None:
                self._quarantine_windows.append(
                    (self._quarantine_open, time.monotonic())
                )
                self._quarantine_open = None
        elif ev.kind == "slow":
            rule = self._timed_rules.pop(id(ev), None)
            if rule is not None:
                inj.remove_rule(rule)
        elif ev.kind == "migrate_stall":
            inj.release_stalls()

    def _addr_of(self, w: int) -> Optional[str]:
        # serve() rewrites engine._fault_addr from the "engine" sentinel to
        # the worker id, which is what dispatch-point rules match on
        addr = getattr(self._engines[w], "_fault_addr", None)
        return addr if addr not in (None, "engine") else None

    # -- the run -----------------------------------------------------------

    async def run(self) -> ChaosReport:
        from dynamo_tpu.runtime import faults, integrity, tracing
        from dynamo_tpu.disagg import migration as mig_mod
        from dynamo_tpu.runtime.distributed import DistributedRuntime
        from dynamo_tpu.runtime.engine import Context
        from dynamo_tpu.runtime.faults import FaultInjector
        from dynamo_tpu.runtime.statestore import StateStoreServer

        if faults.current() is not None:
            raise RuntimeError("a fault injector is already installed")
        sched = self.schedule
        n = sched.n_workers
        self._timed_rules: Dict[int, Any] = {}
        self._quarantine_windows: List[Tuple[float, float]] = []
        self._quarantine_open: Optional[float] = None

        mig_base = mig_mod.migration_counters()
        prev_observer = observer()
        obs = ChaosObserver()
        install_observer(obs)

        self._engines = [await self._build_engine(i) for i in range(n)]
        n_streams = self.streams_per_worker * n
        prompts = [self._prompt(i) for i in range(n_streams)]
        goldens = [
            await self._golden(self._engines[0], p) for p in prompts
        ]

        self._ss = StateStoreServer(port=0)
        await self._ss.start()
        self._rts: List[Any] = []
        self._coords: List[Any] = []
        fe = client = None
        inj = FaultInjector(seed=sched.seed)
        stuck: List[int] = []
        reconverged, reconverge_detail = True, ""
        try:
            for i in range(n):
                rt, coord = await self._serve_worker(i, self._ss.url)
                self._rts.append(rt)
                self._coords.append(coord)
            fe = await DistributedRuntime.create(
                self._ss.url, "127.0.0.1:1"
            )
            client = await fe.namespace(self.namespace).component(
                "w"
            ).endpoint("generate").client(
                "round_robin", policy=self.policy or self._default_policy()
            )
            await client.wait_for_instances(n, timeout=10)

            faults.install(inj)
            if not self.mock:
                self._pace_engines(True)

            results = [
                StreamResult(index=i, prompt=prompts[i], golden=goldens[i])
                for i in range(n_streams)
            ]

            async def one(i: int) -> None:
                s = results[i]
                ctx = Context(self._payload(s.prompt))
                async for item in client.generate(ctx):
                    if item.is_error:
                        s.errs.append(item.error_message() or "error")
                    elif isinstance(item.data, dict):
                        s.toks.extend(item.data.get("token_ids", []))
                s.done = True
                j = ctx.context.journal
                if j is not None:
                    s.journal_migrations = j.migrations
                    s.journal_resumes = j.resumes

            loop = asyncio.get_running_loop()
            t0 = loop.time()
            tasks = [asyncio.create_task(one(i)) for i in range(n_streams)]

            # unified timeline: starts and ends of every event, in order
            timeline: List[Tuple[float, str, ChaosEvent]] = []
            for ev in sched.events:
                timeline.append((ev.t, "start", ev))
                if ev.duration > 0:
                    timeline.append((ev.end(), "end", ev))
            timeline.sort(key=lambda x: (x[0], x[1] == "start"))
            for when, phase, ev in timeline:
                delay = t0 + when - loop.time()
                if delay > 0:
                    await asyncio.sleep(delay)
                logger.info("chaos %s %s w%d (t=%.2f)", phase, ev.kind,
                            ev.worker, when)
                if phase == "start":
                    await self._apply_start(ev, inj)
                else:
                    await self._apply_end(ev, inj)

            # wait the load out under the liveness bound
            done, pending = await asyncio.wait(
                tasks, timeout=self.stream_deadline
            )
            for i, task in enumerate(tasks):
                if task in pending:
                    stuck.append(i)
                    task.cancel()
            if pending:
                await asyncio.gather(*pending, return_exceptions=True)
            for task in done:
                exc = task.exception()
                if exc is not None:
                    raise exc

            # release everything the schedule may have left holding
            inj.clear_rules()
            inj.end_blackout()
            faults.uninstall()
            for w, rt in enumerate(self._rts):
                if rt is None:  # killed with no restart left in-schedule
                    rt, coord = await self._serve_worker(w, self._ss.url)
                    self._rts[w] = rt
                    self._coords[w] = coord
                rt.set_draining(False, source=CHAOS_DRAIN_SOURCE)
            integrity.clear_quarantine(CHAOS_QUARANTINE_SOURCE)
            if self._quarantine_open is not None:
                self._quarantine_windows.append(
                    (self._quarantine_open, time.monotonic())
                )
                self._quarantine_open = None

            # liveness: the fleet reconverges — full discovery, and a fresh
            # probe stream completes byte-equal within the bound. The probe
            # RETRIES inside the bound: right after an undrain the store
            # can still serve stale draining/unhealthy instance records
            # (the re-put rides the next load-report beat), and a breaker
            # opened by the schedule needs its cooldown — both are the
            # fleet converging, not failing to
            deadline = loop.time() + self.reconverge_bound
            reconverged, reconverge_detail = False, ""
            try:
                await client.wait_for_instances(
                    n, timeout=self.reconverge_bound
                )
            except asyncio.TimeoutError:
                reconverge_detail = (
                    f"discovery never re-listed all {n} workers within "
                    f"{self.reconverge_bound}s of the last fault"
                )
            else:
                while True:
                    probe = StreamResult(
                        index=-1, prompt=prompts[0], golden=goldens[0]
                    )
                    p_ctx = Context(self._payload(probe.prompt))
                    try:
                        async def _probe():
                            async for item in client.generate(p_ctx):
                                if item.is_error:
                                    probe.errs.append(
                                        item.error_message() or "err"
                                    )
                                elif isinstance(item.data, dict):
                                    probe.toks.extend(
                                        item.data.get("token_ids", [])
                                    )
                        await asyncio.wait_for(
                            _probe(), max(deadline - loop.time(), 0.1)
                        )
                    except asyncio.TimeoutError:
                        reconverge_detail = "post-fault probe timed out"
                        break
                    except Exception as e:  # NoHealthyInstances et al.
                        logger.info(
                            "chaos reconverge probe failed (retrying "
                            "within the bound): %s: %s",
                            type(e).__name__, e,
                        )
                        probe.errs.append(f"{type(e).__name__}: {e}")
                    if not probe.errs and probe.toks == probe.golden:
                        reconverged = True
                        break
                    if loop.time() >= deadline:
                        reconverge_detail = (
                            f"post-fault probe failing at the bound: "
                            f"errs={probe.errs[:2]}, "
                            f"{len(probe.toks)}/{len(probe.golden)} tokens"
                        )
                        break
                    await asyncio.sleep(0.25)

            # settle: drains/aborts/TTL sweeps must return every page
            await self._settle()

            ctx = ChaosContext(
                streams=results,
                engine_snapshots=[
                    e.metrics_snapshot() for e in self._engines
                ],
                live_requests=[
                    e.live_request_count() for e in self._engines
                ],
                client_stats=dict(client.stats),
                migration_counters=tuple(
                    a - b for a, b in
                    zip(mig_mod.migration_counters(), mig_base)
                ),
                quarantine_windows=list(self._quarantine_windows),
                migration_times=[
                    t for (t, kind, f) in obs.events("migration")
                    if f.get("ok")
                ],
                reconverged=reconverged,
                reconverge_detail=reconverge_detail,
                stuck_streams=stuck,
            )
            suite = InvariantSuite()
            table = suite.table(ctx)
            violations = [v for vs in table.values() for v in vs]
            report = ChaosReport(
                schedule=sched,
                violations=violations,
                invariants={k: not vs for k, vs in table.items()},
                stats={
                    "streams": n_streams,
                    "stuck": len(stuck),
                    "errored": sum(1 for s in results if s.errs),
                    "client": dict(client.stats),
                    "migrations": ctx.migration_counters[0],
                    "migrations_failed": ctx.migration_counters[1],
                    "mock": self.mock,
                },
                decision_log=[
                    {
                        "seq": getattr(d, "seq", 0), "plane": d.plane,
                        "addr": d.addr, "point": d.point,
                        "op_index": d.op_index, "action": d.action,
                        "detail": getattr(d, "detail", ""),
                    }
                    for d in list(inj.log)
                ],
                traces=[
                    t for t in tracing.recorder().traces()
                    if t.get("pinned")
                ] if violations else [],
            )
            return report
        finally:
            faults.uninstall()
            if not self.mock:
                self._pace_engines(False)
            install_observer(prev_observer)
            if client is not None:
                await client.close()
            for rt in self._rts + ([fe] if fe is not None else []):
                if rt is not None:
                    with contextlib.suppress(Exception):
                        await rt.shutdown()
            if self._shared_engines is None:
                for e in self._engines:
                    with contextlib.suppress(Exception):
                        e.close()
            await self._ss.stop()
            integrity.clear_quarantine(CHAOS_QUARANTINE_SOURCE)

    async def _settle(self) -> None:
        """Poll the fleet quiescent: zero live requests, zero allocated KV
        blocks, zero staged migrations on every worker — the conservation
        invariants judge whatever is left at the bound. A staged migration
        whose client resumed elsewhere is freed by its target's TTL sweep
        (the designed clean-up, 30 s by default): while stages are all
        that is outstanding the settle waits that sweep out, so an unclaimed
        stage reads as a leak only when the sweep failed to free it."""
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        sweep = max(
            (e._migration_ttl() for e in self._engines
             if hasattr(e, "_migration_ttl")),
            default=0.0,
        )
        while True:
            live = staged = blocks = 0
            for e in self._engines:
                snap = e.metrics_snapshot()
                live += e.live_request_count()
                blocks += snap.get("kv_active_blocks") or 0
                staged += snap.get("migrate_staged") or 0
            if not (live or blocks or staged):
                return
            waited = loop.time() - t0
            if waited >= self.settle_bound and (
                live or not staged or waited >= self.settle_bound + sweep
            ):
                return
            await asyncio.sleep(0.1)
