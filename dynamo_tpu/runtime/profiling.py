"""Performance attribution plane: where the microseconds go.

The flight recorder (PR5) and the SLO engine (PR6) can say *that* a
request was slow; nothing in the system could say *where* inside a
dispatch or an event-loop tick the time went — which is why the two
standing perf walls (the Pallas decode kernel losing to dense jnp, and
one frontend process capping at ~50k tok/s) have been guess-and-measure
loops since a record of another machine (2026-07-31). This module is the
shared vocabulary of that layer (docs/observability.md §Profiling):

- **ProfilePolicy** — the ``DYN_TPU_PROFILE*`` knob bundle (PR3 clamping
  contract). ``DYN_TPU_PROFILE`` defaults OFF and is THE zero-overhead
  gate: with it unset, no timeline ring, no frontend CPU accumulator and
  no event-loop lag sampler is ever constructed (tests monkeypatch the
  constructors to prove it), and the engine step loop pays one attribute
  check per dispatch.
- **PhaseClock** — the ONE clock of the engine thread (and, as a second
  instance, of the main thread's start-up): entering a phase opens a
  profiler annotation (a span on the host plane of the device trace, on
  the profiler's clock) and charges the time since the last transition to
  the phase that was innermost, so a phase's counter is its SELF time.
  Always on: no knob; "tracing off" is "no profiler session". Counters
  reach ``/debug/engine`` (``host_phase_us``, ``host_starved_us``, …).
- **StepTimeline** — a process-global, thread-safe ring of per-dispatch
  records fed by the engine step loop from the phase clock: phase
  (prefill ``chunk`` / ``decode`` / ``verify``), batch shape, *device
  time as the host observed it* (dispatch → its blocking read returned,
  never forced) vs *host-side dispatch overhead* (split again into
  pre-dispatch build and post-fetch emit work), allocator time
  (``alloc`` + ``seal_crc`` of the host step), per-step queue depths, and the
  request/trace ids (PR5) riding the batch — plus ``jit_compile`` events
  with the triggering variant/shape detail. A decode roofline share that
  falls with concurrency becomes readable as "device idle between
  dispatches" vs "recompile storm" vs "allocator stall".
- **FrontendCpu / EventLoopLagSampler** — the frontend hot path's
  equivalents: per-token CPU split across detokenize / serialize /
  transport-write (the 19.8 µs/token residue, decomposed) and an
  event-loop lag sampler whose gauges the PR8 planner can consume.
- **Chrome-trace export** — :func:`to_chrome_trace` renders any record
  set as a Perfetto-loadable Chrome trace JSON (one track per engine
  phase, one per event loop, slice args carrying the PR5 ids), served by
  ``GET /debug/profile`` and ``llmctl profile capture --trace``.

Sampling: a sampled dispatch costs one record built from the phase
clock's counters; nothing waits for the device that the served path does
not wait for anyway (the forced ``block_until_ready`` of the sampled
decode dispatch went with PR 39). ``sample_every`` bounds the ring's
churn; untimed dispatches still count into ``dispatches_total``.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

logger = logging.getLogger(__name__)

ENV_PROFILE = "DYN_TPU_PROFILE"
ENV_SAMPLE = "DYN_TPU_PROFILE_SAMPLE"
ENV_RING = "DYN_TPU_PROFILE_RING"
ENV_LAG_MS = "DYN_TPU_PROFILE_LAG_MS"

# engine dispatch phases a timeline record may carry (the chrome-trace
# track names); free-form phases still record — these are the documented set
PHASES = ("chunk", "decode", "verify", "loop_lag")

# the PR3 clamping helpers live in the one shared home rather than being
# copied a fifth time — one clamping contract, one implementation
from dynamo_tpu.runtime.envknobs import (  # noqa: E402
    env_clamped_float as _env_clamped_float,
    env_clamped_int as _env_clamped_int,
    env_flag as _env_flag,
)


@dataclass(frozen=True)
class ProfilePolicy:
    """Knob bundle for the profiling plane (PR3 clamping contract:
    malformed / non-positive values fall back to defaults, in-range
    values clamp into the documented bounds).

    ``enabled``       DYN_TPU_PROFILE (default OFF — 1 arms the plane;
                      0/unset is the zero-overhead gate: nothing is ever
                      constructed).
    ``sample_every``  record every Nth engine dispatch (clamped to
                      [1, 1_000_000]; 1 = every dispatch).
    ``ring_size``     dispatch/event records retained (clamped to
                      [256, 262_144]).
    ``lag_ms``        event-loop lag sampler interval in ms (clamped to
                      [5, 10_000]).
    """

    enabled: bool = False
    sample_every: int = 8
    ring_size: int = 4096
    lag_ms: float = 100.0

    @classmethod
    def from_env(cls) -> "ProfilePolicy":
        d = cls()
        return cls(
            enabled=_env_flag(ENV_PROFILE, d.enabled),
            sample_every=_env_clamped_int(
                ENV_SAMPLE, d.sample_every, 1, 1_000_000
            ),
            ring_size=_env_clamped_int(ENV_RING, d.ring_size, 256, 262_144),
            lag_ms=_env_clamped_float(ENV_LAG_MS, d.lag_ms, 5.0, 10_000.0),
        )


def maybe_from_env() -> Optional[ProfilePolicy]:
    """The gate every integration point None-checks: ``None`` unless the
    profiling plane is armed — with ``DYN_TPU_PROFILE`` unset/0 no policy
    object is ever constructed (the PR9/PR13 zero-overhead pattern)."""
    if not _env_flag(ENV_PROFILE, False):
        return None
    return ProfilePolicy.from_env()


def enabled() -> bool:
    """Cheap boolean form of the gate (one env read, no object)."""
    return _env_flag(ENV_PROFILE, False)


def _pctl(sorted_vals: Sequence[float], q: float) -> float:
    """Nearest-rank percentile over an already-sorted sample list."""
    if not sorted_vals:
        return 0.0
    idx = min(int(q * len(sorted_vals)), len(sorted_vals) - 1)
    return sorted_vals[idx]


# ---------------------------------------------------------------------------
# the engine-side dispatch timeline
# ---------------------------------------------------------------------------


class StepTimeline:
    """Process-global ring of per-dispatch timing records + events.

    Constructed lazily behind the :func:`maybe_from_env` gate — with
    profiling off nothing ever constructs it (the zero-overhead guard
    monkeypatches this constructor to prove it). Thread-safe: the engine
    step thread appends, the RPC/HTTP threads snapshot.

    A dispatch record is a plain dict (wire-ready for ``profile_dump``):

    ``ts``         epoch seconds of the dispatch's host-build start
                   (wall-clock so captures from different workers align
                   on one Perfetto timeline)
    ``phase``      "chunk" | "decode" | "verify"
    ``step``       the engine's step counter
    ``batch``      active lanes in the dispatch
    ``tokens``     tokens this dispatch advances (prefill feed or
                   batch × decode_steps)
    ``host_us``    the host step's ``*_build`` self time (the "dispatch
                   overhead" half of the split)
    ``device_us``  jit call → its blocking read returned, as the host
                   step observed it (never forced: in pipelined decode
                   it is the dispatch before, read in this step, and an
                   upper bound of the device's own time)
    ``post_us``    the host step's ``*_emit`` self time (still dispatch
                   overhead, but attributable to token processing)
    ``alloc_us``   the host step's ``alloc`` + ``seal_crc`` self time
    ``queue``      pending + awaiting-remote-prefill depth at dispatch
    ``reqs``       up to 8 request ids riding the batch (PR5 link)
    ``traces``     their trace ids when tracing is on (PR5 link)
    """

    def __init__(self, policy: Optional[ProfilePolicy] = None):
        self._policy = policy or ProfilePolicy.from_env()
        self._lock = threading.Lock()
        self._records: deque = deque(maxlen=self._policy.ring_size)
        # event-loop lag samples ride their OWN ring: a frontend's ~10
        # samples/s must not evict engine dispatch records or count into
        # sampled_total (a co-hosted engine+frontend shares this object)
        self._lag_records: deque = deque(
            maxlen=min(self._policy.ring_size, 4096)
        )
        self._events: deque = deque(maxlen=min(self._policy.ring_size, 1024))
        self._sample_ctr = 0
        self.dispatches_total = 0
        self.sampled_total = 0
        self.jit_compiles_total = 0

    @property
    def policy(self) -> ProfilePolicy:
        return self._policy

    def should_sample(self) -> bool:
        """One call per dispatch: counts it and decides whether this one
        pays the timing tax (every ``sample_every``-th does)."""
        with self._lock:
            self.dispatches_total += 1
            self._sample_ctr += 1
            if self._sample_ctr >= self._policy.sample_every:
                self._sample_ctr = 0
                return True
            return False

    def note_dispatch(
        self,
        phase: str,
        *,
        step: int = 0,
        batch: int = 0,
        tokens: int = 0,
        host_us: float = 0.0,
        device_us: float = 0.0,
        post_us: float = 0.0,
        alloc_us: float = 0.0,
        queue: int = 0,
        reqs: Sequence[str] = (),
        traces: Sequence[str] = (),
        ts: Optional[float] = None,
    ) -> None:
        rec = {
            # epoch-aligned so multi-worker captures merge onto one
            # Perfetto timeline
            "ts": float(ts) if ts is not None else time.time(),  # dynlint: allow-wall-clock(cross-process trace alignment)
            "phase": str(phase),
            "step": int(step),
            "batch": int(batch),
            "tokens": int(tokens),
            "host_us": round(float(host_us), 1),
            "device_us": round(float(device_us), 1),
            "post_us": round(float(post_us), 1),
            "alloc_us": round(float(alloc_us), 1),
            "queue": int(queue),
        }
        if reqs:
            rec["reqs"] = list(reqs)[:8]
        if traces:
            rec["traces"] = list(traces)[:8]
        with self._lock:
            if phase == "loop_lag":
                self._lag_records.append(rec)
            else:
                self._records.append(rec)
                self.sampled_total += 1

    def note_event(self, kind: str, detail: str = "", phase: str = "") -> None:
        ev = {
            "ts": time.time(),  # dynlint: allow-wall-clock(cross-process trace alignment)
            "kind": str(kind),
            "detail": str(detail),
        }
        if phase:
            ev["phase"] = phase
        with self._lock:
            self._events.append(ev)
            if kind == "jit_compile":
                self.jit_compiles_total += 1

    # -- reads --------------------------------------------------------------

    def records(self, since_s: Optional[float] = None) -> List[dict]:
        with self._lock:
            out = list(self._records) + list(self._lag_records)
        out.sort(key=lambda r: r["ts"])
        if since_s is not None and since_s > 0:
            cutoff = time.time() - since_s  # dynlint: allow-wall-clock(records carry epoch ts)
            out = [r for r in out if r["ts"] >= cutoff]
        return out

    def events(self, since_s: Optional[float] = None) -> List[dict]:
        with self._lock:
            out = list(self._events)
        if since_s is not None and since_s > 0:
            cutoff = time.time() - since_s  # dynlint: allow-wall-clock(events carry epoch ts)
            out = [e for e in out if e["ts"] >= cutoff]
        return out

    def summary(self, since_s: Optional[float] = None) -> Dict[str, Any]:
        """Per-phase device/host quantiles + the idle fraction — the
        "read device_idle_frac first" number of the runbook."""
        recs = self.records(since_s)
        phases: Dict[str, Dict[str, List[float]]] = {}
        for r in recs:
            p = phases.setdefault(
                r["phase"],
                {"device": [], "host": [], "alloc": [], "tokens": []},
            )
            p["device"].append(r["device_us"])
            p["host"].append(r["host_us"] + r["post_us"])
            p["alloc"].append(r["alloc_us"])
            p["tokens"].append(r["tokens"])
        out: Dict[str, Any] = {
            "dispatches_total": self.dispatches_total,
            "sampled_total": self.sampled_total,
            "jit_compiles_total": self.jit_compiles_total,
            "phases": {},
        }
        for name, p in phases.items():
            dev = sorted(p["device"])
            host = sorted(p["host"])
            out["phases"][name] = {
                "count": len(dev),
                "device_us_p50": round(_pctl(dev, 0.50), 1),
                "device_us_p95": round(_pctl(dev, 0.95), 1),
                "host_us_p50": round(_pctl(host, 0.50), 1),
                "host_us_p95": round(_pctl(host, 0.95), 1),
                "alloc_us_p95": round(_pctl(sorted(p["alloc"]), 0.95), 1),
                "tokens": int(sum(p["tokens"])),
            }
        out["device_idle_frac"] = self.device_idle_frac(recs)
        return out

    @staticmethod
    def device_idle_frac(recs: List[dict]) -> float:
        """Fraction of the sampled wall span the device spent NOT
        executing a dispatch. Computed over consecutive *sampled*
        engine-phase records (loop_lag and events excluded): each pair's
        busy time is the earlier record's device time scaled by the step
        delta between them — at a sampling stride of N, the N-1 unsampled
        dispatches in the gap are assumed device-shaped like the sampled
        one (capped at the gap), so the default stride doesn't read a
        fully-busy device as mostly idle."""
        eng = [r for r in recs if r["phase"] in ("chunk", "decode", "verify")]
        if len(eng) < 2:
            return 0.0
        eng.sort(key=lambda r: r["ts"])
        busy = 0.0
        span = 0.0
        for a, b in zip(eng, eng[1:]):
            stride = b["step"] - a["step"]
            gap = b["ts"] - a["ts"]
            if stride <= 0 or gap <= 0:
                continue  # step-counter reset (engine restart) or clock skew
            span += gap
            busy += min(a["device_us"] * stride / 1e6, gap)
        if span <= 0:
            return 0.0
        return round(min(max(1.0 - busy / span, 0.0), 1.0), 4)

    # recent-tail bound for the per-tick gauge computation: plenty of
    # samples for a p95, and the cost stays flat at the max ring size
    GAUGE_WINDOW = 2048

    def gauges(self) -> Dict[str, float]:
        """The worker-gauge view (ForwardPassMetrics fields): decode-phase
        p95 split + idle fraction. Runs on the ~1 s metrics loop, so it
        reads only the most recent :data:`GAUGE_WINDOW` engine records —
        a max-size ring (262k records) must not cost a full copy + sort
        per tick inside the plane whose own overhead budget is <2%."""
        with self._lock:
            n = len(self._records)
            recs = list(
                self._records
            ) if n <= self.GAUGE_WINDOW else [
                self._records[i] for i in range(n - self.GAUGE_WINDOW, n)
            ]
        dev: List[float] = []
        host: List[float] = []
        for r in recs:
            if r["phase"] == "decode":
                dev.append(r["device_us"])
                host.append(r["host_us"] + r["post_us"])
        dev.sort()
        host.sort()
        return {
            "dispatch_device_us_p95": round(_pctl(dev, 0.95), 1),
            "dispatch_host_overhead_us_p95": round(_pctl(host, 0.95), 1),
            "device_idle_frac": self.device_idle_frac(recs),
        }


# ---------------------------------------------------------------------------
# the phase clock: the host's time by name
# ---------------------------------------------------------------------------

# phases of a host step, by index (engine_jax/engine.py names them P_*); a
# span is "engine." + the name with its underscore a dot
ENGINE_PHASES = (
    "step", "wait", "posted", "sweep", "spills", "admit", "alloc", "prepare",
    "chunk_build", "chunk_dispatch", "chunk_fetch", "chunk_emit",
    "decode_build", "decode_dispatch", "decode_fetch", "decode_emit",
    "drain", "seal_read", "seal_crc", "compile",
)
(P_STEP, P_WAIT, P_POSTED, P_SWEEP, P_SPILLS, P_ADMIT, P_ALLOC, P_PREPARE,
 P_CHUNK_BUILD, P_CHUNK_DISPATCH, P_CHUNK_FETCH, P_CHUNK_EMIT,
 P_DECODE_BUILD, P_DECODE_DISPATCH, P_DECODE_FETCH, P_DECODE_EMIT,
 P_DRAIN, P_SEAL_READ, P_SEAL_CRC, P_COMPILE) = range(len(ENGINE_PHASES))
# phases of a start-up, main thread (S_*); spans "setup.<name>"
SETUP_PHASES = (
    "before_main", "devices", "weights", "engine", "lower", "compile",
    "sealing", "http",
)
(S_BEFORE_MAIN, S_DEVICES, S_WEIGHTS, S_ENGINE, S_LOWER, S_COMPILE,
 S_SEALING, S_HTTP) = range(len(SETUP_PHASES))


class PhaseClock:
    """One thread's time, by phase. ``with clock(i):`` enters phase ``i`` of
    ``names``; every transition reads the clock once and charges the time
    since the last one to the phase that was innermost, so ``us[i]`` is
    phase ``i``'s SELF time and the counters sum to the time since
    ``start()``. With ``annotate`` (``jax.profiler.TraceAnnotation``, handed
    over by whoever has jax: this module stays free of it) a phase is also a
    span on the profiler's clock, recorded only while a session runs.

    ``in_flight`` counts dispatches whose blocking read has not returned
    (``dispatched`` / ``fetched``); while it is 0 and ``active`` (the engine
    holds a request in a slot) the time is ALSO charged to ``starved_us``: a
    lower bound of the device's idle time, by what the host was doing. A
    stretch over ``stall_s`` between two transitions, outside ``wait``, is a
    stall: one log line and ``stall``. Not thread-safe: its thread alone
    writes; ``snapshot`` may be read from another (a torn read costs one
    stretch in one snapshot, nothing cumulative)."""

    __slots__ = (
        "names", "spans", "us", "starved_us", "annotate", "step_annotate",
        "in_flight", "active", "steps", "stall", "compile_key", "device_us",
        "step_num", "t_step", "_clock", "_t0", "_mark", "_cur", "_stack",
        "_open", "_next", "_base", "_issued", "_wait", "_compile", "_stall_us",
    )

    def __init__(self, names, prefix, annotate=None, step_annotate=None,
                 stall_s=float("inf"), clock=time.perf_counter):
        self.names = tuple(names)
        self.spans = tuple(prefix + n.replace("_", ".") for n in self.names)
        self.us = [0.0] * len(self.names)
        self.starved_us = [0.0] * len(self.names)
        self.annotate, self.step_annotate = annotate, step_annotate
        self.in_flight, self.active = 0, False
        self.steps = [0, 0]  # host steps that dispatched a chunk; a decode alone
        self.stall = {"count": 0, "longest_ms": 0.0, "phase": None, "step": None}
        self.compile_key = None  # set where a step program is built, served or not
        self.device_us = [0.0, 0.0]  # last dispatch -> read returned: chunk, decode
        self.step_num = 0
        self._clock = clock
        self._t0 = self._mark = self.t_step = clock()
        self._cur, self._stack, self._open, self._next = 0, [], [], 0
        self._base = list(self.us)
        self._issued = (deque(), deque())
        self._wait = self.names.index("wait") if "wait" in self.names else -1
        self._compile = self.names.index("compile") if "compile" in self.names else -1
        self._stall_us = stall_s * 1e6

    def start(self) -> None:
        """The thread whose time this is starts (again) now: what passed
        since the last transition is nobody's."""
        now = self._clock()
        self._t0 += now - self._mark
        self._mark = now

    def _charge(self) -> float:
        now = self._clock()
        d, cur = (now - self._mark) * 1e6, self._cur
        self._mark = now
        self.us[cur] += d
        if cur != self._wait:
            if self.active and not self.in_flight:
                self.starved_us[cur] += d
            if d > self._stall_us:
                st = self.stall
                st["count"] += 1
                if d / 1e3 > st["longest_ms"]:
                    st.update(longest_ms=round(d / 1e3, 1),
                              phase=self.names[cur], step=self.step_num)
                logger.warning("host stall: %.0f ms in %s at step %d",
                               d / 1e3, self.spans[cur], self.step_num)
        return now

    def __call__(self, i: int) -> "PhaseClock":
        self._next = i
        return self

    def step(self, n: int) -> "PhaseClock":
        """The root of one host step's tree (phase 0, a step annotation)."""
        self._next, self.step_num = 0, n
        return self

    def __enter__(self) -> "PhaseClock":
        now, i, span = self._charge(), self._next, None
        self._stack.append(self._cur)
        self._cur = i
        if i == 0:
            self.t_step, self._base = now, list(self.us)
            if self.step_annotate is not None:
                span = self.step_annotate(self.spans[0], step_num=self.step_num)
        elif i == self._compile and self.compile_key is not None:
            key, self.compile_key = self.compile_key, None
            logger.warning("a step program compiles on the served path at "
                           "step %d: %s", self.step_num, key)
            if self.annotate is not None:
                span = self.annotate(self.spans[i] + ":" + key)
        elif self.annotate is not None:
            span = self.annotate(self.spans[i])
        if span is not None:
            span.__enter__()
        self._open.append(span)
        return self

    def __exit__(self, *exc) -> bool:
        self._charge()
        self._cur = self._stack.pop()
        span = self._open.pop()
        if span is not None:
            span.__exit__(None, None, None)
        return False

    def switch(self, i: Optional[int]) -> None:
        """Phases in a row, none inside another (a start-up): leave the one
        that is open and enter ``i``; None ends the row."""
        if self._stack:
            self.__exit__()
        if i is not None:
            self(i).__enter__()

    def credit(self, i: int, us: float, out_of: Optional[int] = None) -> None:
        """Time measured elsewhere (another thread's, the process's before
        this clock) goes to phase ``i``, out of phase ``out_of``'s if given."""
        self.us[i] += us
        if out_of is not None:
            self.us[out_of] -= us

    def dispatched(self, kind: int) -> None:
        """A step program was called (inside its dispatch phase): 0 a chunk,
        1 a decode or verify dispatch."""
        issued = self._mark  # where its dispatch phase began
        self._charge()  # the call itself ran with one fewer in flight
        self.in_flight += 1
        self._issued[kind].append(issued)

    def fetched(self, kind: int) -> None:
        """The blocking read of the oldest such dispatch has returned."""
        now = self._charge()
        if self._issued[kind]:
            self.in_flight -= 1
            self.device_us[kind] = (now - self._issued[kind].popleft()) * 1e6

    def step_us(self, i: int) -> float:
        """Phase ``i``'s self time since the host step began."""
        return self.us[i] - self._base[i]

    def snapshot(self) -> Dict[str, Any]:
        """Cumulative counters for ``/debug/engine``, the stretch in progress
        included: read by difference between two snapshots."""
        now, cur = self._clock(), self._cur
        d = max((now - self._mark) * 1e6, 0.0)
        us, starved = list(self.us), list(self.starved_us)
        us[cur] += d
        if cur != self._wait and self.active and not self.in_flight:
            starved[cur] += d
        return {
            "uptime_us": round((now - self._t0) * 1e6),
            "host_phase_us": {n: round(v) for n, v in zip(self.names, us)},
            "host_starved_us": {n: round(v) for n, v in zip(self.names, starved)},
            "host_steps": {"prefill": self.steps[0], "decode": self.steps[1]},
            "host_stall": dict(self.stall),
        }


def process_age_us() -> Optional[float]:
    """Microseconds since the kernel started this process (interpreter
    start-up and imports, when asked at the top of ``main``): field 22 of
    ``/proc/self/stat`` against the boot clock. None where there is no such
    file."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        return (time.clock_gettime(time.CLOCK_BOOTTIME)
                - ticks / os.sysconf("SC_CLK_TCK")) * 1e6
    except (OSError, ValueError, IndexError, AttributeError):
        return None


# ---------------------------------------------------------------------------
# the frontend-side hot-path accounting
# ---------------------------------------------------------------------------


class FrontendCpu:
    """Per-token CPU attribution for the frontend hot path: detokenize /
    serialize / transport-write, cumulative per part with each part's own
    token count (the stages live in different pipeline layers — a
    detokenizer-only process must not divide by the SSE writer's count).
    Constructed lazily behind the gate (zero-overhead guard monkeypatches
    the constructor); the lock only serializes the cross-thread
    ``/metrics`` read against the event-loop writers."""

    PARTS = ("detokenize", "serialize", "transport_write")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._us: Dict[str, float] = {p: 0.0 for p in self.PARTS}
        self._tokens: Dict[str, int] = {p: 0 for p in self.PARTS}

    def note(self, part: str, us: float, tokens: int = 0) -> None:
        with self._lock:
            self._us[part] = self._us.get(part, 0.0) + us
            self._tokens[part] = self._tokens.get(part, 0) + tokens

    def per_token(self) -> Dict[str, Any]:
        with self._lock:
            out: Dict[str, Any] = {}
            for part in self._us:
                n = self._tokens.get(part, 0)
                out[part] = round(self._us[part] / max(n, 1), 3)
            out["tokens"] = dict(self._tokens)
            return out


class EventLoopLagSampler:
    """Measures how late ``asyncio.sleep(interval)`` wakes on this event
    loop — the direct signal of a saturated frontend process (the ~50k
    tok/s wall shows up here before it shows up in ITL). Keeps an EMA and
    the peak; samples also land in the timeline (phase ``loop_lag``) so
    ``--trace`` captures render the event loop as its own track."""

    def __init__(self, interval_s: float = 0.1,
                 timeline: Optional[StepTimeline] = None):
        self.interval_s = max(float(interval_s), 0.005)
        self.lag_ema_ms = 0.0
        self.lag_max_ms = 0.0
        self.samples = 0
        self._timeline = timeline
        self._task = None
        # start/stop are refcounted: the sampler is process-global and
        # co-hosted services share it — one service stopping must not
        # kill the lag gauges of the others still running
        self._starts = 0

    async def _run(self) -> None:
        import asyncio

        while True:
            t0 = time.perf_counter()
            await asyncio.sleep(self.interval_s)
            lag_ms = max(
                (time.perf_counter() - t0 - self.interval_s) * 1e3, 0.0
            )
            self.samples += 1
            self.lag_ema_ms = (
                lag_ms if self.samples == 1
                else self.lag_ema_ms + 0.2 * (lag_ms - self.lag_ema_ms)
            )
            if lag_ms > self.lag_max_ms:
                self.lag_max_ms = lag_ms
            if self._timeline is not None:
                self._timeline.note_dispatch(
                    "loop_lag", host_us=lag_ms * 1e3,
                )

    def start(self):
        import asyncio

        self._starts += 1
        if self._task is None or self._task.done():
            self._task = asyncio.get_running_loop().create_task(self._run())
        return self._task

    def stop(self) -> None:
        self._starts = max(self._starts - 1, 0)
        if self._starts == 0 and self._task is not None:
            self._task.cancel()
            self._task = None

    def gauges(self) -> Dict[str, float]:
        return {
            "ema_ms": round(self.lag_ema_ms, 3),
            "max_ms": round(self.lag_max_ms, 3),
            "samples": self.samples,
        }


# ---------------------------------------------------------------------------
# process-global accessors (constructor-free reads, lazy writes)
# ---------------------------------------------------------------------------

_TIMELINE: Optional[StepTimeline] = None
_SETUP: Optional[PhaseClock] = None
_FRONTEND: Optional[FrontendCpu] = None
_LAG: Optional[EventLoopLagSampler] = None
_LOCK = threading.Lock()


def timeline() -> StepTimeline:
    """The process-global timeline, constructed on first use — callers
    sit behind the :func:`maybe_from_env` gate, so with profiling off
    nothing ever calls this."""
    global _TIMELINE
    if _TIMELINE is None:
        with _LOCK:
            if _TIMELINE is None:
                _TIMELINE = StepTimeline()
    return _TIMELINE


def setup_clock(annotate=None) -> PhaseClock:
    """The process's start-up clock (main thread; ``cli/run.py``, the
    engine's ``warmup``), constructed on first use. Whoever has jax hands
    over ``jax.profiler.TraceAnnotation`` for its spans."""
    global _SETUP
    if _SETUP is None:
        with _LOCK:
            if _SETUP is None:
                _SETUP = PhaseClock(SETUP_PHASES, "setup.")
    if annotate is not None:
        _SETUP.annotate = annotate
    return _SETUP


def setup_done() -> None:
    """Start-up ends where the port answers. Constructor-free: a process
    that timed no start-up has nothing to end."""
    if _SETUP is not None:
        _SETUP.switch(None)


def setup_phase_s() -> Dict[str, float]:
    """``setup_phase_s`` of ``/debug/engine``: seconds by start-up phase
    (constructor-free: empty where nothing timed a start-up)."""
    c = _SETUP
    if c is None:
        return {}
    return {n: round(v / 1e6, 3) for n, v in zip(c.names, c.us)}


def maybe_timeline() -> Optional[StepTimeline]:
    """Constructor-free read: None until something armed the plane."""
    return _TIMELINE


def frontend_cpu() -> FrontendCpu:
    global _FRONTEND
    if _FRONTEND is None:
        with _LOCK:
            if _FRONTEND is None:
                _FRONTEND = FrontendCpu()
    return _FRONTEND


def maybe_frontend_cpu() -> Optional[FrontendCpu]:
    return _FRONTEND


def lag_sampler(interval_s: Optional[float] = None) -> EventLoopLagSampler:
    """The process's event-loop lag sampler (one per process: co-hosted
    services share the loop, so they share the lag)."""
    global _LAG
    if _LAG is None:
        # resolve the timeline BEFORE taking the module lock: timeline()
        # takes the same non-reentrant lock
        tl = timeline()
        with _LOCK:
            if _LAG is None:
                pol = ProfilePolicy.from_env()
                _LAG = EventLoopLagSampler(
                    interval_s if interval_s is not None
                    else pol.lag_ms / 1e3,
                    timeline=tl,
                )
    return _LAG


def maybe_lag_sampler() -> Optional[EventLoopLagSampler]:
    return _LAG


def note_event(kind: str, detail: str = "", phase: str = "") -> None:
    """Constructor-free event feed (``compile_cache.record_compile``
    forwards here): a no-op until something armed the timeline."""
    t = _TIMELINE
    if t is not None:
        t.note_event(kind, detail, phase)


def gauges() -> Dict[str, float]:
    """Constructor-free worker-gauge read for the metrics publisher:
    empty dict until the plane was ever armed in this process."""
    t = _TIMELINE
    if t is None:
        return {}
    return t.gauges()


def dump_state(since_s: Optional[float] = None) -> Dict[str, Any]:
    """The ``profile_dump`` RPC / ``GET /debug/profile`` payload —
    constructor-free; a process that never armed profiling answers with
    ``enabled: false`` and empty sections."""
    t = _TIMELINE
    out: Dict[str, Any] = {"enabled": enabled()}
    if t is not None:
        out["summary"] = t.summary(since_s)
        out["records"] = t.records(since_s)
        out["events"] = t.events(since_s)
    else:
        out["summary"] = {}
        out["records"] = []
        out["events"] = []
    fc = _FRONTEND
    if fc is not None:
        out["frontend_cpu_us_per_token"] = fc.per_token()
    lag = _LAG
    if lag is not None:
        out["event_loop_lag_ms"] = lag.gauges()
    return out


def render_frontend_prometheus(prefix: str = "dynamo_frontend") -> str:
    """Frontend hot-path gauges for the /metrics exposition —
    constructor-free, empty string until anything was recorded."""
    lines: List[str] = []
    fc = _FRONTEND
    if fc is not None:
        per = fc.per_token()
        full = f"{prefix}_cpu_us_per_token"
        lines.append(
            f"# HELP {full} Frontend hot-path CPU microseconds per "
            f"streamed token, by pipeline part"
        )
        lines.append(f"# TYPE {full} gauge")
        for part in FrontendCpu.PARTS:
            lines.append(f'{full}{{part="{part}"}} {per[part]}')
    lag = _LAG
    if lag is not None:
        g = lag.gauges()
        full = f"{prefix}_event_loop_lag_ms"
        lines.append(
            f"# HELP {full} Event-loop wakeup lag (scheduling delay) in ms"
        )
        lines.append(f"# TYPE {full} gauge")
        lines.append(f'{full}{{stat="ema"}} {g["ema_ms"]}')
        lines.append(f'{full}{{stat="max"}} {g["max_ms"]}')
    return "\n".join(lines) + ("\n" if lines else "")


def reset_for_tests() -> None:
    """Drop the process-global state (conftest autouse reset: one test's
    records/lag samples must not bleed into another's assertions)."""
    global _TIMELINE, _FRONTEND, _LAG, _SETUP
    with _LOCK:
        _SETUP = None
        if _LAG is not None:
            _LAG._starts = 0  # force past the refcount: tests must not leak
            if _LAG._task is not None:
                _LAG._task.cancel()
                _LAG._task = None
        _TIMELINE = None
        _FRONTEND = None
        _LAG = None


# ---------------------------------------------------------------------------
# Chrome-trace (Perfetto-loadable) export
# ---------------------------------------------------------------------------

# stable track ids per phase so multi-capture merges stay aligned
_TRACK_IDS = {"chunk": 1, "decode": 2, "verify": 3, "loop_lag": 8}
_HOST_TRACK = 6
_EVENT_TRACK = 7


def to_chrome_trace(
    captures: Iterable[Tuple[str, List[dict], List[dict]]],
) -> Dict[str, Any]:
    """Render captures as a Chrome-trace JSON object (Perfetto loads it
    directly; ``chrome://tracing`` too).

    ``captures`` is an iterable of ``(process_name, records, events)`` —
    one entry per worker/frontend. Layout: one *process* per capture, one
    *track* (tid) per engine phase plus a ``host`` track (pre-build and
    post-emit slices), an ``events`` track (jit compiles as instant
    events) and an ``event_loop`` track for lag samples. ``ts``/``dur``
    are microseconds since the earliest record across all captures.

    Slices on a track are emitted sorted and non-overlapping: a slice
    whose start precedes the previous slice's end is clamped forward (in
    pipelined decode the next dispatch is *queued* while the previous
    executes — the clamped start is when the device actually got to it).
    """
    caps = [
        (name, list(records), list(events)) for name, records, events in captures
    ]
    t0 = min(
        (
            r["ts"]
            for _, records, events in caps
            for r in list(records) + list(events)
        ),
        default=0.0,
    )

    trace_events: List[dict] = []
    for pid, (name, records, events) in enumerate(caps, start=1):
        trace_events.append({
            "ph": "M", "pid": pid, "tid": 0, "name": "process_name",
            "args": {"name": name},
        })
        named_tracks = dict(_TRACK_IDS)
        for phase, tid in sorted(named_tracks.items()):
            label = "event_loop" if phase == "loop_lag" else f"engine/{phase}"
            trace_events.append({
                "ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
                "args": {"name": label},
            })
        trace_events.append({
            "ph": "M", "pid": pid, "tid": _HOST_TRACK, "name": "thread_name",
            "args": {"name": "engine/host"},
        })
        trace_events.append({
            "ph": "M", "pid": pid, "tid": _EVENT_TRACK, "name": "thread_name",
            "args": {"name": "engine/events"},
        })

        # bucket slices per track, then clamp each track independently
        per_track: Dict[int, List[dict]] = {}
        for r in sorted(records, key=lambda r: r["ts"]):
            base_us = (r["ts"] - t0) * 1e6
            phase = r["phase"]
            tid = named_tracks.get(phase)
            if tid is None:
                tid = named_tracks[phase] = 16 + len(named_tracks)
                trace_events.append({
                    "ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
                    "args": {"name": f"engine/{phase}"},
                })
            args = {
                k: r[k]
                for k in ("step", "batch", "tokens", "queue", "alloc_us",
                          "reqs", "traces")
                if k in r and r[k]
            }
            if phase == "loop_lag":
                # lag sample: one slice whose duration IS the lag
                per_track.setdefault(tid, []).append({
                    "ph": "X", "pid": pid, "tid": tid, "name": "loop_lag",
                    "ts": base_us, "dur": max(r["host_us"], 1.0),
                    "args": args,
                })
                continue
            host_end = base_us + r["host_us"]
            dev_end = host_end + r["device_us"]
            if r["host_us"] > 0:
                per_track.setdefault(_HOST_TRACK, []).append({
                    "ph": "X", "pid": pid, "tid": _HOST_TRACK,
                    "name": f"{phase}.build", "ts": base_us,
                    "dur": r["host_us"], "args": args,
                })
            per_track.setdefault(tid, []).append({
                "ph": "X", "pid": pid, "tid": tid, "name": phase,
                "ts": host_end, "dur": max(r["device_us"], 1.0),
                "args": args,
            })
            if r.get("post_us", 0) > 0:
                per_track.setdefault(_HOST_TRACK, []).append({
                    "ph": "X", "pid": pid, "tid": _HOST_TRACK,
                    "name": f"{phase}.emit", "ts": dev_end,
                    "dur": r["post_us"], "args": args,
                })
        for tid, slices in per_track.items():
            slices.sort(key=lambda s: s["ts"])
            prev_end = -1.0
            for s in slices:
                if s["ts"] < prev_end:
                    # queued behind the previous slice on this track
                    shift = prev_end - s["ts"]
                    s["ts"] = prev_end
                    s["dur"] = max(s["dur"] - shift, 1.0)
                s["ts"] = round(s["ts"], 1)
                s["dur"] = round(s["dur"], 1)
                prev_end = s["ts"] + s["dur"]
                trace_events.append(s)
        for e in sorted(events, key=lambda e: e["ts"]):
            trace_events.append({
                "ph": "i", "pid": pid, "tid": _EVENT_TRACK,
                "name": e["kind"], "ts": round((e["ts"] - t0) * 1e6, 1),
                "s": "t", "args": {"detail": e.get("detail", "")},
            })
    return {
        "traceEvents": trace_events,
        "displayTimeUnit": "ms",
        "otherData": {
            "source": "dynamo_tpu profiling plane (llmctl profile capture)",
            "epoch_t0": t0,
        },
    }


def chrome_trace_json(
    captures: Iterable[Tuple[str, List[dict], List[dict]]],
) -> str:
    return json.dumps(to_chrome_trace(captures))
