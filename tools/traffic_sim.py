"""Million-user traffic simulator: the planner's acceptance harness.

Generates a deterministic synthetic workload — a diurnal curve, flash-crowd
bursts, and a heavy-tail ISL mix (``ISL_MIX``, from a record of another
machine, 2026-07-31) — and drives it through a fluid-queue model of a
mock-worker fleet
(``frontend`` / ``prefill`` / ``decode`` pools of
:class:`~dynamo_tpu.components.mock_worker.MockWorkerStats`). Each tick the
fleet publishes exactly what real workers publish on the ``kv_metrics``
stream, so the telemetry aggregator, SLO engine, and planner see a cluster
they cannot tell from a real one — TPU-less and byte-deterministic.

Two execution modes, same model:

- **virtual time** (:class:`VirtualClock`): hours of simulated traffic in
  milliseconds of wall clock; the scenario unit tests run this way.
- **wall clock** over a real statestore/bus: the tier-1 chaos acceptance
  test (``tests/test_planner.py``) publishes each tick onto a real bus with
  env-scaled SLO windows — the full components-on-a-bus loop in ~seconds.

The queue model is fluid (no per-request RNG): per tick, offered requests
split across the ISL mix by largest-remainder, prefill work drains at the
pool's capacity with the backlog's drain time added to TTFT, decode
utilization inflates ITL, and requests past the decode backlog bound are
dropped as failures — which the acceptance criteria require to stay at
**zero** while the planner scales the pools.

Run:  python -m tools.traffic_sim --scenario burst
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from dynamo_tpu.components.mock_worker import MockWorkerStats

# (isl, probability, zero-queue prefill cost ms) — the heavy-tail prompt mix
# as a record of another machine had it (2026-07-31; llama3.2-1b int8: TTFT
# p50 at each ISL): the simulator's input, not a number of this chip
ISL_MIX: Tuple[Tuple[int, float, float], ...] = (
    (128, 0.55, 151.0),
    (1024, 0.25, 642.0),
    (2048, 0.12, 1579.0),
    (4096, 0.08, 4072.0),
)


class VirtualClock:
    """Injectable monotonic clock the driver advances: hand it to
    ``ClusterTelemetry(clock=...)`` and ``Planner(clock=...)`` and a whole
    diurnal cycle runs in milliseconds, fully deterministic."""

    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t


@dataclass(frozen=True)
class Burst:
    """A flash crowd: ``multiplier``× traffic during [start, start+duration)."""

    start: float
    duration: float
    multiplier: float


class TrafficModel:
    """Deterministic offered-load curve: base rate × diurnal sinusoid ×
    active burst multipliers. ``base_rps`` is requests/s at the diurnal
    mean — size it to the fleet, the shape is what matters."""

    def __init__(
        self,
        base_rps: float,
        diurnal_amplitude: float = 0.0,
        diurnal_period: float = 86400.0,
        bursts: Tuple[Burst, ...] = (),
    ):
        self.base_rps = float(base_rps)
        self.diurnal_amplitude = min(max(float(diurnal_amplitude), 0.0), 1.0)
        self.diurnal_period = max(float(diurnal_period), 1e-6)
        self.bursts = tuple(bursts)

    def rate(self, t: float) -> float:
        # phase chosen so t=0 is the diurnal trough (overnight lull)
        f = 1.0 + self.diurnal_amplitude * math.sin(
            2.0 * math.pi * t / self.diurnal_period - math.pi / 2.0
        )
        for b in self.bursts:
            if b.start <= t < b.start + b.duration:
                f *= b.multiplier
        return self.base_rps * f


class IslMix:
    """Largest-remainder integer split of each tick's requests across the
    ISL classes — exact long-run proportions with zero randomness."""

    def __init__(self, mix: Tuple[Tuple[int, float, float], ...] = ISL_MIX):
        total = sum(p for _, p, _ in mix)
        self.mix = tuple((isl, p / total, cost) for isl, p, cost in mix)
        self._total = 0
        self._alloc = [0] * len(self.mix)

    @property
    def mean_prefill_ms(self) -> float:
        return sum(p * cost for _, p, cost in self.mix)

    def split(self, n: int) -> List[int]:
        """Split ``n`` requests across the classes; counts sum to exactly
        ``n`` every tick, and each class's cumulative total tracks its
        probability to within one request (allocation against the ideal
        cumulative share — a per-tick remainder carry double-counts the
        leftovers it hands out)."""
        self._total += n
        owed = [
            p * self._total - a
            for (_, p, _), a in zip(self.mix, self._alloc)
        ]
        counts = [max(int(w), 0) for w in owed]
        short = n - sum(counts)
        frac = [w - c for w, c in zip(owed, counts)]
        while short > 0:  # leftovers go to the most-owed classes
            i = frac.index(max(frac))
            counts[i] += 1
            frac[i] -= 1.0
            short -= 1
        while short < 0:  # rounding overshot: reclaim from least-owed
            i = max(
                (j for j in range(len(counts)) if counts[j] > 0),
                key=lambda j: counts[j] - owed[j],
            )
            counts[i] -= 1
            frac[i] += 1.0
            short += 1
        for i, c in enumerate(counts):
            self._alloc[i] += c
        return counts


class SimPool:
    """One worker pool: N mock workers + a fluid backlog."""

    def __init__(
        self,
        role: str,
        workers: int,
        rps_per_worker: float,
        slots_per_worker: int = 16,
        seed: int = 0,
    ):
        self.role = role
        self.rps_per_worker = float(rps_per_worker)
        self.slots_per_worker = int(slots_per_worker)
        self.seed = seed
        self.stats: List[MockWorkerStats] = []
        self.backlog = 0.0  # prefill: ms of work; decode/frontend: requests
        self._spawned = 0
        self.scale(workers)

    @property
    def size(self) -> int:
        return len(self.stats)

    def capacity_rps(self) -> float:
        return self.size * self.rps_per_worker

    def worker_ids(self) -> List[str]:
        return [f"{self.role}-{i}" for i in range(self.size)]

    def scale(self, target: int) -> None:
        target = max(int(target), 0)
        while len(self.stats) < target:
            # seed by spawn ordinal: a worker re-added after a scale-down is
            # a NEW process (fresh counters), exactly like the real fleet
            self._spawned += 1
            self.stats.append(MockWorkerStats(
                seed=self.seed * 1000 + self._spawned,
                slots_total=self.slots_per_worker,
                role=self.role,
            ))
        del self.stats[target:]


class FleetModel:
    """The 3-pool fleet + queue model the planner reshapes.

    Prefill work is measured in *mean-request units* (one unit = the ISL
    mix's average prefill cost), so ``rps_per_worker`` means the same thing
    for every pool. ``fail_queue_s`` is the users-gave-up bound: requests
    whose decode backlog exceeds this many seconds of *current* capacity
    are dropped as failures — the planner passes the acceptance scenarios
    only by scaling capacity before the backlog gets there.
    """

    def __init__(
        self,
        decode: int = 2,
        prefill: int = 2,
        frontend: int = 1,
        decode_rps_per_worker: float = 100.0,
        prefill_rps_per_worker: float = 100.0,
        frontend_rps_per_worker: float = 2000.0,
        base_itl_ms: float = 30.0,
        fail_queue_s: float = 60.0,
        mix: Optional[IslMix] = None,
        seed: int = 0,
    ):
        self.pools: Dict[str, SimPool] = {
            "decode": SimPool("decode", decode, decode_rps_per_worker, seed=seed + 1),
            "prefill": SimPool("prefill", prefill, prefill_rps_per_worker, seed=seed + 2),
            "frontend": SimPool(
                "frontend", frontend, frontend_rps_per_worker, seed=seed + 3
            ),
        }
        self.mix = mix or IslMix()
        self.base_itl_ms = float(base_itl_ms)
        self.fail_queue_s = float(fail_queue_s)
        self.offered_total = 0
        self.failed_total = 0
        self._req_carry = 0.0
        self.last: Dict[str, float] = {}

    def scale(self, role: str, target: int) -> None:
        pool = self.pools.get(role)
        if pool is None:
            raise ValueError(f"unknown pool {role!r}")
        pool.scale(target)

    def sizes(self) -> Dict[str, int]:
        return {role: p.size for role, p in self.pools.items()}

    # -- the queue model ----------------------------------------------------

    def tick(self, dt: float, offered: float) -> Dict[str, float]:
        """Advance the fluid model one tick of ``dt`` seconds with
        ``offered`` arriving requests (fractional; carried exactly)."""
        self._req_carry += max(offered, 0.0)
        n = int(self._req_carry)
        self._req_carry -= n
        self.offered_total += n

        fe, pf, dc = (
            self.pools["frontend"], self.pools["prefill"], self.pools["decode"]
        )
        demand_rps = n / dt if dt > 0 else 0.0
        fe_util = demand_rps / max(fe.capacity_rps(), 1e-9)

        # prefill: arrivals weighted by their ISL class's cost relative to
        # the mix mean; the backlog's drain time is the queue wait every
        # request's TTFT pays on top of its ISL-class base cost
        counts = self.mix.split(n)
        mean_cost = max(self.mix.mean_prefill_ms, 1e-9)
        work_units = sum(
            c * cost / mean_cost
            for (_, _, cost), c in zip(self.mix.mix, counts)
        )
        pf_cap = pf.capacity_rps()
        pf.backlog += work_units
        pf.backlog -= min(pf.backlog, pf_cap * dt)
        prefill_wait_ms = (
            pf.backlog / pf_cap * 1000.0 if pf_cap > 0 else 0.0
        )
        pf_util = (work_units / dt) / max(pf_cap, 1e-9) if dt > 0 else 0.0

        # decode: requests drain at pool capacity; utilization inflates ITL
        # (slot contention); past the backlog bound requests fail
        dc_cap = dc.capacity_rps()
        dc.backlog += n
        dc.backlog -= min(dc.backlog, dc_cap * dt)
        failed = int(max(0.0, dc.backlog - self.fail_queue_s * dc_cap))
        dc.backlog -= failed
        self.failed_total += failed
        dc_util = demand_rps / max(dc_cap, 1e-9)
        itl_ms = self.base_itl_ms * max(1.0, dc_util)

        # publishable per-worker state: latency observations land on the
        # pool whose scaling fixes them (ttft → prefill, itl → decode);
        # each request counts once (on its prefill/TTFT booking)
        self._shape(fe, fe_util, queue=0.0)
        self._shape(pf, pf_util, queue=pf.backlog)
        self._shape(dc, dc_util, queue=dc.backlog)
        # aggregated serving (no prefill pool): TTFT books on decode, the
        # pool whose scaling then owns it (planner._pool_slo_names mirror)
        ttft_pool = pf if pf.size else dc
        rr = 0
        if ttft_pool.size:
            for (_, _, cost), c in zip(self.mix.mix, counts):
                ttft = cost + prefill_wait_ms
                for _ in range(c):
                    ttft_pool.stats[rr % ttft_pool.size].observe_request(
                        ttft_ms=ttft
                    )
                    rr += 1
        for i, share in enumerate(self._spread(n - failed, dc.size)):
            for _ in range(share):
                dc.stats[i].observe_request(
                    itl_ms=itl_ms, n_itl=8, count=False
                )
        for i, share in enumerate(self._spread(failed, dc.size)):
            for _ in range(share):
                # count=False: the request already counted at its TTFT
                # booking; recounting here dilutes the error_rate SLO
                dc.stats[i].observe_request(errored=True, count=False)

        self.last = {
            "offered": n, "failed": failed, "dc_util": round(dc_util, 3),
            "itl_ms": round(itl_ms, 1),
            "prefill_wait_ms": round(prefill_wait_ms, 1),
        }
        return self.last

    @staticmethod
    def _spread(total: int, n: int) -> List[int]:
        base, rem = divmod(max(total, 0), max(n, 1))
        return [base + (1 if i < rem else 0) for i in range(n)]

    @staticmethod
    def _shape(pool: SimPool, util: float, queue: float) -> None:
        nw = pool.size
        if nw == 0:
            return
        per_queue = int(math.ceil(max(queue, 0.0) / nw))
        for w in pool.stats:
            w.active = min(
                w.slots_total, int(round(min(util, 1.0) * w.slots_total))
            )
            w.queue_depth = per_queue
            # KV occupancy tracks slot utilization exactly: the fluid model
            # is slot-shaped, and the jittered default would make the
            # KV-binding pool headroom fire the planner off random noise
            w.kv_occupancy = min(util, 1.0)

    def emit(self, model: str) -> List[Tuple[str, Any]]:
        """(worker_id, ForwardPassMetrics) for every live worker."""
        out = []
        for pool in self.pools.values():
            for wid, w in zip(pool.worker_ids(), pool.stats):
                out.append((wid, w.metrics(model)))
        return out


# ---------------------------------------------------------------------------
# scenario driver
# ---------------------------------------------------------------------------


@dataclass
class SimResult:
    duration_s: float = 0.0
    offered_total: int = 0
    failed_total: int = 0
    # page episodes: [{"start": t, "end": t|None}] — None = still paging at
    # scenario end (an acceptance failure)
    episodes: List[dict] = field(default_factory=list)
    pool_peak: Dict[str, int] = field(default_factory=dict)
    pool_final: Dict[str, int] = field(default_factory=dict)
    pool_initial: Dict[str, int] = field(default_factory=dict)
    decisions: List[dict] = field(default_factory=list)
    timeline: List[dict] = field(default_factory=list)

    @property
    def first_page_t(self) -> Optional[float]:
        return self.episodes[0]["start"] if self.episodes else None

    @property
    def recovery_s(self) -> Optional[float]:
        """Worst page-to-clear time across episodes; None = never paged,
        inf = a page never cleared."""
        if not self.episodes:
            return None
        worst = 0.0
        for ep in self.episodes:
            if ep["end"] is None:
                return math.inf
            worst = max(worst, ep["end"] - ep["start"])
        return round(worst, 3)

    def to_dict(self) -> dict:
        rec = self.recovery_s
        return {
            "duration_s": self.duration_s,
            "offered_total": self.offered_total,
            "failed_total": self.failed_total,
            "first_page_t": self.first_page_t,
            # "never" instead of inf: json.dumps would emit the non-standard
            # Infinity token and poison the whole BENCH/CLI record
            "recovery_s": "never" if rec == math.inf else rec,
            "episodes": list(self.episodes),
            "pool_initial": dict(self.pool_initial),
            "pool_peak": dict(self.pool_peak),
            "pool_final": dict(self.pool_final),
            "decisions": list(self.decisions),
        }


async def drive(
    fleet: FleetModel,
    traffic: TrafficModel,
    cluster,
    *,
    duration_s: float,
    tick_s: float,
    sink: Callable[[str, Any], Any],
    model: str = "sim-model",
    planner=None,
    clock: Optional[VirtualClock] = None,
    watch_slos: Tuple[str, ...] = ("ttft_p95", "itl_p95", "error_rate"),
    timeline_every: int = 1,
) -> SimResult:
    """Run the scenario: tick the fleet, publish every worker's metrics
    through ``sink``, step ``planner`` (when given) on its own interval, and
    track the watched SLOs' page/recovery timeline from ``cluster``.

    With a :class:`VirtualClock` the loop never sleeps (bench mode); without
    one it sleeps ``tick_s`` wall-clock between ticks so an external
    planner/aggregator running on the same loop (the chaos test) keeps up.
    """
    res = SimResult(pool_initial=fleet.sizes())
    res.pool_peak = fleet.sizes()
    t = 0.0
    next_plan = planner.policy.interval if planner is not None else math.inf
    ticks = 0
    while t < duration_s:
        if clock is not None:
            clock.t = t
        offered = traffic.rate(t) * tick_s
        fleet.tick(tick_s, offered)
        for wid, metrics in fleet.emit(model):
            out = sink(wid, metrics)
            if asyncio.iscoroutine(out):
                await out
        if planner is not None and t >= next_plan:
            await planner.step(cluster.rollup(), cluster.slo_report())
            next_plan += planner.policy.interval
        for role, size in fleet.sizes().items():
            if size > res.pool_peak.get(role, 0):
                res.pool_peak[role] = size
        ticks += 1
        if ticks % max(timeline_every, 1) == 0:
            states = {
                s["slo"]: s["state"] for s in cluster.slo_report()
                if s.get("labels", {}).get("model") == model
                and s["slo"] in watch_slos
            }
            any_page = any(v == "alert" for v in states.values())
            open_ep = res.episodes and res.episodes[-1]["end"] is None
            if any_page and not open_ep:
                res.episodes.append({"start": round(t, 3), "end": None})
            elif open_ep and states and all(
                v == "ok" for v in states.values()
            ):
                res.episodes[-1]["end"] = round(t, 3)
            res.timeline.append(dict(
                t=round(t, 3), sizes=fleet.sizes(), **fleet.last,
                slo=states,
            ))
        t += tick_s
        if clock is None:
            await asyncio.sleep(tick_s)
    res.duration_s = duration_s
    res.offered_total = fleet.offered_total
    res.failed_total = fleet.failed_total
    res.pool_final = fleet.sizes()
    if planner is not None:
        res.decisions = [d.to_dict() for d in planner.decisions]
    return res


# ---------------------------------------------------------------------------
# packaged scenarios (bench planner_sim + tests import these)
# ---------------------------------------------------------------------------


def _sim_components(
    *,
    fast_s: float,
    slow_s: float,
    planner_interval: float,
    cooldown_up: float,
    cooldown_down: float,
    down_stable: float,
    ttft_target_ms: float = 8000.0,
    enabled: bool = True,
):
    """A virtual-time ClusterTelemetry + Planner pair wired to one clock.
    ``ttft_target_ms`` defaults above the ISL mix's 4096-class base cost —
    the heavy tail is the workload, not a violation; queueing is."""
    from dynamo_tpu.components.planner import (
        Planner,
        PlannerPolicy,
        ProcessActuator,
    )
    from dynamo_tpu.components.telemetry_aggregator import ClusterTelemetry
    from dynamo_tpu.runtime.telemetry import TelemetryPolicy

    clock = VirtualClock()
    policy = TelemetryPolicy(
        fast_window=fast_s, mid_window=fast_s, slow_window=slow_s,
        burn_fast=4.0, burn_slow=2.0, ttft_target_ms=ttft_target_ms,
    )
    cluster = ClusterTelemetry("sim", policy=policy, clock=clock)
    plan_policy = PlannerPolicy(
        enabled=enabled, interval=planner_interval,
        cooldown_up=cooldown_up, cooldown_down=cooldown_down,
        down_stable=down_stable, up_step=1.0, queue_high=4.0,
        min_workers=1, max_workers=32,
    )
    return clock, cluster, plan_policy, Planner, ProcessActuator


async def run_burst_scenario(
    *,
    base_rps: float = 150.0,
    multiplier: float = 5.0,
    warm_s: float = 120.0,
    burst_s: float = 180.0,
    cool_s: float = 900.0,
    tick_s: float = 2.0,
    fast_s: float = 30.0,
    slow_s: float = 120.0,
    planner_interval: float = 5.0,
    cooldown_up: float = 10.0,
    cooldown_down: float = 120.0,
    down_stable: float = 90.0,
    planner_enabled: bool = True,
) -> SimResult:
    """The flash-crowd acceptance scenario in virtual time: warm steady
    state, a ``multiplier``× burst, then a long cool-down so the planner
    can trim back. Defaults are the "staging-scaled" shape (seconds instead
    of the production hours); everything is a knob so the tier-1 test can
    shrink it further and the soak can stretch it. ``planner_enabled=False``
    is the control leg: same traffic, frozen topology — it quantifies what
    the closed loop buys (failures + unbounded page)."""
    clock, cluster, plan_policy, Planner, ProcessActuator = _sim_components(
        fast_s=fast_s, slow_s=slow_s, planner_interval=planner_interval,
        cooldown_up=cooldown_up, cooldown_down=cooldown_down,
        down_stable=down_stable, enabled=planner_enabled,
    )
    fleet = FleetModel(decode=2, prefill=2, frontend=1)
    planner = Planner(
        plan_policy,
        actuators=[ProcessActuator(
            on_scale=lambda d: fleet.scale(d.pool, d.to_replicas)
        )],
        clock=clock,
    )
    traffic = TrafficModel(
        base_rps, bursts=(Burst(warm_s, burst_s, multiplier),)
    )
    return await drive(
        fleet, traffic, cluster,
        duration_s=warm_s + burst_s + cool_s, tick_s=tick_s,
        sink=lambda wid, m: cluster.ingest(wid, m),
        planner=planner, clock=clock,
    )


async def run_diurnal_scenario(
    *,
    base_rps: float = 150.0,
    amplitude: float = 0.6,
    period_s: float = 1800.0,
    cycles: float = 2.0,
    bursts: Tuple[Burst, ...] = (),
    tick_s: float = 2.0,
) -> SimResult:
    """The soak-profile leg: full diurnal cycles (optionally with bursts
    riding the peak) in virtual time — the long-horizon oscillation check.
    Marked ``slow`` where tests run it; the burst scenario is the tier-1
    gate."""
    clock, cluster, plan_policy, Planner, ProcessActuator = _sim_components(
        fast_s=30.0, slow_s=120.0, planner_interval=10.0,
        cooldown_up=20.0, cooldown_down=120.0, down_stable=90.0,
    )
    fleet = FleetModel(decode=2, prefill=2, frontend=1)
    planner = Planner(
        plan_policy,
        actuators=[ProcessActuator(
            on_scale=lambda d: fleet.scale(d.pool, d.to_replicas)
        )],
        clock=clock,
    )
    traffic = TrafficModel(
        base_rps, diurnal_amplitude=amplitude, diurnal_period=period_s,
        bursts=bursts,
    )
    return await drive(
        fleet, traffic, cluster,
        duration_s=period_s * cycles, tick_s=tick_s,
        sink=lambda wid, m: cluster.ingest(wid, m),
        planner=planner, clock=clock, timeline_every=5,
    )


def main() -> None:
    p = argparse.ArgumentParser(description="dynamo_tpu traffic simulator")
    p.add_argument("--scenario", choices=("burst", "diurnal"), default="burst")
    p.add_argument("--base-rps", type=float, default=150.0)
    p.add_argument("--multiplier", type=float, default=5.0)
    args = p.parse_args()
    if args.scenario == "burst":
        res = asyncio.run(run_burst_scenario(
            base_rps=args.base_rps, multiplier=args.multiplier
        ))
    else:
        res = asyncio.run(run_diurnal_scenario(base_rps=args.base_rps))
    print(json.dumps(res.to_dict(), indent=2))


if __name__ == "__main__":
    main()
