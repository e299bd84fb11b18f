"""Deterministic noisy-neighbor simulator for the multi-tenant QoS plane.

Drives the REAL policy objects — :class:`~dynamo_tpu.runtime.qos.QosPolicy`,
:class:`~dynamo_tpu.runtime.qos.TenantRateLimiter` (injected virtual
clock), :class:`~dynamo_tpu.runtime.qos.FairQueue`,
:func:`~dynamo_tpu.runtime.qos.split_prefill_budget`, and the engine's
:class:`~dynamo_tpu.engine_jax.allocator.BlockAllocator` (tenant block
accounting + class-tiered eviction) — against a fluid model of one
worker's step loop in *virtual time*. No JAX, no wall clock, no jitter:
the same scenario produces byte-identical latencies every run, which is
what the tier-1 noisy-neighbor chaos gate (tests/test_qos.py) needs. Its
ratios are a simulator's, never a device metric.

The engine model mirrors the aggregated engine's physics: every loop
iteration is ONE dispatch; a dispatch that carries prefill work costs
``step_base_ms + prefill_tokens × prefill_ms_per_token`` (the chunk's
compute scales with the tokens fed), every decode lane advances exactly
one token per dispatch, and a decode lane's inter-token latency IS the
gap between consecutive dispatches — exactly the head-of-line mechanism
a 4096-token prefill uses to spike everyone's ITL (a record of another
machine, 2026-07-31: ~4 s TTFT at ISL 4096).

Scenario (:func:`run_noisy_neighbor`): a *victim* tenant streams steady
short-prompt requests while an *abuser* tenant offers long-prompt
traffic at ~10× its rate quota. Three legs: victim alone (baseline),
victim + abuser with QoS on (rate gate + weighted fair queuing + KV
budget + prefill step budget), and victim + abuser with QoS off (the
control proving the contention is real).

Run:  python -m tools.qos_sim
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from dynamo_tpu.engine_jax.allocator import BlockAllocator
from dynamo_tpu.runtime.qos import (
    FairQueue,
    QosPolicy,
    TenantRateLimiter,
    split_prefill_budget,
)


@dataclass
class SimRequest:
    tenant: str
    arrival_ms: float
    prompt_tokens: int
    gen_tokens: int
    # filled by the sim
    alloc: Optional[object] = None
    prefill_done: int = 0
    emitted: int = 0
    first_token_ms: Optional[float] = None
    token_times_ms: List[float] = field(default_factory=list)
    shed: bool = False


@dataclass
class TenantOutcome:
    offered: int = 0
    completed: int = 0
    shed: int = 0
    itl_p95_ms: float = 0.0
    itl_max_ms: float = 0.0
    ttft_p95_ms: float = 0.0

    def to_dict(self) -> dict:
        return self.__dict__.copy()


def _p95(xs: List[float]) -> float:
    if not xs:
        return 0.0
    s = sorted(xs)
    return s[min(int(0.95 * (len(s) - 1) + 0.5), len(s) - 1)]


@dataclass
class SimConfig:
    """One worker's shape + cost model (virtual milliseconds)."""

    slots: int = 8
    kv_blocks: int = 2048
    block_size: int = 16
    prefill_chunk: int = 256  # per-dispatch prefill consumption cap
    # average prefill tokens per dispatch while decode lanes are live
    # (the engine's DYN_TPU_PREFILL_BUDGET duty cycle; 0 = unlimited).
    # One chunk dispatch is followed by ~chunk/budget pure decode
    # dispatches, so only a budget/chunk share of decode gaps ever carry
    # prefill work — that share is what keeps the victim's p95 intact.
    prefill_budget: int = 8
    step_base_ms: float = 3.0
    prefill_ms_per_token: float = 0.2
    decode_ms_per_lane: float = 0.4
    horizon_ms: float = 60_000.0


class WorkerSim:
    """Virtual-time single-worker loop over the real QoS policy objects."""

    def __init__(self, cfg: SimConfig, qos: Optional[QosPolicy]):
        self.cfg = cfg
        self.qos = qos
        self.now_ms = 0.0
        self.allocator = BlockAllocator(cfg.kv_blocks, cfg.block_size)
        self.fair = FairQueue() if qos is not None else None
        self.limiter = (
            TenantRateLimiter(qos, clock=lambda: self.now_ms / 1e3)
            if qos is not None and qos.rate_rps > 0 else None
        )
        self.kv_budget = (
            max(1, int(qos.kv_frac * cfg.kv_blocks))
            if qos is not None and qos.kv_frac > 0 else 0
        )
        self.pending: List[SimRequest] = []
        self.slots: List[Optional[SimRequest]] = [None] * cfg.slots
        self.done: List[SimRequest] = []
        self._uid = 0  # distinct token ids → no accidental prefix reuse
        self._prefill_debt = 0.0  # duty-cycle state (engine mirror)

    # -- admission ---------------------------------------------------------

    def offer(self, req: SimRequest) -> None:
        """Arrival hits the admission gate (rate bucket) immediately —
        the RPC server's try_admit analogue."""
        if self.limiter is not None and self.limiter.take(req.tenant) > 0:
            req.shed = True
            self.done.append(req)
            return
        self.pending.append(req)

    def _tokens_for(self, req: SimRequest) -> List[int]:
        self._uid += 1
        base = self._uid * 1_000_000
        return [base + i for i in range(req.prompt_tokens)]

    def _contended(self, tenant: str) -> bool:
        return any(
            s is not None and s.tenant != tenant for s in self.slots
        ) or any(p.tenant != tenant for p in self.pending)

    def _admit(self) -> None:
        progress = True
        while progress:
            progress = False
            free = [i for i, s in enumerate(self.slots) if s is None]
            if not free or not self.pending:
                return
            # weighted-fair pick (QoS) vs FIFO (control leg)
            if self.fair is not None and len(self.pending) > 1:
                idx = self.fair.pick([p.tenant for p in self.pending])
            else:
                idx = 0
            req = self.pending[idx]
            level, _w = (self.qos.class_of(req.tenant)
                         if self.qos is not None else (0, 1.0))
            need = self.allocator.blocks_needed(
                req.prompt_tokens + req.gen_tokens
            )
            if self.kv_budget and self._contended(req.tenant):
                held = self.allocator.tenant_blocks.get(req.tenant, 0)
                if held + need > self.kv_budget:
                    # over-share tenant defers; try the next candidate
                    others = [
                        p for p in self.pending if p.tenant != req.tenant
                    ]
                    if not others:
                        return
                    req = others[0]
                    level, _w = (self.qos.class_of(req.tenant)
                                 if self.qos is not None else (0, 1.0))
                    need = self.allocator.blocks_needed(
                        req.prompt_tokens + req.gen_tokens
                    )
            alloc = self.allocator.allocate_sequence(
                self._tokens_for(req), tenant=req.tenant, level=level,
            )
            if alloc is None:
                return  # pool exhausted: wait for completions
            # reserve decode growth up front (fluid model: no preemption)
            self.allocator.grow(
                alloc, req.prompt_tokens + req.gen_tokens
            )
            req.alloc = alloc
            self.pending.remove(req)
            self.slots[free[0]] = req
            progress = True

    # -- one dispatch ------------------------------------------------------

    def step(self) -> bool:
        """One engine dispatch; returns False when fully idle."""
        self._admit()
        active = [s for s in self.slots if s is not None]
        if not active:
            return False
        prefilling = [s for s in active if s.prefill_done < s.prompt_tokens]
        decoding = [s for s in active if s.prefill_done >= s.prompt_tokens]
        budget = self.cfg.prefill_budget if self.qos is not None else 0
        if prefilling and decoding and budget > 0:
            # duty cycle (the engine's _dispatch_step pacing): every
            # dispatch earns `budget` tokens of prefill credit; a chunk
            # dispatch spends what it consumed. While in debt, prefill
            # lanes sit out and decode runs at full speed.
            self._prefill_debt = max(self._prefill_debt - budget, 0.0)
            if self._prefill_debt > 0:
                prefilling = []
        prefill_tokens = 0
        if prefilling:
            if self.fair is not None and len(prefilling) > 1:
                prefilling.sort(key=lambda s: self.fair.vt(s.tenant))
            rem = [s.prompt_tokens - s.prefill_done for s in prefilling]
            # with decode lanes live, one chunk's worth of prefill total
            # (starved tenant first); alone, every lane takes a full chunk
            cap = self.cfg.prefill_chunk if (decoding and budget > 0) else 0
            allows = split_prefill_budget(rem, self.cfg.prefill_chunk, cap)
            for s, n in zip(prefilling, allows):
                s.prefill_done += n
                prefill_tokens += n
                if self.fair is not None:
                    _, w = self.qos.class_of(s.tenant)
                    self.fair.charge(s.tenant, n, w)
            if decoding and budget > 0:
                self._prefill_debt += prefill_tokens
        cost = (
            self.cfg.step_base_ms
            + prefill_tokens * self.cfg.prefill_ms_per_token
            + len(decoding) * self.cfg.decode_ms_per_lane
        )
        self.now_ms += cost
        # prefill completions sample their first token at the end of the
        # dispatch that finished the prompt (the chunk fn's sample_at)
        for s in prefilling:
            if s.prefill_done >= s.prompt_tokens:
                s.first_token_ms = self.now_ms
                s.token_times_ms.append(self.now_ms)
                s.emitted += 1
        for s in decoding:
            s.token_times_ms.append(self.now_ms)
            s.emitted += 1
            if self.fair is not None:
                _, w = self.qos.class_of(s.tenant)
                self.fair.charge(s.tenant, 1, w)
        for i, s in enumerate(self.slots):
            if s is not None and s.emitted >= s.gen_tokens:
                self.allocator.free_sequence(s.alloc)
                self.slots[i] = None
                self.done.append(s)
        return True


def run_noisy_neighbor(
    with_abuser: bool = True,
    qos_on: bool = True,
    cfg: Optional[SimConfig] = None,
    victim_requests: int = 24,
    victim_interval_ms: float = 400.0,
    victim_prompt: int = 64,
    victim_gen: int = 24,
    abuser_interval_ms: float = 100.0,
    abuser_prompt: int = 1024,
    abuser_gen: int = 8,
) -> Dict[str, TenantOutcome]:
    """One leg of the noisy-neighbor scenario → per-tenant outcomes.

    QoS policy: victim = ``standard`` (weight 4), abuser = ``batch``
    (weight 1, level 0 — first to be evicted/preempted). The rate knob
    gives the abuser a 0.5 req/s quota; at a 100 ms offered interval it
    runs at ~20× quota, so the rate gate alone absorbs most of the flood
    and WFQ + the prefill step budget absorb what leaks through.
    """
    cfg = cfg or SimConfig()
    qos = None
    if qos_on:
        qos = QosPolicy(
            tenant_map={"victim": "standard", "abuser": "batch"},
            rate_rps=0.5,  # × weight: victim 2 req/s, abuser 0.5 req/s
            burst=2.0,
            kv_frac=0.5,
        )
    sim = WorkerSim(cfg, qos)

    arrivals: List[SimRequest] = [
        SimRequest("victim", i * victim_interval_ms, victim_prompt, victim_gen)
        for i in range(victim_requests)
    ]
    if with_abuser:
        n_abuse = int(cfg.horizon_ms / abuser_interval_ms)
        arrivals += [
            SimRequest("abuser", 50.0 + i * abuser_interval_ms,
                       abuser_prompt, abuser_gen)
            for i in range(n_abuse)
        ]
    arrivals.sort(key=lambda r: (r.arrival_ms, r.tenant))

    i = 0
    while sim.now_ms < cfg.horizon_ms and (
        i < len(arrivals) or sim.pending or any(sim.slots)
    ):
        while i < len(arrivals) and arrivals[i].arrival_ms <= sim.now_ms:
            sim.offer(arrivals[i])
            i += 1
        if not sim.step():
            # idle: jump to the next arrival
            if i < len(arrivals):
                sim.now_ms = max(sim.now_ms, arrivals[i].arrival_ms)
                continue
            break

    out: Dict[str, TenantOutcome] = {}
    for req in sim.done + [s for s in sim.slots if s is not None] + sim.pending:
        o = out.setdefault(req.tenant, TenantOutcome())
        o.offered += 1
        if req.shed:
            o.shed += 1
        elif req.emitted >= req.gen_tokens:
            o.completed += 1
    for tenant, o in out.items():
        itls: List[float] = []
        ttfts: List[float] = []
        for req in sim.done:
            if req.tenant != tenant or req.shed:
                continue
            if req.first_token_ms is not None:
                ttfts.append(req.first_token_ms - req.arrival_ms)
            ts = req.token_times_ms
            itls.extend(b - a for a, b in zip(ts, ts[1:]))
        o.itl_p95_ms = round(_p95(itls), 3)
        o.itl_max_ms = round(max(itls), 3) if itls else 0.0
        o.ttft_p95_ms = round(_p95(ttfts), 3)
    return out


def run_scenario(cfg: Optional[SimConfig] = None) -> dict:
    """All three legs, as the CLI reports them."""
    alone = run_noisy_neighbor(with_abuser=False, qos_on=True, cfg=cfg)
    qos = run_noisy_neighbor(with_abuser=True, qos_on=True, cfg=cfg)
    ctrl = run_noisy_neighbor(with_abuser=True, qos_on=False, cfg=cfg)
    v_alone = alone["victim"]
    v_qos = qos["victim"]
    v_ctrl = ctrl["victim"]
    return {
        "victim_alone": v_alone.to_dict(),
        "victim_with_abuser_qos": v_qos.to_dict(),
        "victim_with_abuser_no_qos": v_ctrl.to_dict(),
        "abuser_qos": qos["abuser"].to_dict(),
        "abuser_no_qos": ctrl["abuser"].to_dict(),
        "victim_itl_p95_ratio_qos": round(
            v_qos.itl_p95_ms / v_alone.itl_p95_ms, 4
        ) if v_alone.itl_p95_ms else None,
        "victim_itl_p95_ratio_no_qos": round(
            v_ctrl.itl_p95_ms / v_alone.itl_p95_ms, 4
        ) if v_alone.itl_p95_ms else None,
    }


if __name__ == "__main__":
    print(json.dumps(run_scenario(), indent=2))
