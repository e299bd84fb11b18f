"""KV event and metrics protocol types.

Wire-format parity with the reference's event scheme (kv_router/protocols.rs:
19-125): workers emit `stored` events carrying the chain (parent hash + per-
block sequence hash + tokens hash) and `removed` events carrying hashes.
All hashes are the sequence-aware chained xxh3 values from kv/tokens.py.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Union


@dataclass(frozen=True)
class StoredBlock:
    block_hash: int  # sequence-aware chained hash (ExternalSequenceBlockHash)
    tokens_hash: int  # content-only hash (LocalBlockHash)


@dataclass(frozen=True)
class StoredBlocks:
    parent_hash: Optional[int]
    blocks: List[StoredBlock]

    def to_dict(self) -> dict:
        return {
            "type": "stored",
            "parent_hash": self.parent_hash,
            "blocks": [
                {"block_hash": b.block_hash, "tokens_hash": b.tokens_hash}
                for b in self.blocks
            ],
        }


@dataclass(frozen=True)
class RemovedBlocks:
    block_hashes: List[int]

    def to_dict(self) -> dict:
        return {"type": "removed", "block_hashes": list(self.block_hashes)}


KvCacheEventData = Union[StoredBlocks, RemovedBlocks]


@dataclass(frozen=True)
class KvCacheEvent:
    event_id: int
    data: KvCacheEventData

    def to_dict(self) -> dict:
        return {"event_id": self.event_id, "data": self.data.to_dict()}

    @classmethod
    def from_dict(cls, d: dict) -> "KvCacheEvent":
        data = d["data"]
        if data["type"] == "stored":
            payload: KvCacheEventData = StoredBlocks(
                parent_hash=data.get("parent_hash"),
                blocks=[
                    StoredBlock(b["block_hash"], b["tokens_hash"])
                    for b in data["blocks"]
                ],
            )
        else:
            payload = RemovedBlocks(block_hashes=list(data["block_hashes"]))
        return cls(event_id=d["event_id"], data=payload)


@dataclass(frozen=True)
class RouterEvent:
    """A KV cache event attributed to a worker (kv_router/indexer.rs RouterEvent)."""

    worker_id: str
    event: KvCacheEvent

    def to_dict(self) -> dict:
        return {"worker_id": self.worker_id, "event": self.event.to_dict()}

    @classmethod
    def from_dict(cls, d: dict) -> "RouterEvent":
        return cls(worker_id=d["worker_id"], event=KvCacheEvent.from_dict(d["event"]))


@dataclass(frozen=True)
class KVHitRateEvent:
    """Per-scheduling-decision prefix-hit telemetry published on the event
    plane (reference: KVHitRateEvent on the `kv-hit-rate` subject,
    kv_router.rs:52-54 / scheduler.rs emission)."""

    worker_id: str
    isl_blocks: int  # prompt length in blocks
    overlap_blocks: int  # blocks served from that worker's prefix cache

    def to_dict(self) -> dict:
        from dataclasses import asdict

        return asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "KVHitRateEvent":
        return cls(
            worker_id=d["worker_id"],
            isl_blocks=int(d["isl_blocks"]),
            overlap_blocks=int(d["overlap_blocks"]),
        )


@dataclass(frozen=True)
class ScheduleRequest:
    """Request to the KV router's ``schedule`` endpoint: pick a worker for
    this prompt (components/router.py RouterEngine)."""

    token_ids: List[int]

    def to_dict(self) -> dict:
        return {"token_ids": list(self.token_ids)}

    @classmethod
    def from_dict(cls, d: dict) -> "ScheduleRequest":
        return cls(token_ids=list(d.get("token_ids") or []))


@dataclass(frozen=True)
class ScheduleDecision:
    """Reply from the ``schedule`` endpoint: chosen worker + prefix overlap."""

    worker_id: str
    overlap_blocks: int
    prefix_hit_rate: float

    def to_dict(self) -> dict:
        return {
            "worker_id": self.worker_id,
            "overlap_blocks": self.overlap_blocks,
            "prefix_hit_rate": self.prefix_hit_rate,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "ScheduleDecision":
        return cls(
            worker_id=d["worker_id"],
            overlap_blocks=int(d.get("overlap_blocks", 0)),
            prefix_hit_rate=float(d.get("prefix_hit_rate", 0.0)),
        )


@dataclass
class ForwardPassMetrics:
    """Worker load snapshot (reference kv_router/protocols.rs:42-54)."""

    request_active_slots: int = 0
    request_total_slots: int = 1
    kv_active_blocks: int = 0
    kv_total_blocks: int = 1
    num_requests_waiting: int = 0
    gpu_cache_usage_perc: float = 0.0
    gpu_prefix_cache_hit_rate: float = 0.0
    data_parallel_rank: Optional[int] = None
    # overload-protection extras (attach_kv_publishing merges them in):
    # RPC-layer pending requests, requests shed by admission control, and
    # the drain flag (1 ⇒ schedulers must not pick this worker)
    rpc_queue_depth: int = 0
    shed_requests: int = 0
    draining: int = 0
    # health plane (runtime/health.py): self-checked state plus cumulative
    # engine-stall and reaped-stuck-request counters; schedulers skip
    # "unhealthy" workers like draining ones
    health_state: str = "healthy"
    stalls_total: int = 0
    reaped_requests_total: int = 0
    # request-phase latency summary from the tracing plane
    # (runtime/tracing.py phase_summary): {phase: {count, sum_s, p50_ms,
    # p95_ms, p99_ms, buckets}}; None from workers without tracing enabled.
    # Rendered by components/metrics.py as per-phase quantile gauges; the
    # cluster telemetry aggregator diffs the raw `buckets` vectors.
    phase_latency: Optional[dict] = None
    # live engine perf accounting (engine_jax/engine.py, PR6): a roofline
    # share's inputs as live gauges, from host clocks. Zeros from
    # engines without perf sampling (DYN_TPU_SLO=0) or non-JAX engines.
    decode_tokens_per_s: float = 0.0
    step_time_ms: float = 0.0
    batch_slot_util: float = 0.0
    jit_recompiles: int = 0
    kv_peak_occupancy_perc: float = 0.0
    # speculative decoding + KV layout (PR7): acceptance-rate EMA over
    # verify dispatches (0 with speculation off), cumulative drafted/
    # accepted token counters, and whether the KV pool stores int8 pages
    spec_accept_rate: float = 0.0
    spec_drafted_tokens: int = 0
    spec_accepted_tokens: int = 0
    kv_quantized: int = 0
    # request outcome counters from the RPC server (cumulative): the
    # cluster SLO engine diffs them for error-rate / overload-share
    requests_total: int = 0
    requests_errored: int = 0
    # mid-stream resume (docs/resilience.md §Mid-stream resume): cumulative
    # process-level recovery counters (runtime/resilience.resume_counters —
    # streams this process re-admitted elsewhere, and resumable streams
    # that still died in-band). The aggregator sums them into
    # dynamo_cluster_resume_total / dynamo_cluster_resume_failed_total.
    resume_total: int = 0
    resume_failed_total: int = 0
    # live in-flight migration (docs/resilience.md §Live migration):
    # cumulative SOURCE-side drain migrate-outs (streams shipped to a
    # sibling with their KV), failures that degraded to the resume path,
    # and KV blocks moved over the transfer plane. The aggregator sums
    # them into dynamo_cluster_migrations_* / _migrate_kv_blocks_moved.
    migrations_total: int = 0
    migrations_failed_total: int = 0
    migrate_kv_blocks_moved_total: int = 0
    # integrity plane (runtime/integrity.py, docs/resilience.md §Silent
    # corruption): cumulative self-attributable KV checksum failures and
    # output-watchdog lane trips for this process. The aggregator sums
    # them into dynamo_cluster_kv_integrity_failures_total /
    # _watchdog_trips_total; health_state carries "quarantined" when the
    # trip window latched.
    kv_integrity_failures_total: int = 0
    watchdog_trips_total: int = 0
    # performance attribution plane (runtime/profiling.py,
    # docs/observability.md §Profiling): decode-dispatch p95 split into
    # block-until-ready device time vs host-side dispatch overhead, and
    # the fraction of the sampled window the device sat idle between
    # dispatches. Zeros from workers without DYN_TPU_PROFILE armed; the
    # aggregator takes the fleet WORST (max) — a p95/idle fraction is not
    # summable and the slowest worker is the one to look at.
    dispatch_device_us_p95: float = 0.0
    dispatch_host_overhead_us_p95: float = 0.0
    device_idle_frac: float = 0.0
    # fail-slow plane (runtime/straggler.py, docs/resilience.md §Fail-slow):
    # EWMA of step-loop wall microseconds per generated/prefilled token —
    # the normalized latency the telemetry aggregator compares against the
    # peer median for differential straggler verdicts — plus the cumulative
    # detector sample counter (the aggregator's freshness signal: a worker
    # paused by a drain stops sampling and must HOLD its verdict, never
    # earn one) and the worker's own latched verdict ("ok" | "suspect" |
    # "confirmed") echoed back for the cluster suspects rollup. Zeros/"ok"
    # from workers without DYN_TPU_STRAGGLER armed.
    dispatch_us_per_token_ewma: float = 0.0
    straggler_samples_total: int = 0
    straggler_state: str = "ok"
    # process identity for cluster attribution + dashboards
    uptime_s: float = 0.0
    model: Optional[str] = None
    # pool role for topology-aware rollups ("decode" | "prefill" |
    # "frontend" | ""): the planner resizes pools independently, so the
    # cluster rollup must break capacity down by role, not just by model.
    # Empty from pre-planner workers — the aggregator buckets those as
    # "decode" (the only role that existed before the field)
    role: str = ""
    # multi-tenant QoS (runtime/qos.py, docs/qos.md): per-tenant view —
    # {tenant: {"class", "active_slots", "queue_depth", "kv_blocks",
    # "admitted", "rate_limited"}}. None from single-tenant workers (no
    # DYN_TPU_TENANT_* knobs); the aggregator sums the numeric fields into
    # the dynamo_tenant_* cluster gauges.
    tenants: Optional[dict] = None
    # control-plane blackout tolerance (runtime/control_plane.py,
    # docs/resilience.md): this worker's view of the statestore/bus planes
    # ("connected" | "stale" | "disconnected"; "" from pre-blackout
    # workers, read as connected), cumulative events dropped from its
    # outage buffers, and — on snapshots backfilled after a bus outage —
    # how many seconds the snapshot sat buffered before it could publish.
    control_plane_state: str = ""
    bus_dropped_events: int = 0
    stale_s: float = 0.0

    def to_dict(self) -> dict:
        from dataclasses import asdict

        return asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "ForwardPassMetrics":
        known = {f for f in cls.__dataclass_fields__}
        return cls(**{k: v for k, v in d.items() if k in known})


# endpoint name → "dotted.module:ProtocolSymbol" — the KV-routing side of the
# project endpoint registry (see dynamo_tpu/llm/protocols/__init__.py and the
# endpoint-protocol-drift dynlint rule in docs/static_analysis.md)
ENDPOINT_PROTOCOLS = {
    "schedule": "dynamo_tpu.kv_router.protocols:ScheduleRequest",
}
