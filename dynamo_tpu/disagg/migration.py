"""Live in-flight request migration: a draining worker hands its decode
streams — KV pages and all — to healthy siblings instead of holding the
process hostage until every long stream finishes.

The reference system's thesis is that disaggregation makes KV blocks a
*transferable resource* (NIXL-driven GPU-to-GPU movement between prefill and
decode, SURVEY.md §2.10). This module applies the same move to planned
shutdown: when a worker drains (rolling upgrade, planner trim, spot
preemption notice), every in-flight decode stream is checkpointed and its
pages are pushed to a chosen sibling over the existing transfer plane, so
the stream continues there with **zero recomputed prefill tokens** and
greedy output bitwise identical to an undisturbed control.

Division of labor (docs/resilience.md §Live migration):

- **engine** (engine_jax/engine.py): ``export_migratable`` freezes live
  decode sequences and checkpoints them; ``stage_migration`` on the target
  adopts the wire pages into a pre-built allocation whose
  ``cached_tokens`` covers every already-computed position; admission of
  the re-homed stream then computes exactly one fresh position (the next
  token's feed) — nothing is recomputed.
- **transfer plane** (disagg/transfer.py): a ``migrate`` frame carries the
  checkpoint header + packed pages (int8 scale tables included) atomically;
  any rejection is a typed nack, never a torn page set.
- **client** (runtime/distributed.py EndpointClient): the source ends each
  migrated stream with an in-band ``migrating{target}`` marker; the pinned
  client re-homes onto the target instance (the staged KV makes the
  re-admission free) and falls back to the ordinary PR10 resume path —
  re-admit anywhere, recompute softened by the prefix cache — on ANY
  failure along the way.
- **this module**: the drain-side orchestration (pick targets, ship pages,
  deadline the laggards) plus the knob bundle and the process-global
  counters the telemetry plane publishes.

``DYN_TPU_MIGRATE=0`` restores the exact old drain semantics at zero
overhead: :func:`attach_migration` returns ``None`` without constructing a
coordinator (tests monkeypatch the constructor to prove it).
"""

from __future__ import annotations

import asyncio
import contextlib
import logging
import sys
import threading
import time
import weakref
from dataclasses import dataclass
from typing import List, Optional

# the engine-thread trampoline is the transfer plane's (one implementation
# to fix when the post/loop semantics evolve)
from dynamo_tpu.disagg.transfer import _engine_call
from dynamo_tpu.runtime import integrity

logger = logging.getLogger(__name__)

ENV_MIGRATE = "DYN_TPU_MIGRATE"
ENV_DRAIN_DEADLINE = "DYN_TPU_DRAIN_DEADLINE"
ENV_MIGRATE_TIMEOUT = "DYN_TPU_MIGRATE_TIMEOUT"
ENV_MIGRATE_TTL = "DYN_TPU_MIGRATE_TTL"


# the knob parsers (PR3 clamping contract) live in the one shared home
# (runtime/envknobs.py)
from dynamo_tpu.runtime.envknobs import (  # noqa: E402
    env_clamped_float as _env_pos_float,
    env_flag as _env_flag,
)


@dataclass(frozen=True)
class MigrationPolicy:
    """Knob bundle for drain-time live migration.

    ``enabled``          DYN_TPU_MIGRATE (0 = exact old drain semantics:
                         no coordinator object is ever constructed).
    ``drain_deadline``   total wall-clock a drain may spend migrating
                         before the stragglers are cut over to the client
                         resume path (clamped to [1, 600] s).
    ``migrate_timeout``  per-stream bound on one checkpoint+pages transfer
                         (a stalled target must not eat the whole drain
                         deadline; clamped to [0.5, 120] s).
    ``staged_ttl``       how long a target holds a staged migration whose
                         client never attached before freeing its blocks
                         (clamped to [1, 600] s).
    """

    enabled: bool = True
    drain_deadline: float = 30.0
    migrate_timeout: float = 10.0
    staged_ttl: float = 30.0

    @classmethod
    def from_env(cls) -> "MigrationPolicy":
        d = cls()
        return cls(
            enabled=_env_flag(ENV_MIGRATE, d.enabled),
            drain_deadline=_env_pos_float(
                ENV_DRAIN_DEADLINE, d.drain_deadline, 1.0, 600.0
            ),
            migrate_timeout=_env_pos_float(
                ENV_MIGRATE_TIMEOUT, d.migrate_timeout, 0.5, 120.0
            ),
            staged_ttl=_env_pos_float(
                ENV_MIGRATE_TTL, d.staged_ttl, 1.0, 600.0
            ),
        )


# ---------------------------------------------------------------------------
# process-global outcome counters: the drain side's migrate-outs. Published
# by attach_kv_publishing → ForwardPassMetrics.migrations_* →
# dynamo_worker_migrations_* → aggregator sums → dynamo_cluster_migrations_*.
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_MIGRATIONS = 0
_MIGRATIONS_FAILED = 0
_KV_BLOCKS_MOVED = 0


def note_migration(blocks: int = 0, failed: bool = False) -> None:
    global _MIGRATIONS, _MIGRATIONS_FAILED, _KV_BLOCKS_MOVED
    with _LOCK:
        if failed:
            _MIGRATIONS_FAILED += 1
        else:
            _MIGRATIONS += 1
            _KV_BLOCKS_MOVED += blocks
    # chaos-plane observation hook (docs/chaos.md): reaches the observer
    # only when runtime/chaos.py is already imported AND armed — serving
    # deployments never import it, so this is one dict-get. Outside _LOCK:
    # the observer has its own lock and must not nest under this one.
    ch = sys.modules.get("dynamo_tpu.runtime.chaos")
    if ch is not None:
        ch.note_event("migration", ok=not failed, blocks=blocks)


def migration_counters() -> tuple:
    """(migrations_total, migrations_failed_total, kv_blocks_moved_total)
    — cumulative for this process (the SOURCE side of each migration)."""
    with _LOCK:
        return _MIGRATIONS, _MIGRATIONS_FAILED, _KV_BLOCKS_MOVED


def reset_migration_counters() -> None:
    global _MIGRATIONS, _MIGRATIONS_FAILED, _KV_BLOCKS_MOVED
    with _LOCK:
        _MIGRATIONS = _MIGRATIONS_FAILED = _KV_BLOCKS_MOVED = 0


# weakref registry for the conftest leak guard (the HealthMonitor pattern):
# a test that starts a drain migration and tears down mid-flight must not
# leave the coordinator task running into later tests.
_COORDINATORS: "weakref.WeakSet" = weakref.WeakSet()


def live_coordinators() -> List["MigrationCoordinator"]:
    """Coordinators with a drain task still running (conftest leak guard)."""
    return [
        c for c in _COORDINATORS
        if c._drain_task is not None and not c._drain_task.done()
    ]




class MigrationCoordinator:
    """Drain-side orchestration: freeze → checkpoint → ship → re-home.

    Owned by one serving worker (``attach_migration``). ``notify_drain()``
    (called by ``DistributedRuntime.set_draining``) starts one drain task:

    1. export the engine's migratable sequences (mid-decode, ≥1 emitted
       token) — the engine freezes each out of its slot, decode stops for
       it, its KV pages stay held;
    2. pick a healthy, non-draining sibling with a transfer address for
       each, extract its pages, and ship one ``migrate`` frame (checkpoint
       header + pages, int8 scales included);
    3. on ack, the engine ends the stream with an in-band
       ``migrating{target}`` marker — the client re-homes onto the target
       where the staged pages make re-admission recompute-free;
    4. on ANY failure (transport reset, target nack/OOM/dtype-skew,
       timeout, no eligible sibling) the engine ends the stream with a
       ``migrating{resume}`` marker instead — the client degrades to the
       ordinary resume path. Never a torn stream: the client always gets
       an explicit directive or a transport error it already absorbs.
    5. sequences still prefilling are given time to reach decode (their
       first token is at most one chunk away), then everything left at
       ``drain_deadline`` is cut over to the resume path.

    An undrain mid-flight cancels the task and un-freezes anything not yet
    shipped (the sequences re-enter the decode batch where they left off).
    """

    def __init__(self, runtime, endpoint, engine, transfer_client,
                 address: str, policy: Optional[MigrationPolicy] = None):
        from dynamo_tpu.disagg.protocols import TRANSFER_KEY_PREFIX

        self.runtime = runtime
        self.endpoint = endpoint
        self.engine = engine
        self.client = transfer_client
        self.address = address  # this worker's own transfer address
        self.policy = policy or MigrationPolicy.from_env()
        self._transfer_prefix = (
            f"{endpoint.component.namespace.name}/{TRANSFER_KEY_PREFIX}"
        )
        self._loop = asyncio.get_running_loop()
        self._drain_task: Optional[asyncio.Task] = None
        # drill/bench visibility: per-drain outcome of the last run
        self.last_drain: dict = {}
        _COORDINATORS.add(self)

    # -- drain lifecycle (driven by DistributedRuntime.set_draining) -------

    def notify_drain(self) -> None:
        """Idempotent, thread-safe: schedule the drain migration task."""
        def _start() -> None:
            if self._drain_task is None or self._drain_task.done():
                self._drain_task = asyncio.ensure_future(self._run_drain())
        self._loop.call_soon_threadsafe(_start)

    def cancel_drain(self) -> None:
        """Undrained before the deadline: stop migrating, un-freeze."""
        def _cancel() -> None:
            if self._drain_task is not None and not self._drain_task.done():
                self._drain_task.cancel()
        self._loop.call_soon_threadsafe(_cancel)

    async def stop(self) -> None:
        if self._drain_task is not None:
            self._drain_task.cancel()
            # we cancelled it ourselves: its CancelledError is the expected
            # outcome, not ours to propagate (the HealthMonitor.stop idiom)
            with contextlib.suppress(asyncio.CancelledError, Exception):
                await self._drain_task
            self._drain_task = None
        srv = getattr(self, "_owned_server", None)
        if srv is not None:
            await srv.stop()
            self._owned_server = None

    # -- target discovery ---------------------------------------------------

    async def _eligible_targets(self) -> List[tuple]:
        """(instance_id, worker_id, transfer_address, load_score) of healthy
        non-draining siblings, least-loaded first. Empty on store outage —
        migration then degrades to the resume path (stale-but-safe: we never
        ship pages to an address the store can't currently vouch for)."""
        from dynamo_tpu.runtime.admission import LoadSnapshot
        from dynamo_tpu.runtime.distributed import EXCLUDED_HEALTH, InstanceInfo
        from dynamo_tpu.runtime.health import SUSPECT

        rt = self.runtime
        try:
            entries = await rt.store.get_prefix(self.endpoint.instances_prefix)
            addrs = await rt.store.get_prefix(self._transfer_prefix)
        except (ConnectionError, RuntimeError, OSError):
            return []
        by_worker = {
            k.rsplit("/", 1)[-1]: v.decode() for k, v in addrs.items()
        }
        out = []
        suspects: set = set()
        for key in sorted(entries):
            try:
                info = InstanceInfo.from_json(entries[key])
            except (ValueError, KeyError):
                continue
            if info.worker_id == rt.worker_id:
                continue
            # hard health cut only (EXCLUDED_HEALTH, shared with the
            # router — never a local string list that silently drifts when
            # a state is added): a SUSPECT sibling (fail-slow plane,
            # docs/resilience.md §Fail-slow) stays ELIGIBLE — its outputs
            # and KV are trusted, and a slow home beats a cut stream when
            # it is the only home — but sorts after every brisk sibling
            # below, so it only receives streams as a last resort
            if info.draining or info.health in EXCLUDED_HEALTH:
                continue
            taddr = by_worker.get(info.worker_id)
            if not taddr or taddr == self.address:
                continue
            load = (
                LoadSnapshot.from_wire(info.load).utilization()
                if info.load else 0.0
            )
            if info.health == SUSPECT:
                suspects.add(info.instance_id)
            out.append((info.instance_id, info.worker_id, taddr, load))
        out.sort(key=lambda t: (t[0] in suspects, t[3]))
        return out

    # -- the drain task -----------------------------------------------------

    async def _run_drain(self) -> None:
        from dynamo_tpu.runtime import tracing

        deadline = time.monotonic() + self.policy.drain_deadline
        stats = {"migrated": 0, "failed": 0, "cut": 0, "blocks_moved": 0}
        self.last_drain = stats
        rr = 0
        try:
            while time.monotonic() < deadline:
                if not self.runtime.draining:
                    return  # undrained while we slept
                cps = await _engine_call(self.engine, self.engine.export_migratable)
                if not cps and not await _engine_call(
                    self.engine, self.engine.live_request_count
                ):
                    break  # nothing left in flight
                # a QUARANTINED worker's pages are untrusted by definition
                # (docs/resilience.md §Silent corruption): its drain must
                # NOT replicate them into healthy siblings' caches. Zero
                # targets ⇒ every stream gets a resume directive — exactly
                # the store-outage degradation path, clients recompute from
                # their journals with bytes a healthy worker produces.
                if integrity.quarantined():
                    targets = []
                else:
                    targets = await self._eligible_targets()
                for cp in cps:
                    rid = cp["request_id"]
                    if not targets:
                        await _engine_call(
                            self.engine,
                            lambda r=rid: self.engine.abort_migration(
                                r, "no eligible migration target"
                            ),
                        )
                        stats["failed"] += 1
                        note_migration(failed=True)
                        continue
                    iid, wid, taddr, _ = targets[rr % len(targets)]
                    rr += 1
                    ok = await self._migrate_one(cp, iid, wid, taddr)
                    if ok:  # counted by _migrate_one, with the hand-over
                        stats["migrated"] += 1
                        stats["blocks_moved"] += cp["n_blocks"]
                    else:
                        stats["failed"] += 1
                        note_migration(failed=True)
                # sequences still prefilling become migratable after their
                # first token (at most a chunk away) — short poll, bounded
                # by the deadline
                if not await _engine_call(
                    self.engine, self.engine.live_request_count
                ):
                    break
                await asyncio.sleep(0.02)
            # deadline (or nothing migratable left but streams remain):
            # everything still in flight is cut over to the resume path so
            # the process can actually exit
            cut = await _engine_call(self.engine, self.engine.cut_for_resume)
            stats["cut"] = cut
            if cut:
                logger.warning(
                    "drain deadline: cut %d straggler stream(s) over to the "
                    "resume path", cut,
                )
            tracing.record_event_span(
                "migrate.drain", parent=None,
                attributes=dict(stats, worker=self.runtime.worker_id),
            )
            logger.info(
                "drain migration done: %d migrated (%d blocks), %d failed, "
                "%d cut", stats["migrated"], stats["blocks_moved"],
                stats["failed"], stats["cut"],
            )
        except asyncio.CancelledError:
            # undrain mid-flight: anything frozen but not yet shipped goes
            # back into the decode batch exactly where it stopped
            restored = await _engine_call(
                self.engine, self.engine.unfreeze_migrations
            )
            if restored:
                logger.info(
                    "drain cancelled: %d frozen stream(s) resumed locally",
                    restored,
                )
            raise
        except Exception:
            logger.exception("drain migration task failed")
            await _engine_call(self.engine, self.engine.cut_for_resume)

    async def _migrate_one(self, cp: dict, iid: str, wid: str,
                           taddr: str) -> bool:
        """Ship one frozen stream; returns True when the client was handed a
        target directive, False when it was handed a resume directive."""
        from dynamo_tpu.runtime import faults, tracing

        rid = cp["request_id"]
        with tracing.span(
            "migrate.out", parent=tracing.current_span(),
            attributes={"request_id": rid, "target_worker": wid,
                        "pages": cp["n_blocks"]},
        ):
            async def _ship() -> None:
                await faults.migrate_gate("transfer", taddr)
                pages, crcs = await _engine_call(
                    self.engine,
                    lambda: self.engine.extract_for_migration(rid),
                )
                meta = {
                    "mid": cp["mid"],
                    "request_id": rid,
                    "token_ids": cp["token_ids"],
                    "emitted": cp["emitted"],
                    "tenant": cp["tenant"],
                    "level": cp["level"],
                }
                if crcs is not None:
                    # per-block content checksums ride the checkpoint: the
                    # target verifies the page set BEFORE staging a byte
                    meta["crcs"] = crcs
                await self.client.migrate(taddr, meta, pages)

            try:
                # one bound over the WHOLE ship (fault gate + extraction +
                # transfer): a stalled transfer — or an injected
                # migrate_stall — must cost this stream its timeout, not
                # the entire drain deadline
                await asyncio.wait_for(
                    _ship(), timeout=self.policy.migrate_timeout
                )
            except asyncio.CancelledError:
                raise
            except Exception as e:
                # typed nack (MigrationRejected/KvDtypeMismatch/
                # KvIntegrityError), transport reset, timeout, engine export
                # race: degrade THIS stream to the client resume path; the
                # pages stay untouched on the target (the frame is atomic —
                # a nack stages nothing)
                if isinstance(e, integrity.KvIntegrityError):
                    # the target rejected OUR pages as corrupt: count the
                    # trip against this worker — enough of these within the
                    # window and the quarantine latch flips, after which
                    # this drain stops shipping pages entirely
                    integrity.note_trip("kv", where="migrate_nack")
                logger.warning(
                    "migration of %s to %s failed (%s: %s); degrading to "
                    "resume", rid, wid, type(e).__name__, e,
                )
                await _engine_call(
                    self.engine,
                    lambda: self.engine.abort_migration(
                        rid, f"{type(e).__name__}: {e}"
                    ),
                )
                return False
            def _hand_over() -> None:
                # counted on the engine thread, with the directive: once the
                # stream has left, a stop() of this coordinator (the worker
                # shutting down, its drain done) may cancel this task before
                # it runs again, and the client's count would be one ahead
                self.engine.finish_migrated(rid, iid, wid, cp["mid"])
                note_migration(blocks=cp["n_blocks"])

            await _engine_call(self.engine, _hand_over)
            return True


async def attach_migration(
    endpoint, engine, transfer_server=None,
    policy: Optional[MigrationPolicy] = None,
):
    """Wire drain-time live migration onto a serving worker.

    Starts (or reuses) a KV transfer server on the engine, registers its
    address under the disagg rendezvous key (``{ns}/disagg/kv_transfer/
    {worker_id}`` — migration shares the transfer plane with disaggregated
    prefill), and installs a :class:`MigrationCoordinator` on the runtime so
    ``set_draining`` triggers migration instead of a hostage drain.

    Returns the coordinator, or ``None`` with ``DYN_TPU_MIGRATE=0`` — the
    zero-overhead gate: nothing is constructed, drain behavior is exactly
    pre-migration (tests monkeypatch the constructor to prove it).
    """
    policy = policy or MigrationPolicy.from_env()
    if not policy.enabled:
        return None
    from dynamo_tpu.disagg.protocols import TRANSFER_KEY_PREFIX
    from dynamo_tpu.disagg.transfer import KvTransferClient, KvTransferServer

    rt = endpoint.component.namespace.runtime
    server = transfer_server
    if server is None:
        server = KvTransferServer(engine, host="0.0.0.0", port=0)
        await server.start()
    address = f"{rt.advertise_host}:{server.port}"
    key = (
        f"{endpoint.component.namespace.name}/{TRANSFER_KEY_PREFIX}"
        f"{rt.worker_id}"
    )
    if hasattr(endpoint, "_leased_keys"):
        await endpoint.add_leased_key(key, address.encode())
    else:
        await rt.store.put(key, address.encode(),
                           lease=await rt.primary_lease())
    server.fault_addr = address  # corrupt-drill targeting by worker address
    client = KvTransferClient()
    # outbound migrate frames are labelled with the SOURCE's own address:
    # the corrupt drill models a rotten sender, so its rule must match this
    # worker regardless of which sibling it ships to
    client.fault_addr = address
    if hasattr(engine, "_fault_addr"):
        engine._fault_addr = address  # host-tier/poison drills, same label
    coord = MigrationCoordinator(
        rt, endpoint, engine, client, address, policy=policy
    )
    coord._owned_server = server if transfer_server is None else None
    rt.set_migrator(coord)
    logger.info(
        "live migration enabled: transfer %s, drain deadline %.0fs",
        address, policy.drain_deadline,
    )
    return coord
