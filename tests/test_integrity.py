"""Silent-corruption defense (ISSUE 14): end-to-end KV integrity +
poisoned-worker quarantine (docs/resilience.md §Silent corruption).

Coverage:

- knob clamp tables + the DYN_TPU_KV_INTEGRITY=0 zero-overhead guard
  (monkeypatched tracker/checksum constructors: nothing is ever built, no
  crc is ever computed, the jitted programs keep the pre-integrity
  signature);
- checksum plumbing units: page/block checksums, verify semantics
  (checksum-less frames always parse), the trip tracker's threshold/window
  latch under an injected clock, and quarantine source semantics;
- host-tier rehit verification on a REAL tiny engine: a bit-flipped host
  pool entry is dropped as a prefix miss and the prompt recomputes
  byte-identically, with the trip counted;
- output watchdog on a REAL tiny engine: an injected ``poison`` dispatch
  (NaN logits) ends the lane typed and in-band — zero garbage tokens
  emitted;
- migration staging verification: corrupt pages raise typed BEFORE any
  pool state changes (no torn staged entry), and the transfer plane's
  nack teaches the sender to count the trip against itself;
- quarantine plane: health-monitor transitions (sticky, own drain
  source), EndpointClient exclusion, llmctl worker quarantine/unquarantine
  round-trip over a real statestore (exit 0/2);
- integrity counters worker → aggregator → cluster (promtext-parsed) +
  the mock_worker drill flags;
- THE chaos gate: one worker emitting corrupt pages under 2x load is
  drained → every migration nacks typed, zero wrong bytes ever reach a
  client (all streams byte-equal to undisturbed controls via resume), the
  victim quarantines within the trip threshold, its drain migrates
  NOTHING — and a healthy worker's drain afterwards still migrates.
"""

import asyncio
import concurrent.futures
import json

import numpy as np
import pytest

from dynamo_tpu.disagg import migration as mig_mod
from dynamo_tpu.disagg.migration import attach_migration
from dynamo_tpu.kv import pages as kv_pages
from dynamo_tpu.runtime import faults, integrity, resilience
from dynamo_tpu.runtime.annotated import Annotated
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.engine import AsyncEngine, Context
from dynamo_tpu.runtime.faults import FaultInjector, FaultRule
from dynamo_tpu.runtime.integrity import (
    IntegrityPolicy,
    IntegrityTracker,
    KvIntegrityError,
)
from dynamo_tpu.runtime.resilience import ResiliencePolicy
from dynamo_tpu.runtime.statestore import StateStoreServer

from .fixtures import engines_held_back

NO_BUS = "127.0.0.1:1"


# -- knobs ---------------------------------------------------------------------


class TestIntegrityKnobs:
    def test_from_env_table(self, monkeypatch):
        cases = [
            ({}, IntegrityPolicy()),
            ({"DYN_TPU_KV_INTEGRITY": "0"}, IntegrityPolicy(enabled=False)),
            ({"DYN_TPU_KV_INTEGRITY": "off"}, IntegrityPolicy(enabled=False)),
            ({"DYN_TPU_KV_INTEGRITY": "1"}, IntegrityPolicy(enabled=True)),
            # clamps: malformed/non-positive → defaults; out of range → edge
            ({"DYN_TPU_INTEGRITY_TRIPS": "junk"}, IntegrityPolicy()),
            ({"DYN_TPU_INTEGRITY_TRIPS": "-2"}, IntegrityPolicy()),
            ({"DYN_TPU_INTEGRITY_TRIPS": "9999"},
             IntegrityPolicy(trip_threshold=1000)),
            ({"DYN_TPU_INTEGRITY_TRIPS": "5"},
             IntegrityPolicy(trip_threshold=5)),
            ({"DYN_TPU_INTEGRITY_WINDOW": "0"}, IntegrityPolicy()),
            ({"DYN_TPU_INTEGRITY_WINDOW": "99999"},
             IntegrityPolicy(trip_window=3600.0)),
            ({"DYN_TPU_INTEGRITY_LOGIT_LIMIT": "1"},
             IntegrityPolicy(logit_limit=10.0)),
            ({"DYN_TPU_INTEGRITY_LOGIT_LIMIT": "1e12"},
             IntegrityPolicy(logit_limit=1e9)),
        ]
        for env, want in cases:
            for k in ("DYN_TPU_KV_INTEGRITY", "DYN_TPU_INTEGRITY_TRIPS",
                      "DYN_TPU_INTEGRITY_WINDOW",
                      "DYN_TPU_INTEGRITY_LOGIT_LIMIT"):
                monkeypatch.delenv(k, raising=False)
            for k, v in env.items():
                monkeypatch.setenv(k, v)
            assert IntegrityPolicy.from_env() == want, env
        monkeypatch.setenv("DYN_TPU_KV_INTEGRITY", "0")
        assert integrity.maybe_from_env() is None
        assert not integrity.enabled()


# -- checksums -----------------------------------------------------------------


class TestChecksums:
    def _pages(self, n=3):
        k = np.arange(2 * n * 4 * 2 * 3, dtype=np.float32).reshape(
            2, n, 4, 2, 3
        )
        return {"k": k, "v": k + 0.5}

    def test_page_and_entry_checksums_agree(self):
        pages = self._pages()
        crcs = kv_pages.checksums(pages)
        assert len(crcs) == 3
        for i in range(3):
            assert crcs[i] == integrity.checksum(
                [pages["k"][:, i], pages["v"][:, i]]
            )
        # scales change the checksum (they travel WITH their pages)
        ks = np.ones((2, 3, 4), np.float32)
        assert kv_pages.checksums(dict(pages, k_scale=ks, v_scale=ks)) != crcs

    def test_verify_pages_semantics(self):
        pages = self._pages()
        crcs = kv_pages.checksums(pages)
        kv_pages.verify(pages, crcs)  # clean: no raise
        kv_pages.verify(pages, None)  # checksum-less frame
        # -1 / None entries mean "sender can't vouch": skipped
        kv_pages.verify(pages, [-1, None, crcs[2]])
        bad = np.array(pages["k"])
        bad.view(np.uint8).reshape(-1)[7] ^= 0x10
        bad = dict(pages, k=bad)
        with pytest.raises(KvIntegrityError):
            kv_pages.verify(bad, crcs, where="unit")
        # the corrupted block is skippable ⇒ no raise
        kv_pages.verify(bad, [-1, crcs[1], crcs[2]])


# -- trip tracker + quarantine latch -------------------------------------------


class TestTracker:
    def test_threshold_within_window_latches(self):
        now = [0.0]
        t = IntegrityTracker(
            policy=IntegrityPolicy(trip_threshold=3, trip_window=10.0),
            clock=lambda: now[0],
        )
        assert not t.note_trip("kv", "a")
        now[0] = 2.0
        assert not t.note_trip("watchdog", "b")
        now[0] = 30.0  # first two trips aged out of the window
        assert not t.note_trip("kv", "c")
        now[0] = 31.0
        assert not t.note_trip("kv", "d")
        now[0] = 32.0
        assert t.note_trip("kv", "e")  # 3 within 10s ⇒ latched
        assert t.quarantined
        assert "integrity trips" in t.quarantine_reason
        c = t.counters()
        assert c["kv_integrity_failures_total"] == 4
        assert c["watchdog_trips_total"] == 1
        assert c["quarantined"] == 1

    def test_quarantine_sources_and_operator_clear(self):
        t = IntegrityTracker(policy=IntegrityPolicy(trip_threshold=1))
        t.quarantine("store", reason="operator")
        assert t.quarantined
        # syncing an absent store key clears only the store source
        t.clear_quarantine(source="store")
        assert not t.quarantined
        t.note_trip("kv")  # threshold 1 ⇒ latches the trips source
        assert t.quarantined
        t.clear_quarantine(source="store")  # store sync must NOT lift it
        assert t.quarantined
        t.clear_quarantine()  # operator unquarantine: full clear + reset
        assert not t.quarantined
        # the trip window was reset: one fresh trip latches again (threshold
        # 1) but the OLD trips are gone — counters remain cumulative
        assert t.note_trip("kv")
        assert t.counters()["kv_integrity_failures_total"] == 2

    def test_module_accessors_are_constructor_free(self):
        integrity.reset_for_tests()
        assert not integrity.quarantined()
        assert integrity.counters()["kv_integrity_failures_total"] == 0
        integrity.clear_quarantine()  # no-op, builds nothing
        assert integrity._TRACKER is None


# -- real tiny engines ---------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    import dataclasses

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models.llama import LLAMA_PRESETS, init_params

    cfg = dataclasses.replace(LLAMA_PRESETS["tiny"], dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(tiny, **kw):
    from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine

    cfg, params = tiny
    base = dict(max_slots=2, kv_block_size=8, max_model_len=256)
    base.update(kw)
    return JaxServingEngine(cfg, params, EngineConfig(**base))


def _call(engine, fn, timeout=60):
    fut = concurrent.futures.Future()

    def wrap():
        try:
            fut.set_result(fn())
        except Exception as e:  # delivered to the caller
            fut.set_exception(e)

    engine.post(wrap)
    return fut.result(timeout=timeout)


def _payload(toks, max_tokens, migrate=None):
    p = {
        "token_ids": list(toks),
        "stop_conditions": {"max_tokens": max_tokens, "ignore_eos": True},
        "sampling_options": {"temperature": 0.0},
    }
    if migrate is not None:
        p["migrate"] = migrate
    return p


async def _collect(engine, toks, max_tokens):
    out = []
    async for item in engine.generate(Context(_payload(toks, max_tokens))):
        if item.is_error:
            raise AssertionError(item.error_message())
        out.extend((item.data or {}).get("token_ids", []))
    return out


class TestZeroOverheadGuard:
    def test_integrity_off_constructs_and_computes_nothing(
        self, tiny, run, monkeypatch
    ):
        """DYN_TPU_KV_INTEGRITY=0 acceptance: no tracker is ever built, no
        checksum is ever computed, the watchdog variant is never compiled —
        serving is exactly pre-integrity."""
        monkeypatch.setenv("DYN_TPU_KV_INTEGRITY", "0")
        integrity.reset_for_tests()

        def _boom(*a, **kw):
            raise AssertionError("constructed/computed with integrity off")

        monkeypatch.setattr(integrity, "IntegrityTracker", _boom)
        monkeypatch.setattr(integrity, "checksum", _boom)
        # nor the seal-time checksum's worker: no thread, no queue
        from dynamo_tpu.engine_jax import engine as engine_mod

        monkeypatch.setattr(engine_mod, "SealCrcWorker", _boom)
        monkeypatch.setattr(kv_pages, "checksums_at", _boom)

        eng = _engine(tiny, host_cache_blocks=8)
        try:
            assert eng._integrity is None and not eng._watchdog
            assert eng.allocator._checksum is None and eng._crc_worker is None
            toks = run(_collect(eng, list(range(3, 27)), 8))
            assert len(toks) == 8
            assert eng.allocator._crc_of == {}
            assert eng.watchdog_trips == 0
        finally:
            eng.close()
        # transfer senders ship NO crcs header (pre-integrity wire form)
        from dynamo_tpu.disagg.transfer import _sender_crcs

        assert _sender_crcs(object(), [0], None) is None
        zeros = np.zeros((1, 1, 2, 1, 1), np.float32)
        hdr, _ = kv_pages.pack({"k": zeros, "v": zeros})
        assert "crcs" not in hdr

    def test_integrity_on_seals_checksums(self, tiny, run):
        eng = _engine(tiny)
        try:
            assert eng._integrity is not None and eng._watchdog
            assert eng.allocator._checksum is not None
            run(_collect(eng, list(range(3, 27)), 12))
            # 24 prompt + 12 generated = 36 tokens ⇒ 4 sealed 8-blocks
            assert len(eng.allocator._crc_of) >= 3
            bid, crc = next(iter(eng.allocator._crc_of.items()))
            assert eng.allocator.crc_of_block(bid) == crc
            # the registry crc matches a fresh recompute of the live bytes
            assert _call(
                eng, lambda: kv_pages.checksums(eng.extract_blocks([bid]))
            )[0] == crc
        finally:
            eng.close()


class TestHostTierRehit:
    def test_corrupt_host_entry_is_a_prefix_miss(self, tiny, run):
        """Bit-flipped host-pool bytes (bad host RAM): the rehit probe drops
        the entry, counts the trip, and the prompt recomputes byte-equal —
        corrupt KV never reaches the device pool."""
        integrity.reset_for_tests()
        eng = _engine(
            tiny, max_slots=2, kv_block_size=8, num_kv_blocks=12,
            host_cache_blocks=16, max_model_len=128,
        )
        try:
            prompt_a = [(3 * i + 1) % 97 for i in range(48)]
            prompt_b = [(5 * i + 2) % 97 for i in range(48)]
            t1 = run(_collect(eng, prompt_a, 4))
            run(_collect(eng, prompt_b, 4))  # evicts A's blocks → host tier
            assert eng.host_pool.offloaded > 0
            assert len(eng.host_pool) > 0
            # flip one byte in every host entry's k pages (the pool's copy)
            for h, (block, crc) in list(eng.host_pool._data.items()):
                bad = np.array(block["k"])
                bad.view(np.uint8).reshape(-1)[3] ^= 0x40
                eng.host_pool._data[h] = (dict(block, k=bad), crc)
            hits_before = eng.host_pool.hits
            t2 = run(_collect(eng, prompt_a, 4))
            assert t2 == t1, "recompute after the dropped hit must be exact"
            c = integrity.counters()
            assert c["kv_integrity_failures_total"] >= 1
            # the poisoned chain head was dropped at probe: at most one
            # paid "hit" (the probe that failed verification) — the rest of
            # the prompt recomputed instead of serving rotten bytes
            assert eng.host_pool.hits - hits_before <= 1
        finally:
            eng.close()
            integrity.reset_for_tests()

    def test_clean_host_rehit_still_verifies_and_hits(self, tiny, run):
        integrity.reset_for_tests()
        eng = _engine(
            tiny, max_slots=2, kv_block_size=8, num_kv_blocks=12,
            host_cache_blocks=16, max_model_len=128,
        )
        try:
            prompt_a = [(3 * i + 1) % 97 for i in range(48)]
            prompt_b = [(5 * i + 2) % 97 for i in range(48)]
            t1 = run(_collect(eng, prompt_a, 4))
            run(_collect(eng, prompt_b, 4))
            hits_before = eng.host_pool.hits
            t2 = run(_collect(eng, prompt_a, 4))
            assert t2 == t1
            assert eng.host_pool.hits > hits_before
            assert integrity.counters()["kv_integrity_failures_total"] == 0
        finally:
            eng.close()


class TestWatchdog:
    def test_poison_dispatch_trips_lane_in_band(self, tiny, run):
        """The ``poison`` fault action: one dispatch's logits become NaN
        in-jit; the watchdog sentinel kills the lane typed and in-band —
        tokens already delivered stay, NOTHING from the poisoned dispatch
        is emitted, and the stream ends with a resume directive."""
        integrity.reset_for_tests()
        eng = _engine(tiny)
        eng._fault_addr = "victim-e"
        inj = FaultInjector([FaultRule(
            plane="engine", point="dispatch", action="poison",
            match_addr="victim-e", after_ops=3, max_fires=1,
        )])
        try:
            with faults.active(inj):
                toks, marker = run(self._drive(eng, list(range(3, 19)), 32))
            assert marker is not None, "stream must end with the directive"
            assert marker.get("resume") is True
            assert "watchdog" in marker.get("error", "")
            assert all(t >= 0 for t in toks), f"garbage escaped: {toks}"
            assert len(toks) < 32, "the lane must die before its budget"
            assert eng.watchdog_trips == 1
            c = integrity.counters()
            assert c["watchdog_trips_total"] == 1
            # delivered prefix is byte-equal to an undisturbed control
            control = run(_collect(eng, list(range(3, 19)), 32))
            assert toks == control[: len(toks)]
        finally:
            eng.close()
            integrity.reset_for_tests()

    @staticmethod
    async def _drive(eng, prompt, max_tokens):
        toks, marker = [], None
        async for item in eng.generate(Context(_payload(prompt, max_tokens))):
            assert not item.is_error, item.error_message()
            d = item.data or {}
            if "migrating" in d:
                marker = d["migrating"]
                continue
            toks.extend(d.get("token_ids", []))
        return toks, marker

    def test_healthy_streams_unaffected_by_watchdog(self, tiny, run):
        """With the watchdog compiled in but nothing poisoned, greedy
        output is exactly the engine's ordinary output (the sentinel path
        is a no-op on finite logits)."""
        eng = _engine(tiny)
        try:
            a = run(_collect(eng, list(range(5, 21)), 16))
            b = run(_collect(eng, list(range(5, 21)), 16))
            assert a == b and len(a) == 16
            assert eng.watchdog_trips == 0
        finally:
            eng.close()


async def _freeze_mid_stream(engine, prompt, max_tokens, k):
    ctx = Context(_payload(prompt, max_tokens))
    gen = engine.generate(ctx)
    got = []
    with engines_held_back():
        async for item in gen:
            got.extend((item.data or {}).get("token_ids", []))
            if len(got) >= k:
                break
        cps = _call(engine, engine.export_migratable)
    assert len(cps) == 1
    return cps[0], got, gen


class TestMigrationStagingIntegrity:
    def test_corrupt_pages_nack_typed_and_atomic(self, tiny, run):
        """A migrate page set that fails its checksums raises typed BEFORE
        any pool state changes on the target: no torn staged entry, no
        leaked blocks — and clean pages still stage fine afterwards."""
        integrity.reset_for_tests()
        src = _engine(tiny)
        dst = _engine(tiny)
        try:
            async def go():
                cp, got, gen = await _freeze_mid_stream(
                    src, list(range(4, 28)), 24, 4
                )
                pages, crcs = _call(
                    src, lambda: src.extract_for_migration(cp["request_id"])
                )
                assert crcs is not None and len(crcs) == cp["n_blocks"]
                meta = {
                    "mid": cp["mid"], "token_ids": cp["token_ids"],
                    "emitted": cp["emitted"], "tenant": "", "level": 0,
                    "crcs": crcs,
                }
                bad = np.array(pages["k"])
                bad.view(np.uint8).reshape(-1)[11] ^= 0x01
                bad = dict(pages, k=bad)
                free_before = dst.allocator.free_blocks
                with pytest.raises(KvIntegrityError):
                    _call(dst, lambda: dst.stage_migration(meta, bad))
                assert dst.allocator.free_blocks == free_before
                assert dst._staged_migrations == {}
                # clean pages stage fine — the failure was the bytes
                res = _call(dst, lambda: dst.stage_migration(meta, pages))
                assert res["mid"] == cp["mid"]
                _call(src, lambda: src.abort_migration(cp["request_id"]))
                async for _ in gen:
                    pass

            run(go())
        finally:
            src.close()
            dst.close()
            integrity.reset_for_tests()


# -- transfer plane ------------------------------------------------------------


class _PageEngine:
    """Minimal engine for KvTransferServer: serves fixed pages."""

    def __init__(self, n=2, corrupt_after_seal=False):
        self.k = np.arange(2 * n * 4 * 2 * 3, dtype=np.float32).reshape(
            2, n, 4, 2, 3
        )
        self.v = self.k + 1.0
        self.pages = {"k": self.k, "v": self.v}
        self._crcs = kv_pages.checksums(self.pages)
        if corrupt_after_seal:
            # storage rot AFTER seal: registry crcs describe the clean
            # bytes, the pool holds flipped ones
            self.k.view(np.uint8).reshape(-1)[5] ^= 0x01
        self.completed = []
        self.failed = []

    def post(self, fn):
        fn()

    def extract_blocks(self, ids, as_device=False):
        return kv_pages.select(self.pages, list(ids))

    def block_hashes_of(self, ids):
        return [100 + i for i in ids]

    def block_crcs_of(self, ids):
        return [self._crcs[i] for i in ids]

    def complete_remote_prefill(self, rid, first, bids, pages):
        self.completed.append((rid, first, list(bids)))

    def fail_remote_prefill(self, rid, msg):
        self.failed.append((rid, msg))


class TestTransferIntegrity:
    def test_read_blocks_detects_storage_rot(self, run):
        """A worker whose pool rotted after seal serves pages whose
        registry checksums no longer match: the READER detects it and
        recomputes instead of seeding corrupt KV."""
        from dynamo_tpu.disagg.transfer import KvTransferClient, KvTransferServer

        async def go():
            integrity.reset_for_tests()
            eng = _PageEngine(corrupt_after_seal=True)
            srv = KvTransferServer(eng, host="127.0.0.1", port=0)
            await srv.start()
            client = KvTransferClient()
            with pytest.raises(KvIntegrityError):
                await client.read_blocks(f"127.0.0.1:{srv.port}", [0, 1])
            c = integrity.counters()
            assert c["kv_integrity_remote_failures_total"] == 1
            # remote rot is NOT a self-trip: blame stays with the owner
            assert c["kv_integrity_failures_total"] == 0
            await client.close()
            await srv.stop()

        run(go())

    def test_read_blocks_clean_round_trip_ships_crcs(self, run):
        from dynamo_tpu.disagg.transfer import KvTransferClient, KvTransferServer

        async def go():
            integrity.reset_for_tests()
            eng = _PageEngine()
            srv = KvTransferServer(eng, host="127.0.0.1", port=0)
            await srv.start()
            client = KvTransferClient()
            pages, hashes = await client.read_blocks(
                f"127.0.0.1:{srv.port}", [0, 1]
            )
            assert np.array_equal(pages["k"], eng.k)
            assert hashes == [100, 101]
            assert integrity.counters()["kv_integrity_remote_failures_total"] == 0
            await client.close()
            await srv.stop()

        run(go())

    def test_kv_blocks_wire_corruption_nacks_sender(self, run):
        """The ``corrupt`` fault action flips a byte of a kv_blocks frame
        post-checksum: the receiver rejects it typed (local-prefill
        fallback, nothing injected) and the SENDER counts the trip —
        exactly the quarantine plane's signal."""
        from dynamo_tpu.disagg.transfer import KvTransferClient, KvTransferServer

        async def go():
            integrity.reset_for_tests()
            eng = _PageEngine()
            srv = KvTransferServer(eng, host="127.0.0.1", port=0)
            await srv.start()
            client = KvTransferClient()
            client.fault_addr = "rotten-sender"
            inj = FaultInjector([FaultRule(
                plane="transfer", point="pages", action="corrupt",
                match_addr="rotten-sender",
            )])
            with faults.active(inj):
                with pytest.raises(KvIntegrityError):
                    await client.send_blocks(
                        f"127.0.0.1:{srv.port}", "r1", 7, [0, 1], eng.pages
                    )
            assert eng.completed == []
            assert eng.failed and eng.failed[0][0] == "r1"
            c = integrity.counters()
            assert c["kv_integrity_failures_total"] == 1  # the sender's
            assert c["kv_integrity_remote_failures_total"] == 1  # receiver's
            # without the injector the same transfer completes
            await client.send_blocks(
                f"127.0.0.1:{srv.port}", "r2", 7, [0, 1], eng.pages
            )
            assert eng.completed and eng.completed[0][0] == "r2"
            await client.close()
            await srv.stop()

        run(go())


# -- quarantine plane ----------------------------------------------------------


class TestQuarantinePlane:
    def test_health_monitor_latches_and_releases(self):
        from dynamo_tpu.runtime.health import (
            HEALTHY,
            QUARANTINED,
            HealthMonitor,
            HealthPolicy,
        )

        integrity.reset_for_tests()
        calls = []
        mon = HealthMonitor(
            policy=HealthPolicy(recovery_checks=2),
            set_draining=lambda flag, source: calls.append((flag, source)),
        )
        assert mon.check() == HEALTHY
        integrity.tracker().quarantine("store", reason="unit")
        assert mon.check() == QUARANTINED
        assert (True, "quarantine") in calls
        # sticky: passing checks do NOT recover a quarantined worker
        assert mon.check() == QUARANTINED
        assert mon.check() == QUARANTINED
        # operator clears the latch ⇒ immediate recovery, own source undone
        integrity.clear_quarantine()
        assert mon.check() == HEALTHY
        assert (False, "quarantine") in calls
        integrity.reset_for_tests()

    def test_trip_threshold_drives_monitor(self):
        from dynamo_tpu.runtime.health import QUARANTINED, HealthMonitor

        integrity.reset_for_tests()
        mon = HealthMonitor(set_draining=lambda *a, **kw: None)
        t = IntegrityTracker(policy=IntegrityPolicy(trip_threshold=2))
        integrity._TRACKER = t
        t.note_trip("kv")
        assert mon.check() != QUARANTINED
        t.note_trip("watchdog")
        assert mon.check() == QUARANTINED
        integrity.reset_for_tests()

    def test_endpoint_client_excludes_quarantined(self):
        from dynamo_tpu.runtime.admission import LoadSnapshot
        from dynamo_tpu.runtime.distributed import EndpointClient, InstanceInfo

        c = EndpointClient.__new__(EndpointClient)
        c._instances = {
            "i1": InstanceInfo("i1", "h:1", "w1", health="quarantined"),
            "i2": InstanceInfo("i2", "h:2", "w2", health="healthy"),
        }
        c._loads = {}
        assert c._is_unhealthy("i1")
        assert not c._is_unhealthy("i2")
        # piggybacked load snapshots carry it too
        c._loads["i2"] = LoadSnapshot.from_wire(
            LoadSnapshot(health="quarantined").to_wire()
        )
        assert c._is_unhealthy("i2")

    def test_llmctl_quarantine_round_trip(self, run, monkeypatch, capsys):
        """llmctl worker quarantine/unquarantine over a real statestore:
        the control key latches the worker (health → quarantined on the
        instance key, exit 0 with --wait), unquarantine recovers it, and
        --wait exits 2 when the latch can't land in time."""
        from .token_engine import TokenEngine

        from dynamo_tpu.cli import llmctl

        monkeypatch.setenv("DYN_TPU_LOAD_REPORT_INTERVAL", "0.1")
        monkeypatch.setenv("DYN_TPU_HEALTH_CHECK_INTERVAL", "0.1")
        integrity.reset_for_tests()

        async def go():
            ss = StateStoreServer(port=0)
            await ss.start()
            rt = await DistributedRuntime.create(ss.url, NO_BUS)
            ep = rt.namespace("q").component("w").endpoint("gen")
            await ep.serve(TokenEngine("w0", delay=0.01))
            capsys.readouterr()
            rc = await llmctl.amain([
                "--statestore", ss.url, "worker", "quarantine",
                "dyn://q.w.gen", rt.worker_id,
                "--wait", "--timeout", "15", "--json",
            ])
            out = capsys.readouterr().out
            assert rc == 0, out
            env = json.loads(out)
            assert env["quarantined"] is True
            assert all(
                r["health"] == "quarantined" for r in env["instances"]
            )
            assert rt._health_monitor.state == "quarantined"
            assert rt.draining  # quarantine self-drains (stops admitting)

            rc = await llmctl.amain([
                "--statestore", ss.url, "worker", "unquarantine",
                "dyn://q.w.gen", rt.worker_id,
            ])
            assert rc == 0
            deadline = asyncio.get_running_loop().time() + 10.0
            while (rt._health_monitor.state != "healthy"
                   and asyncio.get_running_loop().time() < deadline):
                await asyncio.sleep(0.05)
            assert rt._health_monitor.state == "healthy"
            assert not rt.draining

            # exit-2 leg: with the health plane stopped the latch can never
            # publish — --wait must time out, machine-parseably
            capsys.readouterr()  # drop the unquarantine confirmation line
            await rt._health_monitor.stop()
            rc = await llmctl.amain([
                "--statestore", ss.url, "worker", "quarantine",
                "dyn://q.w.gen", rt.worker_id,
                "--wait", "--timeout", "0.6", "--json",
            ])
            out = capsys.readouterr().out
            assert rc == 2, out
            assert json.loads(out)["quarantined"] is False

            await rt.shutdown()
            await ss.stop()

        run(go())
        integrity.reset_for_tests()


# -- gauges through the metrics planes -----------------------------------------


class TestIntegrityGauges:
    def test_forward_pass_metrics_round_trip(self):
        from dynamo_tpu.kv_router.protocols import ForwardPassMetrics

        m = ForwardPassMetrics(
            kv_integrity_failures_total=3, watchdog_trips_total=2,
            health_state="quarantined",
        )
        back = ForwardPassMetrics.from_dict(m.to_dict())
        assert back.kv_integrity_failures_total == 3
        assert back.watchdog_trips_total == 2
        assert back.health_state == "quarantined"
        # pre-integrity wire dicts still parse (fields default 0)
        old = {
            k: v for k, v in m.to_dict().items()
            if "integrity" not in k and "watchdog" not in k
        }
        assert ForwardPassMetrics.from_dict(old).watchdog_trips_total == 0

    def test_worker_and_cluster_gauges_render(self):
        from dynamo_tpu.components.metrics import MetricsAggregator
        from dynamo_tpu.components.mock_worker import MockWorkerStats
        from dynamo_tpu.components.telemetry_aggregator import ClusterTelemetry

        from .promtext import parse_prometheus_text

        stats = MockWorkerStats(
            seed=1, integrity_failures=4, watchdog_trips=2,
            health_state="quarantined",
        )
        stats.tick(requests=3)
        m = stats.metrics("m1")
        assert m.kv_integrity_failures_total == 4
        assert m.health_state == "quarantined"

        agg = MetricsAggregator("ns1")
        agg.update("w0", m)
        text = agg.render()
        parsed = parse_prometheus_text(text)
        assert "dynamo_worker_kv_integrity_failures_total" in parsed
        assert "dynamo_worker_watchdog_trips_total" in parsed
        # quarantined renders as health_state 3 (graver than unhealthy)
        assert 'dynamo_worker_health_state{namespace="ns1",worker="w0"} 3' \
            in text

        ct = ClusterTelemetry("ns1", clock=lambda: 100.0)
        ct.ingest("w0", m)
        ct.ingest("w1", MockWorkerStats(seed=2, watchdog_trips=1).metrics("m1"))
        roll = ct.rollup()
        e = roll["models"]["m1"]
        assert e["kv_integrity_failures_total"] == 4
        assert e["watchdog_trips_total"] == 3
        assert e["workers_quarantined"] == 1
        assert e["quarantined_worker_ids"] == ["w0"]
        cparsed = parse_prometheus_text(ct.render_prometheus())
        assert "dynamo_cluster_kv_integrity_failures_total" in cparsed
        assert "dynamo_cluster_watchdog_trips_total" in cparsed
        assert "dynamo_cluster_workers_quarantined" in cparsed

    def test_planner_drains_quarantined_immediately(self):
        from dynamo_tpu.components.planner import DRAIN, Planner, PlannerPolicy

        p = Planner(PlannerPolicy(drain_after=120.0), clock=lambda: 100.0)
        rollup = {
            "models": {
                "m1": {
                    "workers": 3, "slots_total": 6, "slots_free": 3,
                    "kv_blocks_total": 100, "kv_blocks_free": 50,
                    "queue_depth": 0,
                    "quarantined_worker_ids": ["w-bad"],
                    "draining_workers": {},
                },
            },
        }
        decisions = p.evaluate(rollup, {})
        drains = [d for d in decisions if d.kind == DRAIN]
        assert len(drains) == 1
        assert drains[0].worker_id == "w-bad"
        assert "quarantined" in drains[0].reason
        # and it NEVER undrains: the worker keeps reporting quarantined
        p2 = rollup["models"]["m1"]
        p2["draining_workers"] = {"w-bad": "quarantined"}
        for t in (200.0, 500.0, 5000.0):
            p._clock = lambda t=t: t
            assert not [
                d for d in p.evaluate(rollup, {}) if d.kind == "undrain"
            ]

    def test_publish_loop_carries_integrity_counters(self, run):
        from dynamo_tpu.runtime.bus import MessageBusServer
        from dynamo_tpu.runtime.distributed import attach_kv_publishing

        class SnapEngine:
            def metrics_snapshot(self):
                return {"request_active_slots": 0, "request_total_slots": 1}

        class _Echo(AsyncEngine):
            async def generate(self, request: Context):
                yield Annotated.from_data({"ok": True})

        async def go():
            integrity.reset_for_tests()
            integrity.note_trip("kv", "t1")
            integrity.note_trip("watchdog", "t2")
            ss = StateStoreServer(port=0)
            await ss.start()
            bus = MessageBusServer(port=0)
            await bus.start()
            rt = await DistributedRuntime.create(ss.url, bus.url)
            ns = rt.namespace("ig")
            got = asyncio.Event()
            seen = {}

            async def consume():
                sub = await ns.subscribe("kv_metrics")
                async for raw in sub:
                    seen.update(json.loads(raw))
                    got.set()
                    return

            task = asyncio.create_task(consume())
            await asyncio.sleep(0.1)
            ep = ns.component("w").endpoint("gen")
            await ep.serve(_Echo())
            await attach_kv_publishing(ep, SnapEngine(), interval=0.05)
            await asyncio.wait_for(got.wait(), 5)
            task.cancel()
            m = seen["metrics"]
            assert m["kv_integrity_failures_total"] == 1
            assert m["watchdog_trips_total"] == 1
            await rt.shutdown()
            await bus.stop()
            await ss.stop()
            integrity.reset_for_tests()

        run(go())


# -- THE chaos gate ------------------------------------------------------------


def _policy(**kw) -> ResiliencePolicy:
    base = dict(
        request_timeout=120.0,
        connect_timeout=2.0,
        max_attempts=4,
        backoff_base=0.01,
        backoff_max=0.05,
        breaker_threshold=2,
        breaker_cooldown=30.0,
        resume_attempts=2,
        seed=7,
    )
    base.update(kw)
    return ResiliencePolicy(**base)


async def _cluster(tiny, n=3, policy=None, **ekw):
    ss = StateStoreServer(port=0)
    await ss.start()
    rts, engines, coords = [], [], []
    for _ in range(n):
        rt = await DistributedRuntime.create(ss.url, NO_BUS)
        eng = _engine(tiny, **ekw)
        ep = rt.namespace("sdc").component("w").endpoint("gen")
        await ep.serve(eng)
        coords.append(await attach_migration(ep, eng))
        rts.append(rt)
        engines.append(eng)
    fe = await DistributedRuntime.create(ss.url, NO_BUS)
    client = await fe.namespace("sdc").component("w").endpoint("gen").client(
        "round_robin", policy=policy or _policy()
    )
    await client.wait_for_instances(n, timeout=10)
    return ss, rts, engines, coords, fe, client


async def _teardown(ss, rts, engines, fe, client):
    await client.close()
    for rt in rts + [fe]:
        await rt.shutdown()
    for eng in engines:
        eng.close()
    await ss.stop()


async def _stream(client, prompt, max_tokens):
    ctx = Context(_payload(prompt, max_tokens))
    toks, errs = [], []
    async for item in client.generate(ctx):
        if item.is_error:
            errs.append(item.error_message())
        elif isinstance(item.data, dict):
            toks.extend(item.data.get("token_ids", []))
    return toks, errs, ctx


async def _goldens(tiny, prompts, max_tokens):
    eng = _engine(tiny, max_slots=4)
    out = []
    for p in prompts:
        out.append(await _collect(eng, p, max_tokens))
    eng.close()
    return out


class TestIntegrityChaosGate:
    def test_corrupt_worker_quarantined_drain_migrates_nothing(
        self, tiny, run, monkeypatch
    ):
        """ISSUE 14 acceptance: one worker emitting corrupt pages under 2x
        load. Its drain-time migrations all nack typed at the receivers
        (zero corrupt bytes ever staged or served — every stream byte-equal
        to its undisturbed control via the resume path), the victim
        quarantines within the trip threshold, its drain migrates NOTHING,
        the client excludes it — and once the latch is cleared, a healthy
        worker's drain still migrates."""
        monkeypatch.setenv("DYN_TPU_LOAD_REPORT_INTERVAL", "0.1")
        monkeypatch.setenv("DYN_TPU_HEALTH_CHECK_INTERVAL", "0.1")
        # threshold 2: the victim must quarantine off its first drain wave
        # even when warm jit caches let streams finish quickly
        monkeypatch.setenv("DYN_TPU_INTEGRITY_TRIPS", "2")

        async def go():
            integrity.reset_for_tests()
            mig_mod.reset_migration_counters()
            resilience.reset_resume_counters()
            ss, rts, engines, coords, fe, client = await _cluster(
                tiny, n=3, max_slots=2,
            )
            victim = 0
            # one process hosts the whole test fleet, but quarantine is a
            # process-global latch (one worker per process in production):
            # stop the SIBLINGS' monitors so only the victim's health plane
            # reacts to the victim's trips
            for i in range(3):
                if i != victim:
                    await rts[i]._health_monitor.stop()

            n_requests, max_t = 12, 128
            prompts = [[17 + i, 23 + 2 * i, 5 + 3 * i] for i in
                       range(n_requests)]
            controls = await _goldens(tiny, prompts, max_t)

            # the victim's OUTBOUND page sets rot post-checksum (its own
            # transfer-address label, set by attach_migration)
            inj = FaultInjector([FaultRule(
                plane="transfer", point="pages", action="corrupt",
                match_addr=coords[victim].address,
            )])
            results = [None] * n_requests

            async def one(i):
                results[i] = await _stream(client, prompts[i], max_t)

            with faults.active(inj):
                tasks = [
                    asyncio.create_task(one(i)) for i in range(n_requests)
                ]
                while sum(e.live_request_count() for e in engines) < 6:
                    await asyncio.sleep(0.02)
                await asyncio.sleep(0.05)
                # rolling-restart the rotten worker: the drain tries to
                # migrate, every frame nacks, trips accumulate
                rts[victim].set_draining(True)
                deadline = asyncio.get_running_loop().time() + 30.0
                while engines[victim].live_request_count():
                    if asyncio.get_running_loop().time() > deadline:
                        raise AssertionError("victim never finished draining")
                    await asyncio.sleep(0.05)
                await asyncio.wait_for(asyncio.gather(*tasks), 120)

                # quarantined within the trip threshold: the monitor latched
                deadline = asyncio.get_running_loop().time() + 10.0
                while (rts[victim]._health_monitor.state != "quarantined"
                       and asyncio.get_running_loop().time() < deadline):
                    await asyncio.sleep(0.05)
                assert rts[victim]._health_monitor.state == "quarantined"
                assert integrity.quarantined()

            failures = [
                (i, errs) for i, (toks, errs, _) in enumerate(results)
                if errs
            ]
            assert failures == [], f"client-visible failures: {failures}"
            for i, (toks, errs, _) in enumerate(results):
                assert toks == controls[i], (
                    f"stream {i} diverged — corrupt bytes reached a client "
                    f"(got {len(toks)}/{len(controls[i])} tokens)"
                )
            # zero successful migrations from the victim: its pages never
            # entered a sibling's cache, no torn staged entries anywhere
            m_ok, m_bad, m_blocks = mig_mod.migration_counters()
            assert m_ok == 0 and m_blocks == 0, (
                f"corrupt pages were staged: migrations={m_ok}"
            )
            assert m_bad >= 2
            assert coords[victim].last_drain.get("migrated") == 0
            for i in range(3):
                if i != victim:
                    snap = engines[i].metrics_snapshot()
                    assert snap["migrate_staged"] == 0
                    assert snap["migrated_in_requests"] == 0
            c = integrity.counters()
            assert c["kv_integrity_failures_total"] >= 2
            assert c["quarantined"] == 1
            # the client excludes the quarantined instance
            vids = [
                iid for iid, info in client._instances.items()
                if info.worker_id == rts[victim].worker_id
            ]
            deadline = asyncio.get_running_loop().time() + 10.0
            while (vids and not all(client._is_unhealthy(i) for i in vids)
                   and asyncio.get_running_loop().time() < deadline):
                await asyncio.sleep(0.05)
            assert all(client._is_unhealthy(i) for i in vids)

            # -- control: healthy drains still migrate -------------------
            integrity.reset_for_tests()  # operator replaced the host
            ctl_tasks = [
                asyncio.create_task(
                    _stream(client, [41 + 3 * j, 43 + j, 47], 200)
                )
                for j in range(4)
            ]
            healthy = None
            deadline = asyncio.get_running_loop().time() + 15.0
            while (healthy is None
                   and asyncio.get_running_loop().time() < deadline):
                for i in (1, 2):
                    # drain the sibling with a MID-DECODE stream (≥1 token
                    # emitted: that's what export_migratable freezes)
                    if any(
                        s is not None and s.generated
                        for s in engines[i]._slots
                    ):
                        healthy = i
                        break
                await asyncio.sleep(0.01)
            assert healthy is not None, "control streams never landed"
            rts[healthy].set_draining(True)
            ctl = await asyncio.wait_for(asyncio.gather(*ctl_tasks), 120)
            assert all(errs == [] for _, errs, _ in ctl)
            m_ok2, _, m_blocks2 = mig_mod.migration_counters()
            assert m_ok2 >= 1 and m_blocks2 > 0, (
                "healthy-worker drains must still migrate"
            )
            await _teardown(ss, rts, engines, fe, client)

        run(go())
        integrity.reset_for_tests()
