"""Control-plane blackout tolerance (ISSUE 12): the data plane keeps
serving when the statestore and bus die.

Covers the ControlPlanePolicy knob clamping, the process-global
connectivity tracker and its exposition, deterministic rejoin jitter
(recovery-storm spread), the disk discovery cache (atomic writes, corrupt
files, cold starts, the zero-overhead guard), stale-but-safe discovery in
EndpointClient and ModelWatcher (hold on outage / restart-empty, purge
rules under probe authority), bounded bus-outage buffering with stamped
backfill, the typed ControlPlaneUnavailable cold-start failure, the
`blackout` fault action, `llmctl control-plane status` exit codes — and
the chaos gate: statestore AND bus killed mid-run under 2x load and
restarted EMPTY → zero client-visible failures, streams byte-equal to
control, full reconvergence (fresh leases, missed drain keys applied,
telemetry flowing).
"""

import asyncio
import itertools
import json
import time

import pytest

from dynamo_tpu.runtime import control_plane, faults
from dynamo_tpu.runtime.annotated import Annotated
from dynamo_tpu.runtime.bus import MessageBusServer
from dynamo_tpu.runtime.control_plane import (
    BoundedPublishBuffer,
    ControlPlanePolicy,
    ControlPlaneState,
    ControlPlaneUnavailable,
    DiscoveryCache,
    maybe_cache,
    rejoin_delay,
)
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.engine import AsyncEngine, Context
from dynamo_tpu.runtime.faults import FaultInjector, FaultRule
from dynamo_tpu.runtime.resilience import ResiliencePolicy
from dynamo_tpu.runtime.statestore import StateStoreClient, StateStoreServer

from tests.token_engine import TokenEngine, expected_stream
from tests.token_engine import payload as _payload

NO_BUS = "127.0.0.1:1"


def _clear_cp_env(monkeypatch):
    for k in (
        "DYN_TPU_STALE_SERVE", "DYN_TPU_STALE_GRACE",
        "DYN_TPU_REJOIN_JITTER", "DYN_TPU_COLD_START_DEADLINE",
        "DYN_TPU_BUS_BUFFER", "DYN_TPU_DISCOVERY_CACHE",
    ):
        monkeypatch.delenv(k, raising=False)


def _policy(**kw) -> ResiliencePolicy:
    base = dict(
        request_timeout=30.0, connect_timeout=1.0, max_attempts=4,
        backoff_base=0.01, backoff_max=0.05, breaker_threshold=3,
        breaker_cooldown=30.0, seed=7,
    )
    base.update(kw)
    return ResiliencePolicy(**base)


# -- knobs ---------------------------------------------------------------------


class TestPolicyKnobs:
    def test_defaults(self, monkeypatch):
        _clear_cp_env(monkeypatch)
        p = ControlPlanePolicy.from_env()
        assert p.stale_serve is True
        assert p.stale_grace == 20.0
        assert p.rejoin_jitter == 5.0
        assert p.cold_start_deadline == 5.0
        assert p.bus_buffer == 256
        assert p.cache_dir == ""

    def test_from_env(self, monkeypatch):
        _clear_cp_env(monkeypatch)
        monkeypatch.setenv("DYN_TPU_STALE_SERVE", "0")
        monkeypatch.setenv("DYN_TPU_STALE_GRACE", "3.5")
        monkeypatch.setenv("DYN_TPU_REJOIN_JITTER", "0")
        monkeypatch.setenv("DYN_TPU_COLD_START_DEADLINE", "1.5")
        monkeypatch.setenv("DYN_TPU_BUS_BUFFER", "12")
        monkeypatch.setenv("DYN_TPU_DISCOVERY_CACHE", "/tmp/x")
        p = ControlPlanePolicy.from_env()
        assert p.stale_serve is False
        assert p.stale_grace == 3.5
        assert p.rejoin_jitter == 0.0  # 0 is a policy: jitter off
        assert p.cold_start_deadline == 1.5
        assert p.bus_buffer == 12
        assert p.cache_dir == "/tmp/x"

    @pytest.mark.parametrize("name,bad", [
        ("DYN_TPU_STALE_GRACE", "abc"),
        ("DYN_TPU_STALE_GRACE", "0"),
        ("DYN_TPU_STALE_GRACE", "-2"),
        ("DYN_TPU_REJOIN_JITTER", "nope"),
        ("DYN_TPU_REJOIN_JITTER", "-1"),
        ("DYN_TPU_COLD_START_DEADLINE", "-3"),
        ("DYN_TPU_BUS_BUFFER", "x"),
        ("DYN_TPU_BUS_BUFFER", "-5"),
    ])
    def test_malformed_values_clamp(self, monkeypatch, name, bad):
        _clear_cp_env(monkeypatch)
        monkeypatch.setenv(name, bad)
        p, d = ControlPlanePolicy.from_env(), ControlPlanePolicy()
        assert p == d or getattr(p, name.split("DYN_TPU_")[1].lower(), None) \
            == getattr(d, name.split("DYN_TPU_")[1].lower(), None)


# -- the process-global tracker ------------------------------------------------


class TestControlPlaneState:
    def test_transitions_and_worst(self):
        st = ControlPlaneState()
        assert st.worst() == "connected"
        st.note_plane("statestore", False)
        assert st.plane_state("statestore") == "disconnected"
        assert st.worst() == "disconnected"
        st.note_plane("statestore", True)
        assert st.worst() == "connected"
        snap = st.snapshot()
        assert snap["planes"]["statestore"]["outages"] == 1
        assert st.seconds_since_disconnect("statestore") < 5.0
        assert st.seconds_since_disconnect("bus") == float("inf")

    def test_stale_entries_make_store_plane_stale(self):
        st = ControlPlaneState()
        st.note_stale_entries("client-a", 3)
        assert st.plane_state("statestore") == "stale"
        assert st.snapshot()["stale_discovery_entries"] == 3
        st.note_stale_entries("client-a", 0)
        assert st.plane_state("statestore") == "connected"
        st.note_stale_entries("client-b", 1)
        st.forget_consumer("client-b")
        assert st.plane_state("statestore") == "connected"

    def test_buffered_events_make_bus_plane_stale(self):
        st = ControlPlaneState()
        st.note_buffer("pub-a", 5, 2)
        assert st.plane_state("bus") == "stale"
        snap = st.snapshot()
        assert snap["bus_buffered_events"] == 5
        assert snap["bus_dropped_events"] == 2
        st.note_buffer("pub-a", 0, 1)
        assert st.plane_state("bus") == "connected"
        assert st.snapshot()["bus_dropped_events"] == 3  # drops accumulate

    def test_render_prometheus_parses(self):
        from tests.promtext import parse_prometheus_text

        control_plane.reset_for_tests()
        control_plane.note_bus(False)
        fams = parse_prometheus_text(control_plane.render_prometheus())
        cp = fams["dynamo_control_plane_state"]
        by_plane = {labels["plane"]: value for _, labels, value in cp["samples"]}
        assert by_plane["bus"] == 2 and by_plane["statestore"] == 0
        assert "dynamo_control_plane_dropped_events" in fams


# -- rejoin jitter -------------------------------------------------------------


class TestRejoinDelay:
    def test_deterministic_and_bounded(self):
        a = rejoin_delay("worker-1", 10.0)
        assert a == rejoin_delay("worker-1", 10.0)
        assert 0.0 <= a < 10.0
        assert rejoin_delay("worker-1", 0.0) == 0.0
        assert rejoin_delay("worker-1", 10.0, seed=1) != a

    def test_recovery_storm_spread(self):
        """Satellite: N workers re-registering after a blackout land with
        seeded-jitter dispersion — no two in the same jitter slot
        (deterministic: the hash is stable, so this documents the actual
        spread for a 100-worker fleet at 2 ms slot granularity)."""
        n, window = 100, 10.0
        ids = [f"worker-{i:03d}" for i in range(n)]
        delays = [rejoin_delay(w, window) for w in ids]
        slots = [int(d / window * 5000) for d in delays]  # 2 ms slots
        assert len(set(slots)) == n, "two workers share a jitter slot"
        # and the spread actually uses the window, not one corner of it
        assert max(delays) - min(delays) > window / 2
        sep = min(abs(a - b) for a, b in itertools.combinations(delays, 2))
        assert sep > 0.002, f"closest rejoins only {sep * 1e3:.2f}ms apart"


# -- disk discovery cache ------------------------------------------------------


class TestDiscoveryCache:
    def test_save_load_roundtrip(self, tmp_path):
        c = DiscoveryCache(str(tmp_path))
        entries = {"ns/x/instances/a": b"\x00binary", "ns/x/instances/b": b"{}"}
        c.save("ns/x/instances/", entries)
        assert c.load("ns/x/instances/") == entries
        assert c.saved_at("ns/x/instances/") is not None
        assert c.has_any()
        assert c.load("ns/other/") is None

    def test_corrupt_file_reads_as_no_cache(self, tmp_path):
        c = DiscoveryCache(str(tmp_path))
        c.save("p/", {"k": b"v"})
        with open(c._path("p/"), "w") as f:
            f.write("{not json")
        assert c.load("p/") is None

    def test_maybe_cache_gated_on_env(self, monkeypatch, tmp_path):
        _clear_cp_env(monkeypatch)
        assert maybe_cache() is None
        monkeypatch.setenv("DYN_TPU_DISCOVERY_CACHE", str(tmp_path))
        c = maybe_cache()
        assert c is not None and c.root == str(tmp_path)


# -- bounded publish buffer ----------------------------------------------------


class TestBoundedPublishBuffer:
    def test_drop_oldest_and_counter(self):
        b = BoundedPublishBuffer(3)
        for i in range(5):
            b.push(i)
        assert b.dropped == 2
        drained = [p for _, p in b.drain()]
        assert drained == [2, 3, 4]
        assert len(b) == 0

    def test_drain_ages_are_nonnegative(self):
        b = BoundedPublishBuffer(4)
        b.push("x")
        ages = [age for age, _ in b.drain()]
        assert len(ages) == 1 and ages[0] >= 0.0

    def test_repush_keeps_true_age(self):
        """A re-buffered item (failed flush) keeps its original age — the
        staleness stamp must not restart at every flush attempt."""
        b = BoundedPublishBuffer(4)
        b.push("x", age_s=60.0)
        age, _ = b.drain()[0]
        assert age >= 60.0


# -- the blackout fault --------------------------------------------------------


class TestBlackoutFault:
    def test_begin_end_installs_and_removes_rules(self):
        inj = FaultInjector()
        inj.begin_blackout(("statestore",))
        assert inj.blackout_active("statestore")
        assert not inj.blackout_active("bus")
        assert inj.decide("statestore", "h:1", "connect", 0) is not None
        inj.begin_blackout(("statestore",))  # idempotent
        n_rules = len(inj.rules)
        inj.begin_blackout(("statestore",))
        assert len(inj.rules) == n_rules
        inj.end_blackout()
        assert not inj.blackout_active("statestore")
        assert inj.decide("statestore", "h:1", "connect", 1) is None

    def test_spec_parses_blackout_action(self):
        inj = faults.injector_from_spec(
            '[{"plane": "statestore", "action": "blackout", "delay": 30}]'
        )
        assert inj.rules[0].action == "blackout"

    def test_timed_env_blackout_fires_once_then_lifts(self, run):
        """The documented one-shot drill: the trigger rule is SPENT at
        first firing — the clients' own recovery redials after the timed
        end must not restart the outage forever."""

        async def go():
            rule = FaultRule(
                plane="statestore", action="blackout", delay=0.15
            )
            inj = FaultInjector([rule])
            with faults.active(inj):
                with pytest.raises(ConnectionResetError):
                    await inj.before_connect("statestore", "h:1")
                assert inj.blackout_active("statestore")
                await asyncio.sleep(0.4)
                assert not inj.blackout_active("statestore")
                # recovery redial: the spent trigger does not re-fire
                await inj.before_connect("statestore", "h:1")

        run(go())

    def test_blackout_breaks_live_statestore_conns(self, run):
        """A scripted blackout kills an ESTABLISHED statestore connection
        and refuses re-dials; end_blackout restores service and the client
        reconnects on its own."""

        async def go():
            ss = StateStoreServer(port=0)
            await ss.start()
            client = await StateStoreClient.connect(ss.url)
            await client.put("k", b"v")
            inj = FaultInjector()
            with faults.active(inj):
                inj.begin_blackout(("statestore",))
                # the live connection is broken; the client's transparent
                # retry loop then blocks re-dialing (refused) — either a
                # typed failure or a timeout proves the plane is dark
                with pytest.raises((ConnectionError, RuntimeError,
                                    asyncio.TimeoutError)):
                    await asyncio.wait_for(client.get("k"), 2)
                inj.end_blackout()
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline:
                    try:
                        if await client.get("k") == b"v":
                            break
                    except (ConnectionError, RuntimeError):
                        pass
                    await asyncio.sleep(0.05)
                else:
                    raise AssertionError("client never recovered")
            await client.close()
            await ss.stop()

        run(go())


# -- stale-but-safe discovery --------------------------------------------------


async def _mini_cluster(n, monkeypatch, bus_url=NO_BUS, delay=0.0,
                        lease_ttl=0.8):
    monkeypatch.setenv("DYN_TPU_HEALTH_PROBE_IDLE_S", "0.4")
    ss = StateStoreServer(port=0)
    await ss.start()
    rts, infos = [], []
    for i in range(n):
        rt = await DistributedRuntime.create(ss.url, bus_url)
        ep = rt.namespace("cp").component("w").endpoint("gen")
        infos.append(await ep.serve(
            TokenEngine(f"w{i}", delay=delay),
            lease=await rt.store.grant_lease(ttl=lease_ttl),
        ))
        rts.append(rt)
    fe = await DistributedRuntime.create(ss.url, bus_url)
    client = await fe.namespace("cp").component("w").endpoint("gen").client(
        "round_robin", policy=_policy()
    )
    await client.wait_for_instances(n, timeout=10)
    return ss, rts, infos, fe, client


async def _teardown(ss, rts, fe, client):
    await client.close()
    for rt in rts + [fe]:
        await rt.shutdown()
    if ss is not None:
        await ss.stop()


async def _stream(client, prompt, max_tokens):
    ctx = Context(_payload(prompt, max_tokens=max_tokens))
    toks, errs = [], []
    async for item in client.generate(ctx):
        if item.is_error:
            errs.append(item.error_message())
        elif isinstance(item.data, dict):
            toks.extend(item.data.get("token_ids", []))
    return toks, errs


class TestStaleServe:
    def test_store_death_holds_instances_and_serves(self, run, monkeypatch):
        """The store dies outright: the instance set freezes (marked
        stale), NEW requests keep routing, and the control-plane state
        reads disconnected."""
        _clear_cp_env(monkeypatch)
        monkeypatch.setenv("DYN_TPU_STALE_GRACE", "30")

        async def go():
            control_plane.reset_for_tests()
            ss, rts, infos, fe, client = await _mini_cluster(2, monkeypatch)
            await ss.stop()
            await asyncio.sleep(0.3)  # let the watch die
            assert len(client.instance_ids()) == 2, "instances were dropped"
            toks, errs = await _stream(client, [3, 5], 8)
            assert errs == []
            assert toks == expected_stream([3, 5], 8)
            # held entries are visible as stale
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline and not client._stale:
                await asyncio.sleep(0.05)
            assert client.health_summary()["stale"] == 2
            assert control_plane.snapshot()["planes"]["statestore"][
                "state"] == "disconnected"
            await _teardown(None, rts, fe, client)

        run(go())

    def test_restart_empty_resync_holds_then_converges(self, run, monkeypatch):
        """The store restarts EMPTY (every lease and key gone): the
        client's resync synthesizes deletes for every instance — they are
        HELD stale, serving continues, and once the workers re-register
        under fresh leases the old entries purge and the stale marks
        clear."""
        _clear_cp_env(monkeypatch)
        monkeypatch.setenv("DYN_TPU_STALE_GRACE", "1.0")
        monkeypatch.setenv("DYN_TPU_REJOIN_JITTER", "0.2")

        async def go():
            ss, rts, infos, fe, client = await _mini_cluster(2, monkeypatch)
            old_ids = set(client.instance_ids())
            port = ss.port
            await ss.stop()
            await asyncio.sleep(0.2)
            ss2 = StateStoreServer("127.0.0.1", port)
            await ss2.start()
            # the client reconnects + resyncs against an empty store: the
            # held set must keep serving throughout
            toks, errs = await _stream(client, [7, 9], 8)
            assert errs == []
            assert toks == expected_stream([7, 9], 8)
            # convergence: workers re-register (fresh instance ids), old
            # entries purge, stale set empties
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                ids = set(client.instance_ids())
                if len(ids) == 2 and not (ids & old_ids) and not client._stale:
                    break
                await asyncio.sleep(0.1)
            else:
                raise AssertionError(
                    f"never reconverged: ids={client.instance_ids()} "
                    f"stale={client._stale} old={old_ids}"
                )
            # and the fresh registration is fully routable
            toks, errs = await _stream(client, [2, 4], 6)
            assert errs == [] and toks == expected_stream([2, 4], 6)
            await _teardown(ss2, rts, fe, client)

        run(go())

    def test_dead_worker_purged_at_grace_by_probe(self, run, monkeypatch):
        """A worker that died DURING the outage: its held entry fails the
        liveness probe and purges at grace; the survivor keeps serving."""
        _clear_cp_env(monkeypatch)
        monkeypatch.setenv("DYN_TPU_STALE_GRACE", "0.5")
        monkeypatch.setenv("DYN_TPU_HEALTH_PROBE_IDLE_S", "0.3")

        async def go():
            ss, rts, infos, fe, client = await _mini_cluster(2, monkeypatch)
            victim_iid = infos[0].instance_id
            await ss.stop()
            await asyncio.sleep(0.2)
            # the worker dies while the store is dark: no delete event ever
            await rts[0]._rpc_server.stop(drain_timeout=0.01)
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                if victim_iid not in client._instances:
                    break
                await asyncio.sleep(0.1)
            else:
                raise AssertionError("dead worker's stale entry never purged")
            toks, errs = await _stream(client, [1, 2], 6)
            assert errs == [] and toks == expected_stream([1, 2], 6)
            await _teardown(None, rts, fe, client)

        run(go())

    def test_stale_serve_off_restores_clear_behavior(self, run, monkeypatch):
        """DYN_TPU_STALE_SERVE=0: a restart-empty resync clears the
        instance set (the pre-blackout behavior)."""
        _clear_cp_env(monkeypatch)
        monkeypatch.setenv("DYN_TPU_STALE_SERVE", "0")

        async def go():
            ss, rts, infos, fe, client = await _mini_cluster(2, monkeypatch)
            port = ss.port
            # keep workers from instantly re-registering (isolates the
            # client-side behavior)
            for rt in rts:
                for t in rt._background:
                    t.cancel()
            await ss.stop()
            ss2 = StateStoreServer("127.0.0.1", port)
            await ss2.start()
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and client.instance_ids():
                await asyncio.sleep(0.1)
            assert client.instance_ids() == []
            assert not client._stale
            await _teardown(ss2, rts, fe, client)

        run(go())


# -- cold start: cache and typed failure ---------------------------------------


class TestColdStart:
    def test_dead_store_no_cache_raises_typed_within_deadline(
        self, run, monkeypatch
    ):
        """Satellite: a frontend cold-started against a dead statestore
        with no cache gets a typed ControlPlaneUnavailable within the
        deadline instead of a hung process."""
        _clear_cp_env(monkeypatch)
        monkeypatch.setenv("DYN_TPU_COLD_START_DEADLINE", "0.4")

        async def go():
            t0 = time.monotonic()
            with pytest.raises(ControlPlaneUnavailable) as ei:
                await DistributedRuntime.create("127.0.0.1:1", NO_BUS)
            took = time.monotonic() - t0
            assert took < 3.0, f"typed failure took {took:.1f}s"
            assert "discovery cache" in str(ei.value)
            # ...and it is still a ConnectionError for old handlers
            assert isinstance(ei.value, ConnectionError)

        run(go())

    def test_cold_start_from_cache_serves(self, run, monkeypatch, tmp_path):
        """A frontend restarted MID-OUTAGE: the discovery cache seeds the
        instance set (marked stale) and requests stream from the live
        workers with no statestore at all."""
        _clear_cp_env(monkeypatch)
        monkeypatch.setenv("DYN_TPU_DISCOVERY_CACHE", str(tmp_path))
        monkeypatch.setenv("DYN_TPU_COLD_START_DEADLINE", "0.3")
        monkeypatch.setenv("DYN_TPU_STALE_GRACE", "30")

        async def go():
            ss, rts, infos, fe, client = await _mini_cluster(2, monkeypatch)
            url = ss.url
            prefix = "cp/components/w/endpoints/gen/instances/"
            cache = DiscoveryCache(str(tmp_path))
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                got = cache.load(prefix)
                if got and len(got) == 2:
                    break
                await asyncio.sleep(0.1)
            else:
                raise AssertionError("discovery cache never flushed")
            # frontend restarts while the store is dark
            await client.close()
            await fe.shutdown()
            await ss.stop()
            fe2 = await DistributedRuntime.create(url, NO_BUS)
            assert not fe2.store.connected
            client2 = await fe2.namespace("cp").component("w").endpoint(
                "gen"
            ).client("round_robin", policy=_policy())
            assert len(client2.instance_ids()) == 2
            assert client2.health_summary()["stale"] == 2
            toks, errs = await _stream(client2, [5, 8], 8)
            assert errs == []
            assert toks == expected_stream([5, 8], 8)
            assert control_plane.snapshot()["cache_cold_starts"] >= 1
            await _teardown(None, rts, fe2, client2)

        run(go())

    def test_zero_overhead_when_cache_knob_unset(self, run, monkeypatch):
        """Acceptance guard: with the control plane healthy and no cache
        knob, no DiscoveryCache is ever constructed (monkeypatched ctor
        raises) and no snapshot file is written."""
        _clear_cp_env(monkeypatch)

        def boom(*a, **kw):
            raise AssertionError("DiscoveryCache built with knob unset")

        monkeypatch.setattr(control_plane.DiscoveryCache, "__init__", boom)

        async def go():
            ss, rts, infos, fe, client = await _mini_cluster(1, monkeypatch)
            assert client._cache is None
            toks, errs = await _stream(client, [1, 3], 6)
            assert errs == [] and toks == expected_stream([1, 3], 6)
            await asyncio.sleep(0.5)  # a few probe/flush ticks
            await _teardown(ss, rts, fe, client)

        run(go())


# -- model watcher holds through outages ---------------------------------------


class TestModelWatcherStaleServe:
    def test_models_survive_restart_empty(self, run, monkeypatch):
        """A store restart-empty must not strip models off the frontend:
        entries are held stale and re-confirmed when workers re-register."""
        from dynamo_tpu.llm.http.discovery import ModelWatcher
        from dynamo_tpu.llm.http.service import ModelManager

        _clear_cp_env(monkeypatch)
        monkeypatch.setenv("DYN_TPU_STALE_GRACE", "2.0")
        monkeypatch.setenv("DYN_TPU_REJOIN_JITTER", "0.2")

        async def go():
            ss, rts, infos, fe, client = await _mini_cluster(1, monkeypatch)
            # register a model entry the watcher will manage
            ep = rts[0].namespace("cp").component("w").endpoint("gen")
            await ep.serve(
                TokenEngine("m"), model_entry={"name": "tiny", "kind": "chat"},
                lease=await rts[0].store.grant_lease(ttl=0.8),
            )
            manager = ModelManager()
            watcher = ModelWatcher(fe, "cp", manager)
            watcher.start()
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and "tiny" not in manager.model_names():
                await asyncio.sleep(0.05)
            assert "tiny" in manager.model_names()
            port = ss.port
            await ss.stop()
            await asyncio.sleep(0.3)
            assert "tiny" in manager.model_names(), "model dropped on outage"
            ss2 = StateStoreServer("127.0.0.1", port)
            await ss2.start()
            # held through the empty resync, re-confirmed by re-registration
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                await asyncio.sleep(0.1)
                if "tiny" in manager.model_names() and not watcher._stale_keys:
                    break
            assert "tiny" in manager.model_names()
            assert not watcher._stale_keys, "stale marks never cleared"
            await watcher.close()
            await _teardown(ss2, rts, fe, client)

        run(go())


# -- bus outage buffering ------------------------------------------------------


class TestBusBuffering:
    def test_snapshots_buffered_and_flushed_with_stale_stamp(
        self, run, monkeypatch
    ):
        """Kill the bus under a publishing worker: snapshots buffer
        (bounded), and at recovery the backfill arrives stamped with
        stale_s so the aggregator knows its age; the live snapshot follows
        unstamped."""
        from dynamo_tpu.runtime.distributed import (
            KV_METRICS_SUBJECT,
            attach_kv_publishing,
        )

        _clear_cp_env(monkeypatch)
        monkeypatch.setenv("DYN_TPU_BUS_BUFFER", "8")

        class SnapEngine:
            def __init__(self):
                self.n = 0

            def metrics_snapshot(self):
                self.n += 1
                return {"request_total_slots": 4, "seq": self.n}

        async def go():
            ss = StateStoreServer(port=0)
            await ss.start()
            bus = MessageBusServer(port=0)
            await bus.start()
            bus_port = bus.port
            wk = await DistributedRuntime.create(ss.url, bus.url)
            ep = wk.namespace("cpb").component("w").endpoint("gen")
            await ep.serve(TokenEngine("w0"))
            await attach_kv_publishing(ep, SnapEngine(), interval=0.1)
            sub_rt = await DistributedRuntime.create(ss.url, bus.url)
            sub = await sub_rt.namespace("cpb").subscribe(KV_METRICS_SUBJECT)
            got: list = []

            async def consume():
                async for raw in sub:
                    got.append(json.loads(raw))

            task = asyncio.create_task(consume())
            await asyncio.sleep(0.35)  # a few live publishes
            n_live = len(got)
            assert n_live >= 1
            await bus.stop()
            await asyncio.sleep(0.6)  # snapshots produced dark → buffered
            bus2 = MessageBusServer("127.0.0.1", bus_port)
            await bus2.start()
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                stamped = [
                    m for m in got if m["metrics"].get("stale_s", 0) > 0
                ]
                fresh_after = [
                    m for m in got[n_live:]
                    if "stale_s" not in m["metrics"]
                ]
                if stamped and fresh_after:
                    break
                await asyncio.sleep(0.1)
            else:
                raise AssertionError(
                    f"no stamped backfill arrived "
                    f"(got {len(got)} messages)"
                )
            # backfill is ordered: the stamped snapshots carry earlier seqs
            # than the fresh one that follows them
            assert stamped[0]["metrics"]["seq"] < fresh_after[-1][
                "metrics"]["seq"]
            assert all(
                m["metrics"]["control_plane_state"] in
                ("connected", "stale", "disconnected") for m in got
            )
            task.cancel()
            await sub_rt.shutdown()
            await wk.shutdown()
            await bus2.stop()
            await ss.stop()

        run(go())


# -- ForwardPassMetrics wire form ----------------------------------------------


class TestWireForm:
    def test_metrics_roundtrip_and_old_dicts_parse(self):
        from dynamo_tpu.kv_router.protocols import ForwardPassMetrics

        m = ForwardPassMetrics(
            control_plane_state="stale", bus_dropped_events=7, stale_s=2.5
        )
        d = m.to_dict()
        back = ForwardPassMetrics.from_dict(d)
        assert back.control_plane_state == "stale"
        assert back.bus_dropped_events == 7
        assert back.stale_s == 2.5
        # pre-blackout dicts parse with the connected defaults
        old = ForwardPassMetrics.from_dict({"request_total_slots": 4})
        assert old.control_plane_state == ""
        assert old.bus_dropped_events == 0

    def test_aggregator_rollup_counts_impaired(self):
        from dynamo_tpu.components.telemetry_aggregator import ClusterTelemetry
        from dynamo_tpu.components.mock_worker import MockWorkerStats

        cluster = ClusterTelemetry("t")
        ok = MockWorkerStats(seed=1)
        bad = MockWorkerStats(
            seed=2, control_plane_state="stale", bus_dropped_events=5
        )
        ok.tick()
        bad.tick()
        cluster.ingest("w-ok", ok.metrics("m1"))
        cluster.ingest("w-bad", bad.metrics("m1"))
        entry = cluster.rollup()["models"]["m1"]
        assert entry["control_plane_impaired"] == 1
        assert entry["control_plane"]["connected"] == 1
        assert entry["control_plane"]["stale"] == 1
        assert entry["control_plane"]["impaired_worker_ids"] == ["w-bad"]
        assert entry["bus_dropped_events"] == 5
        # the new gauges render through the strict parser
        from tests.promtext import parse_prometheus_text

        fams = parse_prometheus_text(cluster.render_prometheus())
        assert "dynamo_cluster_control_plane_impaired" in fams
        assert "dynamo_cluster_bus_dropped_events" in fams


# -- llmctl --------------------------------------------------------------------


class TestLlmctlControlPlane:
    def test_status_exit_codes(self, run, capsys):
        """Satellite: mock worker reporting a stale control plane →
        aggregator → `llmctl control-plane status` exits 2 and names the
        impaired worker; a connected fleet exits 0."""
        from dynamo_tpu.cli.llmctl import amain
        from dynamo_tpu.components.mock_worker import MockWorkerStats
        from dynamo_tpu.components.telemetry_aggregator import (
            run_telemetry_aggregator,
        )
        from dynamo_tpu.runtime.distributed import KV_METRICS_SUBJECT

        async def go():
            ss = StateStoreServer(port=0)
            bus = MessageBusServer(port=0)
            await ss.start()
            await bus.start()
            drt = await DistributedRuntime.create(ss.url, bus.url)
            pub = await DistributedRuntime.create(ss.url, bus.url)
            ns = pub.namespace("dynamo")
            ready = asyncio.Event()
            agg_task = asyncio.create_task(run_telemetry_aggregator(
                drt, "dynamo", port=0, host="127.0.0.1", ready=ready,
            ))
            await asyncio.wait_for(ready.wait(), 10)
            try:
                healthy = MockWorkerStats(seed=1)
                healthy.tick()
                await ns.publish(KV_METRICS_SUBJECT, {
                    "worker_id": "w0",
                    "metrics": healthy.metrics("m1").to_dict(),
                })
                await asyncio.sleep(0.2)
                rc = await amain([
                    "--statestore", ss.url, "control-plane", "status",
                    "dyn://dynamo.telemetry.status",
                ])
                out = capsys.readouterr().out
                assert rc == 0
                assert "connected=  1" in out

                impaired = MockWorkerStats(
                    seed=2, control_plane_state="disconnected"
                )
                impaired.tick()
                await ns.publish(KV_METRICS_SUBJECT, {
                    "worker_id": "w-dark",
                    "metrics": impaired.metrics("m1").to_dict(),
                })
                await asyncio.sleep(0.2)
                rc = await amain([
                    "--statestore", ss.url, "control-plane", "status",
                    "dyn://dynamo.telemetry.status",
                ])
                out = capsys.readouterr().out
                assert rc == 2
                assert "IMPAIRED" in out and "w-dark" in out
                # --json exits the same way
                rc = await amain([
                    "--statestore", ss.url, "control-plane", "status",
                    "--json", "dyn://dynamo.telemetry.status",
                ])
                body = json.loads(capsys.readouterr().out)
                assert rc == 2
                assert body["statestore"] == "connected"
                assert body["rows"][0]["disconnected"] == 1
            finally:
                agg_task.cancel()
                try:
                    await agg_task
                except (asyncio.CancelledError, Exception):
                    pass
                await drt.shutdown()
                await pub.shutdown()
                await bus.stop()
                await ss.stop()

        run(go())

    def test_status_with_dead_statestore_exits_2(self, run, capsys):
        from dynamo_tpu.cli.llmctl import amain

        async def go():
            rc = await amain([
                "--statestore", "127.0.0.1:1", "control-plane", "status",
            ])
            assert rc == 2
            assert "DISCONNECTED" in capsys.readouterr().out
            # --json stays machine-parseable during the exact outage the
            # command exists to report
            rc = await amain([
                "--statestore", "127.0.0.1:1", "control-plane", "status",
                "--json",
            ])
            assert rc == 2
            body = json.loads(capsys.readouterr().out)
            # same envelope shape as the healthy path: object with rows
            assert body["statestore"] == "disconnected"
            assert body["rows"] == []

        run(go())


# -- the chaos gate ------------------------------------------------------------


class TestBlackoutChaosGate:
    def test_full_blackout_is_invisible_to_callers(self, run, monkeypatch):
        """THE acceptance scenario: 3 workers + a routing client at 2x
        load; the statestore AND bus are killed mid-run and restarted
        EMPTY (worst case: every lease and key gone). Requirements:

        - zero client-visible failures, streams byte-equal to control
          (including requests ADMITTED while both planes are dark);
        - reconvergence after recovery: every worker re-registered under
          a fresh lease (with seeded rejoin jitter), stale discovery
          cleared;
        - a drain key written while the worker's watch was down applies
          on resync (missed drains are not lost);
        - telemetry flows again on the restarted bus.
        """
        from dynamo_tpu.runtime.distributed import (
            KV_METRICS_SUBJECT,
            attach_kv_publishing,
        )

        _clear_cp_env(monkeypatch)
        monkeypatch.setenv("DYN_TPU_STALE_GRACE", "1.0")
        monkeypatch.setenv("DYN_TPU_REJOIN_JITTER", "0.3")
        monkeypatch.setenv("DYN_TPU_BUS_BUFFER", "32")

        class SnapEngine:
            def metrics_snapshot(self):
                return {"request_total_slots": 4}

        async def go():
            monkeypatch.setenv("DYN_TPU_HEALTH_PROBE_IDLE_S", "0.4")
            ss = StateStoreServer(port=0)
            await ss.start()
            bus = MessageBusServer(port=0)
            await bus.start()
            ss_port, bus_port = ss.port, bus.port
            rts = []
            for i in range(3):
                rt = await DistributedRuntime.create(ss.url, bus.url)
                ep = rt.namespace("cp").component("w").endpoint("gen")
                await ep.serve(
                    TokenEngine(f"w{i}", delay=0.03),
                    lease=await rt.store.grant_lease(ttl=0.8),
                )
                rts.append(rt)
            # one worker also publishes telemetry (proves the bus half)
            pub_ep = rts[0].namespace("cp").component("w").endpoint("gen")
            await attach_kv_publishing(pub_ep, SnapEngine(), interval=0.15)
            fe = await DistributedRuntime.create(ss.url, bus.url)
            client = await fe.namespace("cp").component("w").endpoint(
                "gen"
            ).client("round_robin", policy=_policy())
            await client.wait_for_instances(3, timeout=10)

            prompts = [[11 + i, 17 + 2 * i] for i in range(12)]
            want = [expected_stream(p, 50) for p in prompts]

            results: dict = {}

            async def one(i):
                results[i] = await _stream(client, prompts[i], 50)

            # 2x load: 12 concurrent streams on 3 × 2-slot-ish mock workers
            tasks = [asyncio.create_task(one(i)) for i in range(8)]
            await asyncio.sleep(0.2)  # streams flowing
            await ss.stop()
            await bus.stop()
            await asyncio.sleep(0.3)
            # admissions DURING the blackout must work off the held set
            tasks += [asyncio.create_task(one(i)) for i in range(8, 12)]
            await asyncio.sleep(0.7)  # > lease ttl: leases are long gone
            ss2 = StateStoreServer("127.0.0.1", ss_port)  # restart EMPTY
            await ss2.start()
            bus2 = MessageBusServer("127.0.0.1", bus_port)
            await bus2.start()
            # a drain ordered while the workers' watches are still down:
            # must apply at resync, not be lost
            store2 = await StateStoreClient.connect(ss2.url)
            drain_key = (
                "cp/components/w/endpoints/gen/drain/" + rts[2].worker_id
            )
            await store2.put(drain_key, b"1")

            await asyncio.gather(*tasks)
            # 1) zero client-visible failures, byte-equal streams
            for i in range(12):
                toks, errs = results[i]
                assert errs == [], f"stream {i} saw errors: {errs}"
                assert toks == want[i], f"stream {i} diverged"

            # 2) reconvergence: 3 fresh leases/instance keys in the store
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                keys = await store2.get_prefix(
                    "cp/components/w/endpoints/gen/instances/"
                )
                if len(keys) >= 3 and not client._stale:
                    break
                await asyncio.sleep(0.1)
            else:
                raise AssertionError(
                    f"fleet never reconverged: {len(keys)} instance keys, "
                    f"stale={client._stale}"
                )

            # 3) the missed drain applied
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and not rts[2].draining:
                await asyncio.sleep(0.1)
            assert rts[2].draining, "drain ordered during the gap was lost"

            # 4) telemetry flows on the restarted bus (backfill + live)
            sub_rt = await DistributedRuntime.create(ss2.url, bus2.url)
            sub = await sub_rt.namespace("cp").subscribe(KV_METRICS_SUBJECT)

            async def first_msg():
                async for raw in sub:
                    return json.loads(raw)

            msg = await asyncio.wait_for(first_msg(), 10)
            assert msg["metrics"]["request_total_slots"] == 4

            await sub_rt.shutdown()
            await store2.close()
            await _teardown(None, rts, fe, client)
            await ss2.stop()
            await bus2.stop()

        run(go())
