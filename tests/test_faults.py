"""The deterministic fault-injection harness, and the recovery paths it
drives: malformed-frame hardening, mid-stream resets, delayed watch events,
lease-loss re-registration, and client watch reconnection — previously only
testable with hand-rolled socket tricks.
"""

import asyncio
import json

import pytest

from dynamo_tpu.runtime import codec, faults
from dynamo_tpu.runtime.annotated import Annotated
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.engine import AsyncEngine, Context
from dynamo_tpu.runtime.faults import FaultInjector, FaultRule, injector_from_spec
from dynamo_tpu.runtime.rpc import RpcClient, RpcServer
from dynamo_tpu.runtime.statestore import StateStoreClient, StateStoreServer


class CountEngine(AsyncEngine):
    async def generate(self, request: Context):
        for i in range(request.data.get("n", 3)):
            await asyncio.sleep(0)
            yield Annotated.from_data({"i": i})


# -- harness core -------------------------------------------------------------


class TestInjectorDeterminism:
    def test_same_seed_same_schedule(self):
        rules = lambda: [  # noqa: E731
            FaultRule(plane="rpc", point="read", action="reset", probability=0.3),
            FaultRule(plane="rpc", point="connect", action="refuse", probability=0.5),
        ]
        a, b = FaultInjector(rules(), seed=99), FaultInjector(rules(), seed=99)
        seq_a = [
            (a.decide("rpc", "h:1", "read", i) or FaultRule(action="none")).action
            for i in range(200)
        ]
        seq_b = [
            (b.decide("rpc", "h:1", "read", i) or FaultRule(action="none")).action
            for i in range(200)
        ]
        assert seq_a == seq_b
        assert "reset" in seq_a  # the schedule actually fires
        c = FaultInjector(rules(), seed=100)
        seq_c = [
            (c.decide("rpc", "h:1", "read", i) or FaultRule(action="none")).action
            for i in range(200)
        ]
        assert seq_c != seq_a  # different seed → different schedule

    def test_rule_matching(self):
        r = FaultRule(plane="rpc", point="connect", action="refuse",
                      match_addr="h:1", after_ops=2, max_fires=1)
        inj = FaultInjector([r])
        assert inj.decide("statestore", "h:1", "connect", 5) is None  # plane
        assert inj.decide("rpc", "h:2", "connect", 5) is None  # addr
        assert inj.decide("rpc", "h:1", "read", 5) is None  # point
        assert inj.decide("rpc", "h:1", "connect", 1) is None  # after_ops
        assert inj.decide("rpc", "h:1", "connect", 2) is r
        assert inj.decide("rpc", "h:1", "connect", 3) is None  # max_fires
        assert [d.action for d in inj.log] == ["refuse"]

    def test_env_spec_parsing(self):
        inj = injector_from_spec(
            '[{"plane": "rpc", "action": "refuse"}, '
            '{"plane": "*", "point": "read", "action": "delay", "delay": 0.1}]',
            seed=7,
        )
        assert len(inj.rules) == 2 and inj.seed == 7
        assert inj.rules[1].delay == 0.1
        with pytest.raises(ValueError):
            injector_from_spec('{"not": "a list"}')

    def test_connect_refusal_scoped_by_context_manager(self, run):
        async def go():
            server = RpcServer(host="127.0.0.1", port=0)
            server.register("e", CountEngine())
            await server.start()
            addr = f"127.0.0.1:{server.port}"
            inj = FaultInjector([FaultRule(plane="rpc", action="refuse")])
            with faults.active(inj):
                with pytest.raises(ConnectionRefusedError):
                    await RpcClient.connect(addr)
            # out of scope: the same dial works
            client = await RpcClient.connect(addr)
            items = [i async for i in client.generate("e", {"n": 2})]
            assert [i.data["i"] for i in items] == [0, 1]
            await client.close()
            await server.stop()

        run(go())

    def test_mid_stream_reset(self, run):
        """A reset mid-response kills the stream cleanly: the delivered
        prefix arrives, then a retryable error envelope — never a hang."""

        async def go():
            server = RpcServer(host="127.0.0.1", port=0)
            server.register("e", CountEngine())
            await server.start()
            # client read call sequence: op0 pending prelude, op1 header,
            # op2 body (item 1), op3 prelude, op4 header (item 2) ← reset
            inj = FaultInjector([
                FaultRule(plane="rpc", point="read", action="reset", after_ops=4)
            ])
            with faults.active(inj):
                client = await RpcClient.connect(f"127.0.0.1:{server.port}")
                items = [i async for i in client.generate("e", {"n": 5})]
            assert items[0].data == {"i": 0}
            assert items[-1].is_error
            assert "lost" in items[-1].error_message()
            await client.close()
            await server.stop()

        run(go())

    def test_delayed_reads_do_not_corrupt_watch_streams(self, run):
        """Delay faults on the statestore plane slow event delivery but must
        never reorder or drop it."""

        async def go():
            server = StateStoreServer(port=0)
            await server.start()
            inj = FaultInjector([
                FaultRule(plane="statestore", point="read", action="delay",
                          delay=0.05, max_fires=10)
            ])
            with faults.active(inj):
                c = await StateStoreClient.connect(server.url)
                watcher = await c.watch_prefix("d/", include_existing=True)
                events = []

                async def consume():
                    async for ev in watcher:
                        events.append((ev.type, ev.key))
                        if len(events) >= 3:
                            return

                task = asyncio.create_task(consume())
                await asyncio.sleep(0.05)
                await c.put("d/a", b"1")
                await c.put("d/b", b"2")
                await c.delete("d/a")
                await asyncio.wait_for(task, 10)
            assert events == [("put", "d/a"), ("put", "d/b"), ("delete", "d/a")]
            assert any(d.action == "delay" for d in inj.log)
            await c.close()
            await server.stop()

        run(go())


# -- malformed-frame hardening (satellite) ------------------------------------


class TestMalformedFrames:
    def test_garbage_bytes_close_only_that_connection(self, run):
        async def go():
            server = RpcServer(host="127.0.0.1", port=0)
            server.register("e", CountEngine())
            await server.start()
            addr = f"127.0.0.1:{server.port}"

            # raw garbage: not even a valid prelude
            r, w = await asyncio.open_connection("127.0.0.1", server.port)
            w.write(b"\xde\xad\xbe\xef" * 16)
            await w.drain()
            assert await asyncio.wait_for(r.read(), 5) == b""  # server hung up
            w.close()

            # codec-valid frame whose header isn't JSON
            r, w = await asyncio.open_connection("127.0.0.1", server.port)
            w.write(codec.encode(codec.TwoPartMessage(b"not json at all", b"")))
            await w.drain()
            assert await asyncio.wait_for(r.read(), 5) == b""
            w.close()

            # valid JSON header but non-JSON body → error reply, conn stays up
            r, w = await asyncio.open_connection("127.0.0.1", server.port)
            hdr = json.dumps({"id": 1, "op": "generate", "endpoint": "e"}).encode()
            w.write(codec.encode(codec.TwoPartMessage(hdr, b"\xff\xfe\xfd")))
            await w.drain()
            reply = await asyncio.wait_for(codec.read_frame(r), 5)
            assert json.loads(reply.header)["op"] == "error"
            w.close()

            # header that is JSON but not an object
            r, w = await asyncio.open_connection("127.0.0.1", server.port)
            w.write(codec.encode(codec.TwoPartMessage(b"[1, 2, 3]", b"")))
            await w.drain()
            assert await asyncio.wait_for(r.read(), 5) == b""
            w.close()

            # through all of that, other clients are unaffected
            client = await RpcClient.connect(addr)
            items = [i async for i in client.generate("e", {"n": 3})]
            assert [i.data["i"] for i in items] == [0, 1, 2]
            await client.close()
            await server.stop()

        run(go())


    def test_client_survives_malformed_server_frame(self, run):
        """A codec-valid frame whose header is JSON-but-not-an-object from a
        buggy server must surface as a clean retryable stream error — not
        silently kill the client's reader task and hang every stream."""

        async def fake_server(reader, writer):
            try:
                await codec.read_frame(reader)  # the generate request
            except (asyncio.IncompleteReadError, ConnectionError):
                return
            writer.write(codec.encode(codec.TwoPartMessage(b"[1, 2, 3]", b"")))
            await writer.drain()
            # the buggy server then hangs up; an unclosed writer would also
            # hold Server.wait_closed() below for ever on Python 3.12
            writer.close()

        async def go():
            server = await asyncio.start_server(fake_server, "127.0.0.1", 0)
            port = server.sockets[0].getsockname()[1]
            client = await RpcClient.connect(f"127.0.0.1:{port}")
            items = await asyncio.wait_for(
                _collect(client.generate("e", {})), 5
            )
            assert len(items) == 1 and items[0].is_error
            assert "malformed" in items[0].error_message()
            assert client.closed  # conn marked dead, not silently reusable
            await client.close()
            server.close()
            await server.wait_closed()

        async def _collect(agen):
            return [i async for i in agen]

        run(go())


# -- recovery loops under injected outages (satellite) ------------------------


class TestRecoveryLoops:
    def test_lease_loss_reregistration_and_watch_reconnect(self, run):
        """One statestore outage, both recovery halves: the worker's lease
        dies (keepalives fail) and it re-registers under a fresh lease; the
        client's watch dies and it reconnects with a resync snapshot. Driven
        entirely by injected faults — the statestore server itself never
        stops."""

        async def go():
            ss = StateStoreServer(port=0)
            await ss.start()

            async def mk_runtime():
                store = await StateStoreClient.connect(ss.url, reconnect_timeout=1.0)
                rt = DistributedRuntime(store, None)
                rt._store_url = ss.url
                return rt

            wk = await mk_runtime()
            fe = await mk_runtime()
            ep = wk.namespace("f").component("c").endpoint("g")
            lease = await wk.store.grant_lease(ttl=1.0)
            info = await ep.serve(CountEngine(), lease=lease)
            client = await fe.namespace("f").component("c").endpoint("g").client(
                "round_robin"
            )
            await client.wait_for_instances(1, timeout=10)
            old_iid = info.instance_id

            inj = FaultInjector(seed=5)
            with faults.active(inj):
                # outage: every statestore connection resets, re-dials refused
                inj.add_rule(FaultRule(plane="statestore", point="read",
                                       action="reset"))
                inj.add_rule(FaultRule(plane="statestore", point="write",
                                       action="reset"))
                inj.add_rule(FaultRule(plane="statestore", point="connect",
                                       action="refuse"))
                # long enough for: keepalive failure → lease.lost, server-side
                # lease expiry (ttl=1s), and the client watch to die
                await asyncio.sleep(2.5)
                assert lease.lost.is_set(), "keepalive failure never surfaced"
                inj.clear_rules()

                # worker re-registers under a fresh lease; client resyncs
                new_iid = None
                for _ in range(200):
                    ids = client.instance_ids()
                    if ids and ids != [old_iid]:
                        new_iid = ids[-1]
                        break
                    await asyncio.sleep(0.1)
                assert new_iid is not None, (
                    f"re-registration/resync never completed (seed=5, "
                    f"log tail={inj.log[-5:]})"
                )
                assert new_iid != old_iid  # fresh lease → fresh instance id
                # and the path actually serves again
                items = [i async for i in client.generate(Context({"n": 2}))]
                assert not any(i.is_error for i in items)
                assert [i.data["i"] for i in items] == [0, 1]

            await client.close()
            await wk.shutdown()
            await fe.shutdown()
            await ss.stop()

        run(go())

    def test_watch_reconnect_alone_under_connect_refusals(self, run):
        """A shorter, watch-only variant: the client's statestore connection
        dies once (single reset), re-dials are refused a bounded number of
        times, and the watch must come back with a consistent view."""

        async def go():
            ss = StateStoreServer(port=0)
            await ss.start()
            store = await StateStoreClient.connect(ss.url, reconnect_timeout=5.0)
            fe = DistributedRuntime(store, None)
            fe._store_url = ss.url
            wk_store = await StateStoreClient.connect(ss.url)
            wk = DistributedRuntime(wk_store, None)
            wk._store_url = ss.url
            ep = wk.namespace("w2").component("c").endpoint("g")
            await ep.serve(CountEngine())
            client = await fe.namespace("w2").component("c").endpoint("g").client(
                "round_robin"
            )
            await client.wait_for_instances(1, timeout=10)

            inj = FaultInjector(seed=11)
            with faults.active(inj):
                inj.add_rule(FaultRule(plane="statestore", point="read",
                                       action="reset", max_fires=1))
                inj.add_rule(FaultRule(plane="statestore", point="connect",
                                       action="refuse", max_fires=3))
                # trigger traffic so the reset fires on the fe store conn
                try:
                    await fe.store.get("__poke__")
                except (ConnectionError, RuntimeError):
                    pass
                deadline = asyncio.get_running_loop().time() + 15
                while asyncio.get_running_loop().time() < deadline:
                    if client.instance_ids():
                        try:
                            items = [
                                i async for i in client.generate(Context({"n": 1}))
                            ]
                            if items and not items[0].is_error:
                                break
                        except (ConnectionError, RuntimeError, OSError):
                            pass
                    await asyncio.sleep(0.1)
                else:
                    raise AssertionError(
                        f"watch never recovered (seed=11, log={inj.log})"
                    )

            await client.close()
            await wk.shutdown()
            await fe.shutdown()
            await ss.stop()

        run(go())


class TestBoundedLogAndDataFaults:
    def test_decision_log_is_bounded(self):
        """ISSUE 14 satellite: the decision log is a bounded ring (the PR8
        decision-ring pattern) — a soak run with a per-frame rule must not
        grow one entry per fired decision forever."""
        from dynamo_tpu.runtime.faults import FAULT_LOG_MAX

        inj = FaultInjector([FaultRule(plane="rpc", point="connect",
                                       action="delay", delay=0.0)])
        for i in range(FAULT_LOG_MAX * 3):
            assert inj.decide("rpc", "a:1", "connect", i) is not None
        assert len(inj.log) == FAULT_LOG_MAX
        # newest entries retained; list idioms (slices) still answer
        assert inj.log[-1].op_index == FAULT_LOG_MAX * 3 - 1
        assert len(inj.log[-10:]) == 10

    def test_corrupt_pages_flips_one_bit_deterministically(self):
        body = bytes(range(64))
        inj = FaultInjector([FaultRule(
            plane="transfer", point="pages", action="corrupt",
            match_addr="w0", after_ops=1,
        )])
        with faults.active(inj):
            # op 0 skipped (after_ops=1), op 1 fires, wrong addr never
            assert faults.corrupt_pages("transfer", "w0", body) == body
            out = faults.corrupt_pages("transfer", "w0", body)
            assert out != body and len(out) == len(body)
            assert sum(a != b for a, b in zip(out, body)) == 1
            assert faults.corrupt_pages("transfer", "other", body) == body
        # no injector ⇒ identity
        assert faults.corrupt_pages("transfer", "w0", body) == body

    def test_corrupt_array_copies_and_flips(self):
        import numpy as np

        arr = np.zeros((4, 8), np.float32)
        arr.setflags(write=False)  # device_get views may be read-only
        inj = FaultInjector([FaultRule(
            plane="engine", point="pages", action="corrupt",
        )])
        with faults.active(inj):
            out = faults.corrupt_array("engine", "w0", arr)
        assert out is not arr
        assert (out != arr).sum() >= 1
        assert (arr == 0).all()  # original untouched

    def test_sync_decide_filters_on_action(self):
        """A differently-actioned rule at the same point must neither fire
        nor burn its max_fires budget when a corrupt/poison gate consults
        the injector (review hardening: decide_sync matches on action)."""
        body = bytes(range(16))
        delay_rule = FaultRule(plane="transfer", point="pages",
                               action="delay", delay=0.5, max_fires=2)
        corrupt_rule = FaultRule(plane="transfer", point="pages",
                                 action="corrupt")
        inj = FaultInjector([delay_rule, corrupt_rule])
        with faults.active(inj):
            out = faults.corrupt_pages("transfer", "w0", body)
        assert out != body            # the corrupt rule (listed second) fired
        assert delay_rule.fired == 0  # the delay rule kept its budget
        assert corrupt_rule.fired == 1
        assert [d.action for d in inj.log] == ["corrupt"]

    def test_poison_gate_counts_dispatches(self):
        inj = FaultInjector([FaultRule(
            plane="engine", point="dispatch", action="poison",
            after_ops=2, max_fires=1,
        )])
        with faults.active(inj):
            fired = [faults.poison_gate("engine", "w0") for _ in range(5)]
        assert fired == [False, False, True, False, False]
        assert not faults.poison_gate("engine", "w0")  # uninstalled
