"""Cross-host device-path KV transfer protocol (VERDICT r3 missing item 4).

The real device plane (jax.experimental.transfer) needs a PJRT backend with
the transfer-server hooks — TPU pods have them, the CPU test backend does
not (the capability probe returns False here, and that clean refusal is
itself under test). The PROTOCOL — stage → descriptor over TCP control →
pull → inject, plus mixed-fleet fallback — is exercised with a fake plane
that moves arrays through an in-memory registry, exactly the seam the real
DevicePlane implements.
"""

import asyncio
import json

import numpy as np
import pytest

from dynamo_tpu.disagg.transfer import KvTransferClient, KvTransferServer


class FakePlaneRegistry:
    """Shared 'fabric': (addr, uuid) → arrays."""

    def __init__(self):
        self.staged = {}
        self.pulls = 0


class FakePlane:
    def __init__(self, registry, addr):
        self.registry = registry
        self._addr = addr
        self._uuid = 0

    def address(self):
        return self._addr

    def stage(self, arrays):
        self._uuid += 1
        self.registry.staged[(self._addr, self._uuid)] = [np.asarray(a) for a in arrays]
        specs = [{"shape": list(a.shape), "dtype": str(np.asarray(a).dtype)} for a in arrays]
        return self._uuid, specs

    def release(self, uid):
        self.registry.staged.pop((self._addr, uid), None)

    def pull(self, address, uid, specs):
        self.registry.pulls += 1
        return self.registry.staged[(address, uid)]


class FakeEngine:
    """Just enough engine for the transfer server: records injections and
    serves extractions."""

    def __init__(self):
        self.completed = []
        self.pages_k = np.arange(2 * 3 * 4, dtype=np.float32).reshape(2, 3, 4)
        self.pages_v = self.pages_k + 100

    def post(self, fn):
        fn()

    def complete_remote_prefill(self, request_id, first_token, block_ids, pages):
        self.completed.append((request_id, first_token, block_ids,
                              np.asarray(pages["k"]).copy(),
                              np.asarray(pages["v"]).copy()))

    def fail_remote_prefill(self, request_id, message):
        self.completed.append(("FAIL", request_id, message))

    def extract_blocks(self, ids, as_device=False):
        return {"k": self.pages_k, "v": self.pages_v}

    def block_hashes_of(self, ids):
        return [7] * len(ids)


def run(coro):
    return asyncio.run(coro)


def test_capability_probe_refuses_cleanly_on_cpu():
    from dynamo_tpu.disagg import device_transfer

    device_transfer._supported = None  # reset cache
    assert device_transfer.device_transfer_supported() is False
    assert device_transfer.make_device_plane() is None


def test_device_path_send_and_read():
    """Both ends have planes: bulk rides the fake fabric, control rides TCP,
    injection and hash validation behave exactly like the host path."""

    async def go():
        reg = FakePlaneRegistry()
        eng = FakeEngine()
        server = KvTransferServer(
            eng, host="127.0.0.1", port=0, device_plane=FakePlane(reg, "dev-decode")
        )
        await server.start()
        client = KvTransferClient(device_plane=FakePlane(reg, "dev-prefill"))
        addr = f"127.0.0.1:{server.port}"

        k = np.ones((2, 2, 4), np.float32)
        v = k * 2
        await client.send_blocks(addr, "req-1", 42, [5, 6], {"k": k, "v": v})
        assert len(eng.completed) == 1
        rid, tok, ids, got_k, got_v = eng.completed[0]
        assert (rid, tok, ids) == ("req-1", 42, [5, 6])
        assert np.array_equal(got_k, k) and np.array_equal(got_v, v)

        read, hashes = await client.read_blocks(addr, [1, 2, 3])
        assert set(read) == {"k", "v"}
        assert np.array_equal(np.asarray(read["k"]), eng.pages_k)
        assert hashes == [7, 7, 7]
        assert reg.pulls == 2  # one per direction — the bulk used the fabric
        assert not reg.staged or len(reg.staged) <= 1  # send released its stage

        await client.close()
        await server.stop()

    run(go())


def test_mixed_fleet_falls_back_to_tcp():
    """Client has a plane, server doesn't: first attempt is refused, the
    call transparently retries host-staged, and the peer is remembered."""

    async def go():
        reg = FakePlaneRegistry()
        eng = FakeEngine()
        server = KvTransferServer(eng, host="127.0.0.1", port=0)  # no plane
        await server.start()
        client = KvTransferClient(device_plane=FakePlane(reg, "dev-prefill"))
        addr = f"127.0.0.1:{server.port}"

        k = np.ones((2, 2, 4), np.float32)
        await client.send_blocks(addr, "req-2", 9, [1], {"k": k, "v": k})
        assert eng.completed and eng.completed[0][0] == "req-2"
        assert reg.pulls == 0  # fabric never used
        assert client._dev_peers[addr] is False  # remembered: no retry storm

        read, hashes = await client.read_blocks(addr, [1, 2, 3])
        assert set(read) == {"k", "v"}
        assert np.array_equal(read["k"], eng.pages_k)

        await client.close()
        await server.stop()

    run(go())
