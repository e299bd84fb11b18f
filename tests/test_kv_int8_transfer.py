"""int8 KV blocks + scale tables through the disagg transfer plane.

Covers (ISSUE 7 acceptance): quantize/dequantize round-trip accuracy, the
TCP and local/device transfer paths carrying dtype+scales end to end with
greedy parity, and the dtype-skew case — a peer without int8 support (or a
native frame landing in an int8 pool) must surface a clean typed error and
a local-prefill fallback, never corrupt pages.
"""

import asyncio
import dataclasses
import logging
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.disagg.prefill_worker import PrefillEngine
from dynamo_tpu.disagg.transfer import (
    KvDtypeMismatch,
    KvTransferClient,
    KvTransferServer,
    LocalKvTransfer,
)
from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
from dynamo_tpu.kv import pages as kv_pages
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models.llama import (
    LLAMA_PRESETS,
    dequantize_kv,
    init_params,
    quantize_kv,
)
from dynamo_tpu.runtime.engine import Context

BLOCK = 8
CFG = dataclasses.replace(LLAMA_PRESETS["tiny"], dtype=jnp.float32)
INT8_CFG = EngineConfig(
    max_slots=2, kv_block_size=BLOCK, max_model_len=128, kv_dtype="int8"
)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


class ForcedRemotePolicy:
    """Route every prefill remote; capture the submit for the test driver."""

    def __init__(self):
        self.submitted = threading.Event()
        self.request = None

    def should_remote(self, uncached_len: int) -> bool:
        return True

    def submit(self, request_id, token_ids, block_ids, cached_tokens, sampling,
               **kw):
        self.request = dict(
            request_id=request_id, token_ids=token_ids, block_ids=block_ids,
            cached_tokens=cached_tokens, sampling=sampling, **kw,
        )
        self.submitted.set()


async def _collect(engine, prompt, max_tokens=5):
    req = PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0),
    )
    toks = []
    async for item in engine.generate(Context(req)):
        if item.is_error:
            raise AssertionError(item.error_message())
        toks.extend((item.data or {}).get("token_ids", []))
    return toks


def test_quantize_dequantize_round_trip_accuracy():
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.normal(size=(2, 8, 2, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 8, 2, 16)).astype(np.float32))
    kq, vq, ks, vs = quantize_kv(k, v)
    assert kq.dtype == jnp.int8 and ks.shape == (2, 8)
    kd = dequantize_kv(kq, ks, jnp.float32)
    # per-token absmax: reconstruction error bounded by half a scale step
    err = np.abs(np.asarray(kd) - np.asarray(k))
    bound = np.asarray(ks)[..., None, None] * 0.51
    assert (err <= bound).all()
    # all-zero rows (padding lanes) must round-trip exactly
    z = jnp.zeros((1, 4, 2, 16), jnp.float32)
    zq, _, zs, _ = quantize_kv(z, z)
    assert np.asarray(dequantize_kv(zq, zs, jnp.float32)).max() == 0.0


def test_int8_disagg_tcp_round_trip(params, run):
    """Prefill and decode engines both int8: pages + scale tables ride the
    framed TCP path (send_blocks AND read_blocks) with exact greedy parity
    against an aggregated int8 engine."""

    async def go():
        local = JaxServingEngine(CFG, params, INT8_CFG)
        prompt = list(range(3, 43))
        golden = await _collect(local, prompt)
        local.close()

        decode = JaxServingEngine(CFG, params, INT8_CFG)
        policy = ForcedRemotePolicy()
        decode.set_remote_prefill_policy(policy)
        server = KvTransferServer(decode, host="127.0.0.1", port=0)
        await server.start()
        addr = f"127.0.0.1:{server.port}"
        prefill = PrefillEngine(
            CFG, params, max_model_len=128, block_size=BLOCK,
        )
        # the prefill engine reads DYN_TPU_KV_DTYPE at construction; build
        # its int8 twin explicitly instead (config wins over env)
        prefill.engine.close()
        prefill.engine = JaxServingEngine(
            CFG, params,
            EngineConfig(
                max_slots=4, kv_block_size=BLOCK, max_model_len=128,
                decode_steps=1, prefill_chunk=128, kv_dtype="int8",
            ),
        )
        client = KvTransferClient()
        try:
            task = asyncio.create_task(_collect(decode, prompt))
            await asyncio.to_thread(policy.submitted.wait, 10.0)
            sub = policy.request
            assert sub is not None

            tok, pages, _ = await prefill.prefill_request(
                sub["token_ids"], sub["cached_tokens"], sub["sampling"]
            )
            assert pages["k"].dtype == np.int8
            assert pages["k_scale"].dtype == np.float32
            await client.send_blocks(
                addr, sub["request_id"], tok, sub["block_ids"], pages
            )
            toks = await asyncio.wait_for(task, 30)
            assert toks == golden

            # read the decode side's pages back over TCP: values AND scales
            read, hashes = await client.read_blocks(
                addr, sub["block_ids"][:2]
            )
            assert read["k"].dtype == np.int8
            assert set(read) == set(pages)
            for m in ("k", "k_scale"):
                np.testing.assert_array_equal(
                    np.asarray(read[m]), np.asarray(pages[m])[:, :2]
                )
        finally:
            await client.close()
            await server.stop()
            prefill.close()
            decode.close()

    run(go())


def test_int8_local_transfer_round_trip(params, run):
    """Same-host device path (LocalKvTransfer): jax pages + scales move
    without host staging, with greedy parity."""

    async def go():
        local = JaxServingEngine(CFG, params, INT8_CFG)
        prompt = list(range(5, 45))
        golden = await _collect(local, prompt)
        local.close()

        decode = JaxServingEngine(CFG, params, INT8_CFG)
        policy = ForcedRemotePolicy()
        decode.set_remote_prefill_policy(policy)
        prefill_eng = JaxServingEngine(
            CFG, params,
            EngineConfig(
                max_slots=2, kv_block_size=BLOCK, max_model_len=128,
                prefill_chunk=128, kv_dtype="int8",
            ),
        )
        try:
            task = asyncio.create_task(_collect(decode, prompt))
            await asyncio.to_thread(policy.submitted.wait, 10.0)
            sub = policy.request

            # compute the prompt on the prefill engine and extract pages +
            # scales as device arrays via the held-pages path
            prefill = PrefillEngine.__new__(PrefillEngine)
            prefill.model_config = CFG
            prefill.block_size = BLOCK
            prefill.model = ""
            prefill.max_model_len = 128
            prefill.engine = prefill_eng
            prefill._computed = {}
            prefill.last_computed_tokens = -1
            tok, pages, _ = await prefill.prefill_request(
                sub["token_ids"], sub["cached_tokens"], sub["sampling"],
                as_device=True,
            )
            assert isinstance(pages["k"], jax.Array) and "k_scale" in pages
            xfer = LocalKvTransfer(decode)
            await xfer.send_blocks(
                "", sub["request_id"], tok, sub["block_ids"], pages
            )
            toks = await asyncio.wait_for(task, 30)
            assert toks == golden

            # device-path read-back returns scales too
            read, hashes = await xfer.read_blocks("", sub["block_ids"][:1])
            assert "k_scale" in read and isinstance(read["k"], jax.Array)
        finally:
            prefill_eng.close()
            decode.close()

    run(go())


def test_native_frame_into_int8_pool_falls_back_cleanly(params, run, caplog):
    """A peer without dtype support (native pages, no scales) shipping into
    an int8 pool: the decode engine must emit a clean typed fallback — the
    request completes via local prefill with correct output — and never
    write the mismatched bytes."""

    async def go():
        local = JaxServingEngine(CFG, params, INT8_CFG)
        prompt = list(range(7, 47))
        golden = await _collect(local, prompt)
        local.close()

        decode = JaxServingEngine(CFG, params, INT8_CFG)
        policy = ForcedRemotePolicy()
        decode.set_remote_prefill_policy(policy)
        # native (pre-int8) prefill engine — the "old peer"
        prefill = PrefillEngine(CFG, params, max_model_len=128, block_size=BLOCK)
        try:
            task = asyncio.create_task(_collect(decode, prompt))
            await asyncio.to_thread(policy.submitted.wait, 10.0)
            sub = policy.request
            tok, pages, _ = await prefill.prefill_request(
                sub["token_ids"], sub["cached_tokens"], sub["sampling"]
            )
            assert set(pages) == {"k", "v"}  # native pool: no scale tables
            with caplog.at_level(logging.ERROR, "dynamo_tpu.engine_jax.engine"):
                decode.complete_remote_prefill(
                    sub["request_id"], tok, sub["block_ids"], pages
                )
                toks = await asyncio.wait_for(task, 30)
            # fell back to LOCAL prefill → exact int8-engine output
            assert toks == golden
            assert any("kv_dtype" in r.message for r in caplog.records)
        finally:
            prefill.close()
            decode.close()

    run(go())


def test_prefill_death_mid_transfer_never_tears_a_page(params, run):
    """ISSUE 11 satellite: the prefill worker dies MID-FRAME while shipping
    pages (partial bytes on the wire, then the socket closes). The framed
    codec makes the torn frame unparseable — complete_remote_prefill must
    never fire with it — and the decode side recovers via its remote
    timeout into a clean local prefill with exact greedy parity."""
    import json

    from dynamo_tpu.runtime.codec import TwoPartMessage, encode

    async def go():
        local = JaxServingEngine(CFG, params, INT8_CFG)
        prompt = list(range(9, 49))
        golden = await _collect(local, prompt)
        local.close()

        fast_cfg = dataclasses.replace(INT8_CFG, remote_prefill_timeout=1.5)
        decode = JaxServingEngine(CFG, params, fast_cfg)
        completions = []
        real_complete = decode.complete_remote_prefill
        decode.complete_remote_prefill = (
            lambda *a, **kw: (completions.append(a), real_complete(*a, **kw))
        )
        policy = ForcedRemotePolicy()
        decode.set_remote_prefill_policy(policy)
        server = KvTransferServer(decode, host="127.0.0.1", port=0)
        await server.start()
        try:
            task = asyncio.create_task(_collect(decode, prompt))
            await asyncio.to_thread(policy.submitted.wait, 10.0)
            sub = policy.request
            assert sub is not None

            # a plausible kv_blocks frame, cut mid-body: the dying worker's
            # last TCP segment
            header = json.dumps({
                "op": "kv_blocks", "request_id": sub["request_id"],
                "first_token": 1, "block_ids": sub["block_ids"],
                "dtype": "int8", "shape": [1, 1, BLOCK, 1, 4],
                "k_bytes": 4096, "kv_dtype": "int8",
                "scale_dtype": "float32", "scale_shape": [1, 1, BLOCK],
                "ks_bytes": 64,
            }).encode()
            frame = encode(TwoPartMessage(header, b"\x01" * (2 * 4096 + 128)))
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", server.port
            )
            writer.write(frame[: len(frame) // 2])
            await writer.drain()
            writer.close()  # worker process gone

            toks = await asyncio.wait_for(task, 30)
            assert toks == golden, "local-prefill fallback must be exact"
            assert completions == [], (
                "a torn frame must never reach complete_remote_prefill"
            )
        finally:
            await server.stop()
            decode.close()

    run(go())


def test_send_blocks_transport_failure_then_typed_fallback(params, run):
    """The prefill side's send dies at the transport (injected reset on the
    transfer plane); after its retries it reports the failure in-band via
    send_failure, and the decode request falls back to local prefill
    immediately — no torn page, exact output, no timeout wait."""
    from dynamo_tpu.runtime import faults as faults_mod
    from dynamo_tpu.runtime.faults import FaultInjector, FaultRule

    async def go():
        local = JaxServingEngine(CFG, params, INT8_CFG)
        prompt = list(range(11, 51))
        golden = await _collect(local, prompt)
        local.close()

        decode = JaxServingEngine(CFG, params, INT8_CFG)
        policy = ForcedRemotePolicy()
        decode.set_remote_prefill_policy(policy)
        server = KvTransferServer(decode, host="127.0.0.1", port=0)
        await server.start()
        addr = f"127.0.0.1:{server.port}"
        prefill = PrefillEngine(CFG, params, max_model_len=128,
                                block_size=BLOCK)
        prefill.engine.close()
        prefill.engine = JaxServingEngine(
            CFG, params,
            EngineConfig(
                max_slots=4, kv_block_size=BLOCK, max_model_len=128,
                decode_steps=1, prefill_chunk=128, kv_dtype="int8",
            ),
        )
        client = KvTransferClient()
        try:
            task = asyncio.create_task(_collect(decode, prompt))
            await asyncio.to_thread(policy.submitted.wait, 10.0)
            sub = policy.request
            tok, pages, _ = await prefill.prefill_request(
                sub["token_ids"], sub["cached_tokens"], sub["sampling"]
            )
            inj = FaultInjector([FaultRule(
                plane="transfer", point="write", action="reset",
            )])
            with faults_mod.active(inj):
                with pytest.raises((ConnectionError, OSError)):
                    await client.send_blocks(
                        addr, sub["request_id"], tok, sub["block_ids"], pages
                    )
            # retries exhausted: the worker reports in-band (fresh dial —
            # the failed conn was identity-evicted by send_blocks)
            await client.send_failure(
                addr, sub["request_id"], "injected transport death"
            )
            toks = await asyncio.wait_for(task, 30)
            assert toks == golden
        finally:
            await client.close()
            await server.stop()
            prefill.close()
            decode.close()

    run(go())


def test_inject_blocks_dtype_mismatch_is_typed(params):
    int8_eng = JaxServingEngine(CFG, params, INT8_CFG)
    native_eng = JaxServingEngine(
        CFG, params,
        EngineConfig(max_slots=2, kv_block_size=BLOCK, max_model_len=128),
    )
    try:
        page = np.zeros((CFG.num_layers, 1, BLOCK, CFG.num_kv_heads,
                         CFG.head_dim), np.float32)
        scales = np.ones((CFG.num_layers, 1, BLOCK), np.float32)
        native = {"k": page, "v": page}
        with pytest.raises(KvDtypeMismatch):
            int8_eng.inject_blocks([0], native)  # scales missing
        with pytest.raises(KvDtypeMismatch):
            native_eng.inject_blocks(
                [0], dict(native, k_scale=scales, v_scale=scales)
            )
        with pytest.raises(KvDtypeMismatch):
            int8_eng.seed_external_prefix(list(range(BLOCK)), native)
    finally:
        int8_eng.close()
        native_eng.close()


def test_pre_int8_peer_read_refused_typed(params, run):
    """A pre-int8 peer (no ``int8_ok`` marker in its read request) asking an
    int8 pool for pages gets a typed ok=False refusal on BOTH the TCP and
    device read ops — never a 4-segment body its fixed 2-segment unpack
    would misparse (TCP), and never a 4-array stage it would inject as
    native KV (device). A current client advertising the capability still
    reads the same pool fine."""
    import json

    from dynamo_tpu.kv.tokens import compute_block_hashes_for_seq
    from dynamo_tpu.runtime.codec import (
        TwoPartMessage,
        read_frame,
        write_frame,
    )

    async def go():
        decode = JaxServingEngine(CFG, params, INT8_CFG)
        prompt = list(range(2, 34))
        await _collect(decode, prompt, max_tokens=1)
        hashes = compute_block_hashes_for_seq(prompt[:24], BLOCK)
        block_ids = [decode.allocator._by_hash[h] for h in hashes]
        # refusal happens before staging, so any non-None plane marker works
        server = KvTransferServer(
            decode, host="127.0.0.1", port=0, device_plane=object()
        )
        await server.start()
        addr = f"127.0.0.1:{server.port}"
        try:
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", server.port
            )
            for op in ("read_blocks", "read_blocks_dev"):
                await write_frame(writer, TwoPartMessage(json.dumps(
                    {"op": op, "block_ids": block_ids}
                ).encode(), b""))
                h = json.loads((await read_frame(reader)).header)
                assert h["ok"] is False and "int8" in h["error"], op
            writer.close()

            client = KvTransferClient()
            try:
                read, _ = await client.read_blocks(addr, block_ids)
                assert read["k"].dtype == np.int8 and "k_scale" in read
                assert client._int8_peers[addr] is True
            finally:
                await client.close()
        finally:
            await server.stop()
            decode.close()

    run(go())


class _RecordingEngine:
    """Stands in for a decode engine behind KvTransferServer: records
    complete_remote_prefill calls, needs no device."""

    def __init__(self):
        self.calls = []

    def complete_remote_prefill(self, *a):
        self.calls.append(a)


def test_int8_send_avoids_device_plane_until_peer_proven(run):
    """int8 page sets must not ride the device plane to a peer that has not
    proven scale-table support — a pre-int8 peer would pull the 4-array
    stage, keep [k, v], and inject raw int8 values as native KV. The first
    int8 transfer goes TCP (loud failure on old peers), its ack teaches the
    capability, and only then does the device path open up. Native page
    sets are ungated."""

    async def go():
        eng = _RecordingEngine()
        server = KvTransferServer(eng, host="127.0.0.1", port=0)
        await server.start()
        addr = f"127.0.0.1:{server.port}"
        client = KvTransferClient(device_plane=object())
        dev_calls = []

        async def fake_dev(*a, **kw):
            dev_calls.append(a)

        client._send_blocks_dev = fake_dev
        k = np.zeros((1, 1, BLOCK, 1, 4), np.int8)
        scale = np.ones((1, 1, BLOCK), np.float32)
        int8 = {"k": k, "v": k, "k_scale": scale, "v_scale": scale}
        try:
            # unproven peer + int8 scales → TCP, not the device plane
            await client.send_blocks(addr, "r1", 1, [0], int8)
            assert not dev_calls and len(eng.calls) == 1
            assert client._int8_peers.get(addr) is True
            # capability proven → device plane
            await client.send_blocks(addr, "r2", 1, [0], int8)
            assert len(dev_calls) == 1
            # native pages were never gated on the capability
            client._int8_peers.clear()
            f32 = k.astype(np.float32)
            await client.send_blocks(addr, "r3", 1, [0], {"k": f32, "v": f32})
            assert len(dev_calls) == 2
        finally:
            await client.close()
            await server.stop()

    run(go())


def test_dtype_skew_prefix_readback_recomputes_not_fails(params, run, caplog):
    """Rolling-upgrade skew: int8 prefix pages read back from the decode
    fleet land at a NATIVE prefill engine. The seed is unusable
    (KvDtypeMismatch), but the prompt is not — prefill_request must
    recompute the full prompt and answer, never fail the remote prefill
    (which would silently disable disaggregation for every prefix-hit
    request until the skew is noticed)."""

    async def go():
        decode = JaxServingEngine(CFG, params, INT8_CFG)
        prompt = list(range(2, 34))
        await _collect(decode, prompt, max_tokens=1)
        from dynamo_tpu.kv.tokens import compute_block_hashes_for_seq

        hashes = compute_block_hashes_for_seq(prompt[:24], BLOCK)
        block_ids = [decode.allocator._by_hash[h] for h in hashes]
        read, _ = await LocalKvTransfer(decode).read_blocks("", block_ids)
        assert "k_scale" in read
        decode.close()

        golden = JaxServingEngine(CFG, params, dataclasses.replace(
            INT8_CFG, kv_dtype=None))
        want = await _collect(golden, prompt, max_tokens=1)
        golden.close()

        # native prefill engine handed int8 pages + scales
        prefill = PrefillEngine(CFG, params, max_model_len=128,
                                block_size=BLOCK)
        try:
            with caplog.at_level(
                logging.WARNING, "dynamo_tpu.disagg.prefill_worker"
            ):
                tok, _, computed = await prefill.prefill_request(
                    prompt, 24, {}, prefix_kv=kv_pages.to_host(read),
                )
            assert tok == want[0]
            assert computed == len(prompt)  # full recompute, no seeded prefix
            assert any("recomputing full prompt" in r.message
                       for r in caplog.records)
        finally:
            prefill.close()

    run(go())


def test_int8_prefix_readback_seeds_prefill_engine(params, run):
    """Multi-turn shape: the prefix pages read back from an int8 decode
    worker (with scales) seed an int8 prefill engine's cache via
    seed_external_prefix — turn 2 computes only the suffix."""

    async def go():
        decode = JaxServingEngine(CFG, params, INT8_CFG)
        prompt = list(range(2, 34))  # 4 full blocks
        await _collect(decode, prompt, max_tokens=1)
        # pages for the 3 cacheable full blocks (last block holds the tail)
        from dynamo_tpu.kv.tokens import compute_block_hashes_for_seq

        hashes = compute_block_hashes_for_seq(prompt[:24], BLOCK)
        block_ids = [decode.allocator._by_hash[h] for h in hashes]
        xfer = LocalKvTransfer(decode)
        read, got_hashes = await xfer.read_blocks("", block_ids)
        assert "k_scale" in read
        assert list(got_hashes) == list(hashes)

        pre = JaxServingEngine(
            CFG, params,
            EngineConfig(max_slots=2, kv_block_size=BLOCK, max_model_len=128,
                         kv_dtype="int8"),
        )
        fut = asyncio.get_running_loop().create_future()

        def seed():
            fut.get_loop().call_soon_threadsafe(
                fut.set_result,
                pre.seed_external_prefix(prompt[:24], kv_pages.to_host(read)),
            )

        pre.post(seed)
        seeded = await asyncio.wait_for(fut, 10)
        assert seeded == 3
        # the seeded engine prefix-hits the injected blocks
        probe_before = pre.allocator.hit_tokens
        toks = await _collect(pre, prompt, max_tokens=3)
        assert pre.allocator.hit_tokens - probe_before >= 24
        golden = await _collect(decode, prompt, max_tokens=3)
        assert toks == golden
        pre.close()
        decode.close()

    run(go())
