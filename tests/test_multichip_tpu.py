"""Staged tests for paths that need ≥2 REAL TPU chips in one process.

The proof that the multi-chip main paths run on real chips is
``python3 chip_smoke.py --chips 4`` (run through the chip tool; see
docs/multihost_serving.md "Running on real chips"): mesh-sharded serving,
tp=4 Qwen2.5-7B through cli.run, four one-chip replicas behind the router.
This module keeps what that script does not reach. All tests are marked
``tpu`` and skip unless real multi-chip hardware is present — run with::

    DYN_TPU_TESTS_REAL=1 python -m pytest tests/test_multichip_tpu.py -m tpu -v

(the env var stops conftest from forcing the virtual CPU mesh). Covers, in
dependency order:

1. device-plane probe + one real chip-to-chip KV pull
   (disagg/device_transfer.py has only ever run against fakes off-TPU);
2. sharded int8 decode on a real tp mesh;
3. a 2-chip disaggregated serve: prefill engine and decode engine on
   DIFFERENT chips, KV over the device plane.
"""

import asyncio
import dataclasses
import os

import pytest

pytestmark = pytest.mark.tpu


def _real_chips() -> int:
    if os.environ.get("DYN_TPU_TESTS_REAL") != "1":
        return 0
    import jax

    try:
        return len([d for d in jax.devices() if d.platform == "tpu"])
    except Exception:
        return 0


needs_two_chips = pytest.mark.skipif(
    _real_chips() < 2, reason="needs >=2 real TPU chips (DYN_TPU_TESTS_REAL=1)"
)


@needs_two_chips
def test_device_plane_probe_and_cross_chip_pull():
    """(a) The device transfer plane stages KV on chip 0 and pulls it onto
    chip 1 — the first real bytes over ICI for this plane."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.disagg.device_transfer import (
        DevicePlane,
        device_transfer_supported,
    )

    assert device_transfer_supported(), "device plane must probe TRUE on TPU"

    plane = DevicePlane()
    devs = [d for d in jax.devices() if d.platform == "tpu"]
    block = jax.device_put(
        jnp.arange(16 * 8 * 64, dtype=jnp.bfloat16).reshape(16, 8, 64), devs[0]
    )
    uid, specs = plane.stage([block])
    # pull into THIS process but onto the second chip: exercises the
    # cross-device PJRT path end to end
    out = plane.pull(plane.address(), uid, specs)
    np.testing.assert_array_equal(
        np.asarray(out[0], np.float32), np.asarray(block, np.float32)
    )


@needs_two_chips
def test_sharded_int8_decode_on_real_mesh():
    """(b) The headline serving mode (hybrid int8) on a REAL tp=2 mesh:
    greedy tokens must match the single-chip int8 engine exactly."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.models.llama import LLAMA_PRESETS, init_params, param_shardings
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh
    from dynamo_tpu.runtime.engine import Context

    cfg = dataclasses.replace(LLAMA_PRESETS["tiny"], dtype=jnp.bfloat16)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ec = EngineConfig(
        max_slots=4, kv_block_size=16, max_model_len=128, decode_steps=8,
        prefill_chunk=32, quantize="int8",
    )
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]

    async def serve(engine):
        req = PreprocessedRequest(
            token_ids=prompt,
            stop_conditions=StopConditions(max_tokens=8, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0),
        )
        toks = []
        async for item in engine.generate(Context(req)):
            toks.extend((item.data or {}).get("token_ids", []))
        return toks

    single = JaxServingEngine(cfg, params, ec)
    try:
        expected = asyncio.run(serve(single))
    finally:
        single.close()
    assert len(expected) == 8

    mesh = make_mesh(MeshConfig(tp=2))
    sharded = jax.device_put(params, param_shardings(cfg, mesh))
    eng = JaxServingEngine(cfg, sharded, ec, mesh=mesh)
    try:
        got = asyncio.run(serve(eng))
    finally:
        eng.close()
    assert got == expected


@needs_two_chips
def test_two_chip_disagg_serve_device_plane():
    """(c) Disaggregated serve with the prefill engine's arrays on chip 1
    and the decode engine on chip 0, KV moving over the device plane
    (statestore + bus + queue + worker: the full disagg stack)."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.disagg.prefill_worker import PrefillEngine, run_prefill_worker
    from dynamo_tpu.disagg.protocols import DisaggConfig
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

    cfg = dataclasses.replace(LLAMA_PRESETS["tiny"], dtype=jnp.bfloat16)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ec = EngineConfig(
        max_slots=4, kv_block_size=8, max_model_len=128, decode_steps=4,
        prefill_chunk=32,
    )

    async def collect(engine, prompt, max_tokens):
        req = PreprocessedRequest(
            token_ids=prompt,
            stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0),
        )
        toks = []
        async for item in engine.generate(Context(req)):
            toks.extend((item.data or {}).get("token_ids", []))
        return toks

    async def go():
        ss = StateStoreServer(port=0)
        bus = MessageBusServer(port=0)
        await ss.start()
        await bus.start()
        rt = await DistributedRuntime.create(ss.url, bus.url)

        prompt = list(range(3, 43))
        local = JaxServingEngine(cfg, params, ec)
        golden = await collect(local, prompt, max_tokens=5)
        local.close()

        decode = JaxServingEngine(cfg, params, ec)
        ep = rt.namespace("dz").component("decode").endpoint("gen")
        await enable_disagg_decode(
            ep, decode, "dec-1",
            config=DisaggConfig(
                max_local_prefill_length=8, max_prefill_queue_size=10
            ),
            register_local=False,
        )
        devs = [d for d in jax.devices() if d.platform == "tpu"]
        with jax.default_device(devs[1]):
            pre_engine = PrefillEngine(cfg, params, max_model_len=128, block_size=8)
        worker_task = asyncio.create_task(run_prefill_worker(rt, "dz", pre_engine))
        try:
            toks = await asyncio.wait_for(collect(decode, prompt, max_tokens=5), 120)
            assert toks == golden, f"2-chip disagg {toks} != local {golden}"
        finally:
            worker_task.cancel()
            decode.close()
            await rt.shutdown()
            await bus.stop()
            await ss.stop()

    asyncio.run(go())
