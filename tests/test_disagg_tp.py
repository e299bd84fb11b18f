"""Cross-TP disaggregated transfer: prefill tp=1 → decode tp=2.

The decisive assertion: a tp=2-sharded decode engine fed KV pages computed
by an unsharded prefill engine produces exactly the same greedy tokens as
an unsharded local engine — over BOTH transfer paths:

- host-staged (numpy pages; relayout is implicit because the host array is
  the canonical unsharded layout), and
- the same-host device path (jax arrays; XLA reshards across the meshes at
  the inject boundary — the TP split/merge the reference needed a custom
  kernel for, `kv_rearrange.py`, SURVEY.md §2.10).
"""

import asyncio
import dataclasses
import threading

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.disagg.prefill_worker import PrefillEngine
from dynamo_tpu.disagg.transfer import LocalKvTransfer
from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models.llama import LLAMA_PRESETS, init_params, param_shardings
from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh
from dynamo_tpu.runtime.engine import Context

BLOCK = 8
CFG = dataclasses.replace(LLAMA_PRESETS["tiny"], dtype=jnp.float32)
ENGINE_CFG = EngineConfig(max_slots=2, kv_block_size=BLOCK, max_model_len=128)


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


def _tp2_engine(params):
    mesh = make_mesh(MeshConfig(tp=2))
    sharded = jax.device_put(params, param_shardings(CFG, mesh))
    return JaxServingEngine(
        CFG, sharded, ENGINE_CFG, mesh=mesh, cache_dtype=jnp.float32
    )


def test_inprocess_disagg_uses_device_path(params, run):
    """The full disagg stack (queue + prefill worker) takes the device path
    automatically when decode and prefill share a process, with parity."""
    import logging

    from dynamo_tpu.disagg.protocols import DisaggConfig
    from dynamo_tpu.disagg.prefill_worker import run_prefill_worker
    from dynamo_tpu.disagg.serving import LOCAL_DECODE_ENGINES, enable_disagg_decode
    from dynamo_tpu.runtime.bus import MessageBusServer
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.statestore import StateStoreServer

    async def go():
        ss, bus = StateStoreServer(port=0), MessageBusServer(port=0)
        await ss.start()
        await bus.start()
        rt = await DistributedRuntime.create(ss.url, bus.url)

        local = JaxServingEngine(CFG, params, ENGINE_CFG, cache_dtype=jnp.float32)
        prompt = list(range(5, 45))
        golden = await _collect(local, prompt)
        local.close()

        decode = JaxServingEngine(CFG, params, ENGINE_CFG, cache_dtype=jnp.float32)
        ep = rt.namespace("dloc").component("decode").endpoint("gen")
        await enable_disagg_decode(
            ep, decode, "dec-1",
            config=DisaggConfig(max_local_prefill_length=8, max_prefill_queue_size=10),
        )
        assert rt.worker_id in LOCAL_DECODE_ENGINES  # device path armed

        pre_engine = PrefillEngine(CFG, params, max_model_len=128, block_size=BLOCK)
        records = []
        handler = logging.Handler()
        handler.emit = lambda rec: records.append(rec.getMessage())
        plog = logging.getLogger("dynamo_tpu.disagg.prefill_worker")
        plog.addHandler(handler)
        plog.setLevel(logging.INFO)
        worker = asyncio.create_task(run_prefill_worker(rt, "dloc", pre_engine))
        try:
            toks = await asyncio.wait_for(_collect(decode, prompt), 60)
            assert toks == golden
            assert any("device path" in m for m in records), (
                "in-process disagg did not take the device path"
            )
        finally:
            worker.cancel()
            LOCAL_DECODE_ENGINES.clear()
            decode.close()
            await rt.shutdown()
            await ss.stop()
            await bus.stop()

    run(go())


@pytest.mark.parametrize("device_path", [False, True])
def test_tp1_prefill_feeds_tp2_decode(params, run, device_path):
    prompt = list(range(3, 43))  # 40 tokens → 5 blocks

    # golden: plain unsharded local engine
    local = JaxServingEngine(CFG, params, ENGINE_CFG, cache_dtype=jnp.float32)
    golden = run(_collect(local, prompt))
    local.close()

    decode = _tp2_engine(params)  # decode mesh = devices [0, 1]
    # split-chip deployment: the prefill engine lives on a chip OUTSIDE the
    # decode mesh — the transfer must move pages across committed device sets
    prefill_params = (
        jax.device_put(params, jax.devices()[4]) if device_path else params
    )
    prefill = PrefillEngine(CFG, prefill_params, max_model_len=128, block_size=BLOCK)
    policy = ForcedRemotePolicy()
    decode.set_remote_prefill_policy(policy)

    async def go():
        task = asyncio.create_task(_collect(decode, prompt))
        await asyncio.to_thread(policy.submitted.wait, 10.0)
        sub = policy.request
        assert sub is not None, "engine never submitted the remote prefill"

        first_tok, pages = prefill.prefill(
            sub["token_ids"], sub["cached_tokens"], sub["sampling"],
            as_device=device_path,
        )
        if device_path:
            assert isinstance(pages["k"], jax.Array)
            xfer = LocalKvTransfer(decode)
            await xfer.send_blocks(
                "", sub["request_id"], first_tok, sub["block_ids"], pages
            )
        else:
            import numpy as np

            assert isinstance(pages["k"], np.ndarray)
            decode.complete_remote_prefill(
                sub["request_id"], first_tok, sub["block_ids"], pages
            )
        return await task

    toks = run(go())
    decode.close()
    assert toks == golden, (
        f"cross-TP disagg diverged ({'device' if device_path else 'host'} path)"
    )
