"""Multihost SERVING e2e (VERDICT r3 item 3): the real engine step loop over
a 2-process global mesh, leader driving dispatch, both hosts holding tp
shards — greedy tokens identical to a single-process engine run.

Two fresh CPU subprocesses join one jax.distributed coordinator (the same
path `cli/run.py --num-nodes/--node-rank/--coordinator-addr` uses), build a
global tp=2 mesh (one device per host), shard the params across processes,
and serve: rank 0 runs JaxServingEngine + LeaderBroadcaster, rank 1 runs
follower_serve. The parent compares rank 0's streamed tokens with a
single-process engine on the same params.
"""

import json

import pytest

from .fixtures import run_ranks

_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)
import jax
jax.config.update("jax_platforms", "cpu")

import argparse, asyncio, dataclasses, json
import jax.numpy as jnp

from dynamo_tpu.cli.run import init_multihost

rank = int(sys.argv[1])
addr = sys.argv[2]
flags = argparse.Namespace(num_nodes=2, node_rank=rank, coordinator_addr=addr)
init_multihost(flags)
assert jax.process_count() == 2 and jax.device_count() == 2

from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest, SamplingOptions, StopConditions,
)
from dynamo_tpu.models.llama import LLAMA_PRESETS, init_params
from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh
from dynamo_tpu.parallel.multihost_serving import (
    LeaderBroadcaster, follower_serve, shard_params_global,
)
from dynamo_tpu.runtime.engine import Context

cfg = dataclasses.replace(LLAMA_PRESETS["tiny"], dtype=jnp.float32)
params = init_params(jax.random.PRNGKey(0), cfg)  # identical on both ranks
mesh = make_mesh(MeshConfig(tp=2))
gparams = shard_params_global(params, cfg, mesh)
ec = EngineConfig(
    max_slots=2, kv_block_size=8, max_model_len=64,
    prefill_chunk=16, decode_steps=4,
)

PROMPTS = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8, 2]]
# full sampling surface through lockstep (VERDICT r4 item 3): logprobs +
# frequency/presence penalties ride the descriptors like any other request
LP_PROMPT = [6, 2, 4, 4, 1]

def lp_request():
    return PreprocessedRequest(
        token_ids=LP_PROMPT,
        stop_conditions=StopConditions(max_tokens=6, ignore_eos=True),
        sampling_options=SamplingOptions(
            temperature=0.0, logprobs=2,
            frequency_penalty=0.7, presence_penalty=0.3,
        ),
    )

if rank == 0:
    eng = JaxServingEngine(cfg, gparams, ec, mesh=mesh)
    eng.warmup()  # lockstep with follower_serve's warmup
    hook = LeaderBroadcaster(eng)
    eng._dispatch_hook = hook

    async def one(prompt):
        req = PreprocessedRequest(
            token_ids=prompt,
            stop_conditions=StopConditions(max_tokens=6, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0),
        )
        toks = []
        async for item in eng.generate(Context(req)):
            toks.extend((item.data or {}).get("token_ids", []))
        return toks

    async def one_lp():
        toks, lps = [], []
        async for item in eng.generate(Context(lp_request())):
            d = item.data or {}
            toks.extend(d.get("token_ids", []))
            lps.extend(d.get("log_probs") or [])
        return toks, lps

    async def main():
        # sequential: the lockstep protocol serializes dispatches anyway
        res = [await one(p) for p in PROMPTS]
        lp = await one_lp()
        return res, lp

    results, (lp_toks, lp_vals) = asyncio.run(main())
    eng.close()
    hook.shutdown()
    print("TOKENS " + json.dumps(results))
    print("LPTOKS " + json.dumps(lp_toks))
    print("LPVALS " + json.dumps([round(v, 4) for v in lp_vals]))
else:
    follower_serve(cfg, gparams, ec, mesh)
    print("FOLLOWER DONE")
"""


@pytest.mark.timeout(300)
def test_multihost_serving_matches_single_process(tmp_path):
    # reference: the same prompts on a plain single-process engine
    import dataclasses

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
    from dynamo_tpu.models.llama import LLAMA_PRESETS, init_params

    cfg = dataclasses.replace(LLAMA_PRESETS["tiny"], dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ec = EngineConfig(
        max_slots=2, kv_block_size=8, max_model_len=64,
        prefill_chunk=16, decode_steps=4,
    )
    eng = JaxServingEngine(cfg, params, ec)

    import asyncio

    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context

    prompts = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8, 2]]

    async def one(prompt):
        req = PreprocessedRequest(
            token_ids=prompt,
            stop_conditions=StopConditions(max_tokens=6, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0),
        )
        toks = []
        async for item in eng.generate(Context(req)):
            toks.extend((item.data or {}).get("token_ids", []))
        return toks

    lp_prompt = [6, 2, 4, 4, 1]

    async def one_lp():
        req = PreprocessedRequest(
            token_ids=lp_prompt,
            stop_conditions=StopConditions(max_tokens=6, ignore_eos=True),
            sampling_options=SamplingOptions(
                temperature=0.0, logprobs=2,
                frequency_penalty=0.7, presence_penalty=0.3,
            ),
        )
        toks, lps = [], []
        async for item in eng.generate(Context(req)):
            d = item.data or {}
            toks.extend(d.get("token_ids", []))
            lps.extend(d.get("log_probs") or [])
        return toks, lps

    expected = [asyncio.run(one(p)) for p in prompts]
    exp_lp_toks, exp_lp_vals = asyncio.run(one_lp())
    eng.close()
    assert all(len(t) == 6 for t in expected)
    assert len(exp_lp_toks) == 6 and len(exp_lp_vals) == 6

    # two-process serve over the global mesh
    script = tmp_path / "serve_worker.py"
    script.write_text(_WORKER)
    outs = run_ranks(script, n_ranks=2, deadline_s=240)
    assert "FOLLOWER DONE" in outs[1], outs[1]

    line = next(l for l in outs[0].splitlines() if l.startswith("TOKENS "))
    got = json.loads(line[len("TOKENS "):])
    assert got == expected, f"multihost {got} != single-process {expected}"

    # full sampling surface (VERDICT r4 item 3): the logprobs+penalties
    # request serves through lockstep with token AND logprob parity
    lp_line = next(l for l in outs[0].splitlines() if l.startswith("LPTOKS "))
    got_lp_toks = json.loads(lp_line[len("LPTOKS "):])
    assert got_lp_toks == exp_lp_toks, (got_lp_toks, exp_lp_toks)
    lv_line = next(l for l in outs[0].splitlines() if l.startswith("LPVALS "))
    got_lp_vals = json.loads(lv_line[len("LPVALS "):])
    assert len(got_lp_vals) == 6
    for a, b in zip(got_lp_vals, exp_lp_vals):
        assert abs(a - b) < 1e-3, (got_lp_vals, exp_lp_vals)
