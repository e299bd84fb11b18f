"""The reader of ``chunk_token_fill_share``: on hand-made snapshots, and on the
snapshots of a tiny engine on the CPU, whose counters it is a reader of.

``BENCHMARK.json`` does not register it yet (PERF.md 7 says which line of an
accepted benchmark file stands in the way), so no run reports it: what is held
here is that the reader is ready for the entry."""

import dataclasses
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402

NAME = "chunk_token_fill_share"
OLD = {"request_active_slots": 32, "request_total_slots": 32, "kv_active_blocks": 9, "kv_total_blocks": 64}


def snap(fed, dispatched, **more):
    return OLD | {"chunk_tokens_fed": fed, "chunk_positions_dispatched": dispatched} | more


@pytest.mark.parametrize("samples, before, after, want", [
    # a program without the counters (the parent): nothing to read, no error
    ([OLD | {"t": 0.0}, OLD | {"t": 0.5}], OLD, OLD, None),
    ([], None, None, None),
    # samples that carry the counters: first to last of the window
    ([snap(100, 512, t=0.0), snap(130, 640, t=0.5), snap(292, 1024, t=1.0)], snap(0, 0), snap(999, 999), 37.5),
    # the sampler kept none of them: the snapshots at both ends of the run
    ([OLD | {"t": 0.0}, OLD | {"t": 0.5}], snap(64, 128), snap(704, 1408), 50.0),
    # one sample is no difference; no chunk dispatch ran
    ([snap(100, 512, t=0.0)], OLD, snap(704, 1408), None),
    ([snap(100, 512, t=0.0), snap(100, 512, t=0.5)], snap(100, 512), snap(100, 512), None),
], ids=["parent", "nothing", "samples", "both_ends", "one_sample", "no_dispatch"])
def test_the_share_is_the_rise_of_tokens_fed_over_the_rise_of_positions_dispatched(samples, before, after, want):
    reader = bench_run.load_readers("layer_metrics")[NAME]
    got = reader.read({"engine_samples": samples, "engine_before": before, "engine_after": after})
    assert got == want


def test_the_reader_fits_the_entry_a_benchmark_pr_registers_it_with():
    """Its layer is one ``BENCHMARK.json`` already names, it moves an
    end-to-end metric both cells on ``models/llama.py`` report, and an entry
    of its name, once there, is this one."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    reader = bench_run.load_readers("layer_metrics")[NAME]
    want = {"name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
            "layer": "model, prompt processing", "moves": "ttft_mean_ms",
            "workloads": ["batch.qwen2.5-1.5b", "batch.qwen2.5-7b-tp4"]}
    assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) == (
        want["name"], want["unit"], want["layer"], want["moves"])
    assert want["layer"] in {m["layer"] for m in bench["per_layer"] if m["name"] != NAME}
    assert want["moves"] in {m["name"] for m in bench["end_to_end"]}
    assert set(want["workloads"]) <= {w["name"] for w in bench["workloads"]}
    assert [m for m in bench["per_layer"] if m["name"] == NAME] in ([], [want])


def test_the_reader_reads_the_counters_the_engine_keeps():
    """Two prompts of 20 and 9 tokens, one after the other, in chunks of 16 on
    a tiny engine: three chunk dispatches of one row each feed 16 + 4 + 9
    tokens into 48 positions. A CPU run: the counters are the program's, no
    device metric."""
    import asyncio

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
    from dynamo_tpu.llm.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions
    from dynamo_tpu.models.llama import LLAMA_PRESETS, init_params
    from dynamo_tpu.runtime.engine import Context

    cfg = dataclasses.replace(LLAMA_PRESETS["tiny"], dtype=jnp.float32)
    eng = JaxServingEngine(cfg, init_params(jax.random.PRNGKey(0), cfg), EngineConfig(
        max_slots=8, kv_block_size=8, max_model_len=64, prefill_chunk=16))
    reader = bench_run.load_readers("layer_metrics")[NAME]

    async def serve(prompt):
        req = PreprocessedRequest(
            token_ids=prompt, stop_conditions=StopConditions(max_tokens=3, ignore_eos=True),
            sampling_options=SamplingOptions())
        return [item async for item in eng.generate(Context(req))]

    try:
        before = eng.metrics_snapshot()
        for prompt in ([(5 * i + 2) % 90 + 1 for i in range(20)], [(3 * i + 7) % 90 + 1 for i in range(9)]):
            assert asyncio.run(serve(prompt))
        after = json.loads(json.dumps(eng.metrics_snapshot()))  # as /debug/engine sends it
    finally:
        eng.close()
    assert (after["chunk_tokens_fed"], after["chunk_positions_dispatched"]) == (29, 48)
    assert after["chunk_dispatches_by_rows"] == {"1": 3}
    got = reader.read({"engine_samples": [], "engine_before": before, "engine_after": after})
    assert got == pytest.approx(100.0 * 29 / 48)
