"""The six readers of the engine thread's counters (PR 39): on hand-made
snapshots, against the entry each is written out with in PERF.md 7, and on the
snapshots of a tiny engine on the CPU, whose counters they are readers of.

``BENCHMARK.json`` registers none of them (PERF.md 7 says which line of an
accepted benchmark file stands in the way), so no run reports them: what is
held here is that each reader is ready for its entry."""

import dataclasses
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402

ALL_CELLS = ["batch.qwen2.5-1.5b", "batch.qwen2.5-7b-tp4", "batch.kimi-linear-48b-a3b"]
# the entries a `benchmark` PR registers them with (PERF.md 7), in this order
ENTRIES = [
    {"name": "host_starved_share", "unit": "%", "better": "lower", "source": "program_counter",
     "layer": "engine step loop", "moves": "ttft_mean_ms", "workloads": ALL_CELLS},
    {"name": "prefill_step_share", "unit": "%", "better": "lower", "source": "program_counter",
     "layer": "engine step loop", "moves": "ttft_mean_ms", "workloads": ALL_CELLS},
    {"name": "queue_wait_mean_ms", "unit": "ms", "better": "lower", "source": "program_counter",
     "layer": "engine step loop", "moves": "ttft_mean_ms", "workloads": ALL_CELLS},
    {"name": "prefix_hit_share", "unit": "%", "better": "higher", "source": "program_counter",
     "layer": "KV cache", "moves": "ttft_mean_ms", "workloads": ALL_CELLS},
    {"name": "seal_crc_host_share", "unit": "%", "better": "lower", "source": "program_counter",
     "layer": "KV cache", "moves": "ttft_mean_ms", "workloads": ALL_CELLS},
    {"name": "setup_python_s", "unit": "s", "better": "lower", "source": "program_counter",
     "layer": "HTTP frontend and engine host loop", "moves": "setup_s", "workloads": ALL_CELLS},
]
OLD = {"request_active_slots": 32, "request_total_slots": 32, "kv_active_blocks": 9, "kv_total_blocks": 64}


def snap(uptime, *, starved=(0, 0), crc=0, steps=(0, 0), waited=(0.0, 0), prefix=(0, 0), setup=None):
    """A /debug/engine snapshot with the counters of runtime/profiling.py:PhaseClock."""
    return OLD | {
        "uptime_us": uptime,
        "host_phase_us": {"step": 5, "wait": 0, "decode_build": uptime - crc - 5, "seal_crc": crc},
        "host_starved_us": {"step": 0, "wait": 0, "decode_build": starved[0], "seal_crc": starved[1]},
        "host_steps": {"prefill": steps[0], "decode": steps[1]},
        "queue_wait_us_sum": waited[0], "queue_wait_count": waited[1],
        "prefix_hit_tokens": prefix[0], "prefix_probe_tokens": prefix[1],
        "setup_phase_s": setup or {"before_main": 1.25, "devices": 3.0, "weights": 2.0, "engine": 1.0,
                                   "lower": 4.5, "compile": 6.0, "sealing": 0.5, "http": 0.25},
    }


BEFORE = snap(1_000_000, starved=(100_000, 20_000), crc=50_000, steps=(10, 30), waited=(40_000.0, 4), prefix=(64, 640))
AFTER = snap(5_000_000, starved=(300_000, 60_000), crc=250_000, steps=(110, 330), waited=(1_240_000.0, 104),
             prefix=(1_664, 6_640), setup={"before_main": 9.0, "lower": 9.0})
WANT = {
    "host_starved_share": 100.0 * 240_000 / 4_000_000,
    "prefill_step_share": 100.0 * 100 / 400,
    "queue_wait_mean_ms": 1_200_000.0 / 100 / 1000.0,
    "prefix_hit_share": 100.0 * 1_600 / 6_000,
    "seal_crc_host_share": 100.0 * 200_000 / 4_000_000,
    "setup_python_s": 1.25 + 4.5,  # of the snapshot before the pre-roll
}


@pytest.fixture(scope="module")
def readers():
    return bench_run.load_readers("layer_metrics")


@pytest.mark.parametrize("entry", ENTRIES, ids=[e["name"] for e in ENTRIES])
def test_a_reader_takes_the_rise_between_two_snapshots_of_the_counters(readers, entry):
    read = readers[entry["name"]].read
    got = read({"engine_samples": [], "engine_before": BEFORE, "engine_after": AFTER})
    assert got == pytest.approx(WANT[entry["name"]])
    if entry["name"] != "setup_python_s":
        # samples of the window that carry the counters come first: first to last
        assert read({"engine_samples": [BEFORE | {"t": 0.0}, OLD | {"t": 0.5}, AFTER | {"t": 1.0}],
                     "engine_before": OLD, "engine_after": snap(9_000_000)}) == pytest.approx(WANT[entry["name"]])
    # a program without the counters (the parent): nothing to read, no error
    assert read({"engine_samples": [OLD | {"t": 0.0}], "engine_before": OLD, "engine_after": OLD}) is None
    assert read({"engine_samples": [], "engine_before": None, "engine_after": None}) is None
    assert read({"engine_samples": [], "engine_before": OLD, "engine_after": AFTER}) is None
    if entry["name"] != "setup_python_s":
        # nothing happened between the two: no share of nothing
        assert read({"engine_samples": [], "engine_before": AFTER, "engine_after": AFTER}) is None


@pytest.mark.parametrize("entry", ENTRIES, ids=[e["name"] for e in ENTRIES])
def test_a_reader_fits_the_entry_a_benchmark_pr_registers_it_with(readers, entry):
    """Its layer is one ``BENCHMARK.json`` already names, it moves an
    end-to-end metric every cell reports, and an entry of its name, once
    there, is this one."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    reader = readers[entry["name"]]
    assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) == (
        entry["name"], entry["unit"], entry["layer"], entry["moves"])
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"] if m["name"] != entry["name"]}
    assert entry["moves"] in {m["name"] for m in bench["end_to_end"]}
    assert set(entry["workloads"]) <= {w["name"] for w in bench["workloads"]}
    assert [m for m in bench["per_layer"] if m["name"] == entry["name"]] in ([], [entry])


def test_the_entries_written_out_in_perf_md_are_these():
    """PERF.md 7 hands the `benchmark` PR the six entries; they are the ones
    held above, letter for letter."""
    with open(os.path.join(ROOT, "PERF.md")) as f:
        text = f.read()
    for entry in ENTRIES:
        assert json.dumps(entry) in text, entry["name"]


def test_the_readers_read_the_counters_the_engine_keeps():
    """Three requests on a tiny engine of two slots, the third a repeat of the
    first's prompt: it waits for a slot and finds its prefix. A CPU run: the
    counters are the program's, no device metric."""
    import asyncio

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
    from dynamo_tpu.llm.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions
    from dynamo_tpu.models.llama import LLAMA_PRESETS, init_params
    from dynamo_tpu.runtime.engine import Context

    cfg = dataclasses.replace(LLAMA_PRESETS["tiny"], dtype=jnp.float32)
    eng = JaxServingEngine(cfg, init_params(jax.random.PRNGKey(0), cfg), EngineConfig(
        max_slots=2, kv_block_size=8, max_model_len=64, prefill_chunk=16))
    readers = bench_run.load_readers("layer_metrics")

    async def serve(prompt):
        req = PreprocessedRequest(
            token_ids=prompt, stop_conditions=StopConditions(max_tokens=6, ignore_eos=True),
            sampling_options=SamplingOptions())
        return [item async for item in eng.generate(Context(req))]

    async def three():
        first = [(5 * i + 2) % 90 + 1 for i in range(20)]
        return await asyncio.gather(serve(first), serve([(3 * i + 7) % 90 + 1 for i in range(9)]), serve(first))

    try:
        before = json.loads(json.dumps(eng.metrics_snapshot()))  # as /debug/engine sends it
        assert all(asyncio.run(three()))
        after = json.loads(json.dumps(eng.metrics_snapshot()))
    finally:
        eng.close()
    ctx = {"engine_samples": [], "engine_before": before, "engine_after": after}
    got = {e["name"]: readers[e["name"]].read(ctx) for e in ENTRIES}
    assert after["queue_wait_count"] - before["queue_wait_count"] == 3
    assert got["queue_wait_mean_ms"] > 0
    # 20 + 9 + 20 tokens probed; the repeat finds the two whole blocks of its twin
    assert (after["prefix_probe_tokens"], after["prefix_hit_tokens"]) == (49, 16)
    assert got["prefix_hit_share"] == pytest.approx(100.0 * 16 / 49)
    steps = after["host_steps"]
    assert steps["prefill"] >= 3 and steps["decode"] >= 5
    assert got["prefill_step_share"] == pytest.approx(100.0 * steps["prefill"] / (steps["prefill"] + steps["decode"]))
    assert 0.0 <= got["seal_crc_host_share"] <= got["seal_crc_host_share"] + got["host_starved_share"] <= 200.0
    assert 0.0 < got["host_starved_share"] <= 100.0
    # an engine built by hand timed no start-up but its warm-up's (none here)
    assert got["setup_python_s"] is None or got["setup_python_s"] >= 0.0
