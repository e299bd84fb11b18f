"""The reader of ``chunk_rows_computed_share`` (PR 67): on hand-made snapshots.
Its top counter is ``models/jamba.py``'s (``tests/test_jamba.py`` holds what it
counts), its base the engine's own, so the entry lists the one cell whose
module keeps the top: no server, no JAX compile."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402

NAME = "chunk_rows_computed_share"
# what every engine's snapshot holds, the parent's too: the rungs the dispatches took, no rows computed
OLD = {"request_active_slots": 64, "request_total_slots": 64, "chunk_rows_dispatched": 800, "chunk_rows_live": 500}


def snap(computed, dispatched, **more):
    return OLD | {"chunk_rows_computed": computed, "chunk_rows_dispatched": dispatched} | more


@pytest.mark.parametrize("samples, before, after, want", [
    # a program without the counter (the parent, another model's module): nothing to read, no error
    ([OLD | {"t": 0.0}, OLD | {"chunk_rows_dispatched": 960, "t": 0.5}], OLD, OLD | {"chunk_rows_dispatched": 999}, None),
    ([], None, None, None),
    # samples that carry the counters: first to last of the window (8 + 4 + 12 rows computed of 8 + 8 + 16)
    ([snap(100, 160, t=0.0), snap(108, 168, t=0.5), snap(124, 192, t=1.0)], snap(0, 0), snap(999, 999), 75.0),
    # the sampler kept none of them: the snapshots at both ends of the run
    ([OLD | {"t": 0.0}, OLD | {"t": 0.5}], snap(64, 128), snap(704, 1408), 50.0),
    # the whole rung computed, as a program without the group loop would count it
    ([snap(16, 16, t=0.0), snap(80, 80, t=0.5)], None, None, 100.0),
    # one sample is no difference; no chunk dispatch ran
    ([snap(100, 160, t=0.0)], OLD, snap(704, 1408), None),
    ([snap(100, 160, t=0.0), snap(100, 160, t=0.5)], snap(100, 160), snap(100, 160), None),
], ids=["parent", "nothing", "samples", "both_ends", "whole_rungs", "one_sample", "no_dispatch"])
def test_the_share_is_the_rise_of_rows_computed_over_the_rise_of_rows_dispatched(samples, before, after, want):
    reader = bench_run.load_readers("layer_metrics")[NAME]
    got = reader.read({"engine_samples": samples, "engine_before": before, "engine_after": after})
    assert got == want


def test_the_entry_is_found_by_its_name_and_lists_the_one_cell_whose_module_counts():
    """``BENCHMARK.json`` registers the reader under its ``NAME`` for
    ``batch.jamba2-3b`` alone, in a layer another entry names too, and it moves
    an end-to-end metric that cell reports; every other cell's line leaves it
    out (``run.py:registered``)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    reader = bench_run.load_readers("layer_metrics")[NAME]
    want = {"name": NAME, "unit": "%", "better": "lower", "source": "program_counter",
            "layer": "model, prompt processing", "moves": "ttft_mean_ms", "workloads": ["batch.jamba2-3b"]}
    assert [m for m in bench["per_layer"] if m["name"] == NAME] == [want]
    assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) == (
        want["name"], want["unit"], want["layer"], want["moves"])
    assert want["layer"] in {m["layer"] for m in bench["per_layer"] if m["name"] != NAME}
    assert want["moves"] in {m["name"] for m in bench["end_to_end"]}
    for cell in bench["workloads"]:
        names = {m["name"] for m in bench_run.registered(bench, "per_layer", cell["name"])}
        assert (NAME in names) == (cell["name"] == "batch.jamba2-3b"), cell["name"]
    jamba_cell = next(w for w in bench["workloads"] if w["name"] == "batch.jamba2-3b")
    assert jamba_cell["config"] == "jamba2-3b"
