"""The reader of ``chunk_history_read_share``: on hand-made snapshots, and at
the end of a traced rehearsal of the one-chip cell on the CPU."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402

NAME = "chunk_history_read_share"
OLD = {"request_active_slots": 32, "request_total_slots": 32, "kv_active_blocks": 9, "kv_total_blocks": 64}


def snap(read, full, **more):
    return OLD | {"chunk_history_tiles_read": read, "chunk_history_tiles_full": full} | more


@pytest.mark.parametrize("samples, before, after, want", [
    # a program without the counters (the parent): nothing to read, no error
    ([OLD | {"t": 0.0}, OLD | {"t": 0.5}], OLD, OLD, None),
    ([], None, None, None),
    # samples that carry the counters: first to last of the window
    ([snap(10, 40, t=0.0), snap(13, 48, t=0.5), snap(25, 80, t=1.0)], snap(0, 0), snap(99, 99), 37.5),
    # the sampler kept none of them: the snapshots at both ends of the run
    ([OLD | {"t": 0.0}, OLD | {"t": 0.5}], snap(4, 8), snap(64, 128), 50.0),
    # one sample is no difference; no history-bearing chunk dispatch ran
    ([snap(10, 40, t=0.0)], OLD, snap(64, 128), None),
    ([snap(10, 40, t=0.0), snap(10, 40, t=0.5)], snap(10, 40), snap(10, 40), None),
], ids=["parent", "nothing", "samples", "both_ends", "one_sample", "no_dispatch"])
def test_the_share_is_the_rise_of_tiles_read_over_the_rise_of_tiles_full(samples, before, after, want):
    reader = bench_run.load_readers("layer_metrics")[NAME]
    got = reader.read({"engine_samples": samples, "engine_before": before, "engine_after": after})
    assert got == want


def test_benchmark_json_registers_the_reader_for_every_cell_whose_traced_run_read_it():
    """The engine counts the tiles for every module (each says what its chunk
    program reads of the tables: ``chunk_history_tiles``), so the two cells on
    ``models/llama.py`` were never alone: a traced run of each of the seven on
    the chip read both counters rise over the window (PR 56; PERF.md 3)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)  # by name: entries are appended
    reader = bench_run.load_readers("layer_metrics")[NAME]
    cells = entry.pop("workloads")
    assert entry == {"name": NAME, "unit": reader.UNIT, "better": "lower", "source": "program_counter",
                     "layer": reader.LAYER, "moves": reader.MOVES}
    # a later cell is appended once a traced run of it has read the share
    assert cells[:7] == ["batch.qwen2.5-1.5b", "batch.qwen2.5-7b-tp4", "batch.kimi-linear-48b-a3b",
                         "batch.jamba2-3b", "batch.lfm2-24b-a2b", "batch.qwen3-next-80b-a3b",
                         "batch.openpangu-ultra-moe-718b"]
    assert set(cells) <= {w["name"] for w in bench["workloads"]} and len(set(cells)) == len(cells)
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"] if m["name"] != NAME}


@pytest.mark.timeout(400)
def test_the_one_chip_cell_rehearses_traced_and_reports_the_share():
    """``batch.qwen2.5-1.5b`` at ``rehearse.json``'s tiny shape on the CPU,
    traced: the rehearsal lists the share with the other per-layer metrics,
    and it is a share. A rehearsal prints no device metric. The CPU engine is
    slow at 32 lanes: on a loaded machine no request may complete, or none be
    sent, inside so short a window, which is no fault of the reader's. Since
    PR 54 the share is the window's own (the sampler keeps whole snapshots):
    a prompt prefills in one dispatch, so the few history-bearing dispatches
    of 6 s may have read no tile of the pool, and the share is then 0; and
    where next to nothing was sent inside the window, none may have run
    between its samples, and there is no share to report."""
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", "batch.qwen2.5-1.5b",
         "--seed", "2147483779", "--seconds", "6", "--trace", "1", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=380)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(x) for x in done.stdout.splitlines() if x.startswith("{")]
    info, line = lines[-2]["info"], lines[-1]
    faults = [r for r in info["not_correct_because"]
              if "no request fell due" not in r and "requests failed" not in r]
    assert not faults and line["metrics"] == {}
    assert {"batch_occupancy", "kv_pool_fill"} <= set(line["rehearsal"])
    if line["attempted"] >= 4:
        assert 0.0 <= line["rehearsal"][NAME] <= 100.0
    # the readers PR 54 registered for every cell ride the same samples
    assert NAME not in line["rehearsal"] or {"chunk_token_fill_share", "chunk_dispatches_per_prompt",
                                             "host_starved_share"} <= set(line["rehearsal"])
