"""What PR 54 brought: readers of the program's counters over one window, the
sampler that keeps whole snapshots, the device's gaps named by the engine
thread's spans, the traced run that ends when its xplane stands, and the case
file that leaves out a pair whose word the table does not hold. All on
hand-made lists and snapshots: no server, no JAX compile.
"""

import asyncio
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import counters, reference_child, trace_reduce  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
OLD = {"request_active_slots": 32, "request_total_slots": 32, "kv_active_blocks": 9, "kv_total_blocks": 64}
EXPERT_CELLS = ["batch.kimi-linear-48b-a3b", "batch.lfm2-24b-a2b", "batch.qwen3-next-80b-a3b",
                "batch.openpangu-ultra-moe-718b"]

# name -> (its entry but for the name, the two counters it reads (top, base), top's and base's rise -> the reading)
RATIOS = {
    "chunk_dispatches_per_prompt": (
        {"unit": "dispatches", "better": "lower", "source": "program_counter", "layer": "engine step loop",
         "moves": "ttft_mean_ms"}, ("prompt_dispatches", "prompts_prefilled"), lambda top, base: top / base),
    "chunk_rows_per_lane": (
        {"unit": "rows", "better": "higher", "source": "program_counter", "layer": "engine step loop",
         "moves": "ttft_mean_ms"}, ("chunk_rows_live", "chunk_lanes_fed"), lambda top, base: top / base),
    "decode_history_read_share": (
        {"unit": "%", "better": "lower", "source": "program_counter", "layer": "model, token generation",
         "moves": "ttft_mean_ms"},
        ("decode_history_tiles_read", "decode_history_tiles_full"), lambda top, base: 100.0 * top / base),
    "moe_live_row_share": (
        {"unit": "%", "better": "higher", "source": "program_counter", "layer": "model, expert layer",
         "moves": "ttft_mean_ms", "workloads": EXPERT_CELLS},
        ("moe_held_rows", "moe_rows_computed"), lambda top, base: 100.0 * top / base),
    "state_tokens_per_pass": (
        {"unit": "tokens", "better": "higher", "source": "program_counter", "layer": "model, prompt processing",
         "moves": "ttft_mean_ms", "workloads": ["batch.kimi-linear-48b-a3b", "batch.qwen3-next-80b-a3b"]},
        ("kda_chunk_tokens", "kda_state_passes"), lambda top, base: top / base),
    # the four that looked at the two ends of the run alone until PR 54
    "mla_history_read_share": (
        {"unit": "%", "better": "higher", "source": "program_counter", "layer": "model, latent attention",
         "moves": "ttft_mean_ms", "workloads": ["batch.openpangu-ultra-moe-718b"]},
        ("mla_history_positions_live", "mla_history_positions_read"), lambda top, base: 100.0 * top / base),
    "ssm_tokens_per_state_pass": (
        {"unit": "tokens", "better": "higher", "source": "program_counter", "layer": "model, state-space layers",
         "moves": "ttft_mean_ms", "workloads": ["batch.jamba2-3b"]},
        ("ssm_chunk_tokens", "ssm_state_passes"), lambda top, base: top / base),
    "moe_rows_per_held_expert": (
        {"unit": "rows", "better": "higher", "source": "program_counter", "layer": "model, expert layer",
         "moves": "ttft_mean_ms", "workloads": EXPERT_CELLS},
        ("moe_held_rows", "moe_layer_calls"), lambda top, base: top / (base * 128)),
    "moe_experts_hit_share": (
        {"unit": "%", "better": "lower", "source": "program_counter", "layer": "model, expert layer",
         "moves": "ttft_mean_ms", "workloads": EXPERT_CELLS},
        ("moe_experts_hit", "moe_layer_calls"), lambda top, base: 100.0 * top / (base * 128)),
}


@pytest.fixture(scope="module")
def readers():
    return bench_run.load_readers("layer_metrics")


@pytest.mark.parametrize("name", RATIOS)
def test_a_ratio_reader_is_held_to_its_entry_and_takes_the_rise_over_the_window(readers, name):
    rest, (top, base), want = RATIOS[name]
    mod = readers[name]
    assert [m for m in BENCH["per_layer"] if m["name"] == name] == [{"name": name} | rest]
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (name, rest["unit"], rest["layer"], rest["moves"])
    assert set(rest.get("workloads", ())) <= {w["name"] for w in BENCH["workloads"]}

    def snap(a, b, **more):
        return OLD | {top: a, base: b} | more

    shape = {"num_experts": 128}
    # between the two ends of the run where the sampler kept nothing (an untraced run)
    ctx = {"engine_samples": [], "engine_before": snap(10, 40), "engine_after": snap(310, 640), "shape": shape}
    assert mod.read(ctx) == pytest.approx(want(300, 600))
    # the window's samples that carry the counters come first: the pre-roll's rise is left out
    ctx["engine_samples"] = [snap(110, 240, t=0.0), OLD | {"t": 0.5}, snap(210, 440, t=1.0)]
    assert mod.read(ctx) == pytest.approx(want(100, 200))
    # a program without the counters (another model, the parent): nothing to read, no error
    assert mod.read({"engine_samples": [OLD | {"t": 0.0}], "engine_before": OLD, "engine_after": OLD,
                     "shape": shape}) is None
    assert mod.read({"engine_samples": [], "engine_before": None, "engine_after": None, "shape": shape}) is None
    # a base that did not rise: no share of nothing, never 0
    assert mod.read({"engine_samples": [], "engine_before": snap(10, 40), "engine_after": snap(10, 40),
                     "shape": shape}) is None


def test_the_delta_rule_reader_reads_whichever_pair_the_snapshot_carries(readers):
    read = readers["state_tokens_per_pass"].read
    gdn = [OLD | {"gdn_chunk_tokens": a, "gdn_state_passes": b} for a, b in ((0, 0), (1074, 10))]
    assert read({"engine_samples": [], "engine_before": gdn[0], "engine_after": gdn[1]}) == pytest.approx(107.4)
    kda = [OLD | {"kda_chunk_tokens": a, "kda_state_passes": b} for a, b in ((5, 5), (325, 8))]
    assert read({"engine_samples": [], "engine_before": kda[0], "engine_after": kda[1]}) == pytest.approx(320 / 3)


def test_the_window_is_the_samples_that_carry_the_counter_else_the_two_ends():
    a, b, c = ({"x": 1, "t": 0.0}, {"t": 0.5}, {"x": 9, "t": 1.0})
    assert counters.window_ends({"engine_samples": [a, b, c]}, "x") == (a, c)
    assert counters.window_ends({"engine_samples": [a, b], "engine_before": {"x": 0}, "engine_after": {"x": 20}},
                                "x") == ({"x": 0}, {"x": 20})
    assert counters.window_ends({"engine_samples": [a, c]}, "x", "y") is None
    assert counters.rise_ratio({"engine_samples": [a, c]}, "x", "x", 100.0) == 100.0
    assert counters.rise_ratio({"engine_samples": [a, a]}, "x", "x") is None


def test_the_handover_counters_are_a_difference_of_registered_ones_and_stay_unregistered():
    """``conv_tail_handovers`` (LFM2) and ``ssm_state_handovers`` (Jamba) equal
    ``chunk_rows_live`` - ``chunk_lanes_fed`` by construction (held on engines
    in ``tests/test_lfm2.py`` and ``tests/test_jamba_lane_rows.py``), which
    ``chunk_rows_per_lane`` reads: a second entry would say the same thing."""
    names = {m["name"] for m in BENCH["per_layer"]}
    assert "chunk_rows_per_lane" in names and not names & {"conv_tail_handovers", "ssm_state_handovers"}
    rows, lanes = 70, 40
    snaps = [OLD | {"chunk_rows_live": 0, "chunk_lanes_fed": 0, "conv_tail_handovers": 0},
             OLD | {"chunk_rows_live": rows, "chunk_lanes_fed": lanes, "conv_tail_handovers": rows - lanes}]
    got = bench_run.load_readers("layer_metrics")["chunk_rows_per_lane"].read(
        {"engine_samples": [], "engine_before": snaps[0], "engine_after": snaps[1]})
    assert (got - 1.0) * lanes == pytest.approx(snaps[1]["conv_tail_handovers"])


def test_every_per_layer_entry_names_a_layer_some_other_entry_or_perf_md_names():
    """Entries are found by name, wherever they stand; a new layer comes with
    its first entry and has its row in PERF.md 3."""
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for entry in BENCH["per_layer"]:
        assert f"| {entry['layer']} |" in perf, entry
    assert len({m["name"] for m in BENCH["per_layer"]}) == len(BENCH["per_layer"]) >= 33


class FakeSession:
    """``session.get(url)`` as ``sample_engine`` uses it: an async context
    manager whose ``json()`` is the next snapshot."""

    def __init__(self, snaps):
        self.snaps = iter(snaps)

    def get(self, url):
        return self

    async def __aenter__(self):
        return self

    async def __aexit__(self, *exc):
        return False

    async def json(self):
        return next(self.snaps)


def test_the_sampler_keeps_a_counter_it_was_never_told_of(monkeypatch):
    snaps = [OLD | {"a_counter_of_a_later_pr": n, "nested": {"deep": n}} for n in (3, 5, 8)]
    # the clock as the loop reads it: before the window (no sample), three samples, past the end
    ticks = iter([-1.0, -0.5] + [0.0] * 3 + [0.5] * 3 + [1.0] * 3 + [2.0])

    async def no_sleep(_):
        return None

    monkeypatch.setattr(bench_run.asyncio, "sleep", no_sleep)
    into = []
    asyncio.run(bench_run.sample_engine(lambda: next(ticks), asyncio.Event(), FakeSession(snaps), 1, 1.5, into))
    assert [s["a_counter_of_a_later_pr"] for s in into] == [3, 5, 8] and into[2]["nested"] == {"deep": 8}
    assert [s["t"] for s in into] == [0.0, 0.5, 1.0] and all(OLD.items() <= s.items() for s in into)
    # ... so that a reader written later takes its rise over the window with no edit here
    assert counters.rise_ratio({"engine_samples": into}, "a_counter_of_a_later_pr", "t") == 5.0


# device 0: busy 0-10, 40-50, 53-60, 2_000_060-2_000_070 (ns); the engine thread's spans beside it
MODULES = [["jit_decode(1)", 0, 10], ["jit_chunk(2)", 40, 10], ["jit_decode(1)", 53, 7],
           ["jit_decode(1)", 2_000_060, 10]]
HOST = [["engine.step", 5, 50], ["engine.decode.emit", 10, 8], ["engine.seal.crc", 18, 20],
        ["engine.chunk.dispatch", 38, 2], ["engine.step", 60, 2_000_000], ["engine.decode.build", 100, 1_500_000]]


def reduced(host=None):
    out = {"devices": {"0": {"modules": MODULES, "ops": []}}}
    if host is not None:
        out["host"] = host
    return out


def test_an_idle_gap_is_named_by_the_innermost_span_that_covers_most_of_it():
    assert trace_reduce.idle_gaps(reduced(HOST)) == [
        ["engine.decode.build", 2_000_000e-9],   # the innermost span wins over the root around it
        ["engine.seal.crc", 30e-9],              # 20 of the 30 ns; emit 8, dispatch 2
        ["engine.step", 3e-9]]                   # under the root alone: it reads the root
    # a trace without the program's spans (a parent older than PR 39) reads as it did
    assert trace_reduce.idle_gaps(reduced()) == trace_reduce.idle_gaps(reduced([])) == [
        ["before jit_decode", 2_000_000e-9], ["before jit_chunk", 30e-9], ["before jit_decode", 3e-9]]
    assert trace_reduce.idle_gaps(reduced(HOST), 1) == [["engine.decode.build", 2_000_000e-9]]
    # spans that cover none of a gap: the gap keeps its module's name
    assert trace_reduce.idle_gaps(reduced([["engine.step", 0, 5]]))[0] == ["before jit_decode", 2_000_000e-9]


def test_idle_seconds_by_span_sum_to_the_idle_time_in_gaps():
    by = trace_reduce.idle_s_by_span(reduced(HOST))
    assert by[0] == ["engine.decode.build", 1_500_000e-9] and [name for name, _ in by] == [
        "engine.decode.build", "engine.step", "engine.seal.crc", "engine.decode.emit", "engine.chunk.dispatch"]
    span = trace_reduce.span_ns(reduced())
    idle_ns = (span[1] - span[0]) - trace_reduce.busy_union_ns(MODULES)
    assert sum(s for _, s in by) == pytest.approx(idle_ns / 1e9) == pytest.approx(2_000_033e-9)
    # what lies under no span is kept, under its own name, so that the sum holds
    assert trace_reduce.idle_s_by_span(reduced(HOST[:4]))[0] == [trace_reduce.NO_SPAN, pytest.approx(2_000_000e-9)]
    assert trace_reduce.idle_s_by_span(reduced()) == [] and len(trace_reduce.idle_s_by_span(reduced(HOST), 2)) == 2


def test_the_share_of_long_idle_under_a_named_span(readers, tmp_path):
    # one gap over 1 ms: 1.5 of its 2 ms under engine.decode.build, the rest under the root alone
    assert trace_reduce.idle_under_span_share(reduced(HOST)) == pytest.approx(0.75)
    assert trace_reduce.idle_under_span_share(reduced(HOST), floor_ns=20) == pytest.approx(1_500_030 / 2_000_030)
    assert trace_reduce.idle_under_span_share(reduced()) is None           # no spans
    short = {"devices": {"0": {"modules": MODULES[:3], "ops": []}}, "host": HOST}
    assert trace_reduce.idle_under_span_share(short) is None               # no gap over 1 ms: never 0
    reader = readers["idle_under_span_share"]
    assert reader.read({"trace": reduced(HOST)}) == pytest.approx(75.0)
    assert reader.read({"trace": None}) is None and reader.read({"trace": short}) is None
    assert [m for m in BENCH["per_layer"] if m["name"] == reader.NAME] == [{
        "name": "idle_under_span_share", "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "device", "moves": "ttft_mean_ms",
        "workloads": ["batch.qwen2.5-1.5b", "batch.qwen2.5-7b-tp4"]}]
    # the reduced trace carries the spans through its file
    path = str(tmp_path / "reduced.json.gz")
    trace_reduce.save_reduced(reduced(HOST), path)
    assert trace_reduce.load_reduced(path)["host"] == HOST


def test_innermost_segments_are_the_spans_self_time():
    segments = trace_reduce.innermost_segments(HOST[:4])
    assert segments == [["engine.step", 5, 10], ["engine.decode.emit", 10, 18], ["engine.seal.crc", 18, 38],
                        ["engine.chunk.dispatch", 38, 40], ["engine.step", 40, 55]]
    ends = [e for _, _, e in segments]
    assert trace_reduce.overlap_by_name(segments, ends, 12, 39) == {
        "engine.decode.emit": 6, "engine.seal.crc": 20, "engine.chunk.dispatch": 1}
    # a child that outlives its parent by clock jitter is cut to it
    assert trace_reduce.innermost_segments([["engine.step", 0, 10], ["engine.drain", 8, 5]]) == [
        ["engine.step", 0, 8], ["engine.drain", 8, 10]]


class Growing:
    """A clock whose every ``sleep`` lets the child's profiler write on: the
    xplane grows by ``step`` bytes until ``full``, then stands."""

    def __init__(self, path, full, step):
        self.path, self.full, self.step, self.now = path, full, step, 0.0

    def clock(self):
        return self.now

    def sleep(self, s):
        self.now += s
        size = os.path.getsize(self.path) if os.path.exists(self.path) else 0
        if size < self.full:
            os.makedirs(os.path.dirname(self.path), exist_ok=True)
            with open(self.path, "ab") as f:
                f.write(b"x" * min(self.step, self.full - size))


def test_the_trace_is_taken_once_its_xplane_stands_still(tmp_path):
    trace_dir = str(tmp_path)
    world = Growing(os.path.join(trace_dir, "plugins", "profile", "run", "host.xplane.pb"), 5000, 100)
    took = bench_run.wait_for_xplane(trace_dir, lambda: True, 90.0, 2.0, 0.1, world.clock, world.sleep)
    # 50 steps of growth, then 2 s of standing still: no `done` was ever written
    assert took and os.path.getsize(world.path) == 5000 and 6.9 <= world.now <= 7.3
    assert not os.path.exists(os.path.join(trace_dir, "done"))


@pytest.mark.parametrize("full, alive, done, want, by", [
    (10 ** 9, True, False, False, 90.3),    # still growing at the deadline: the last resort
    (0, True, False, False, 90.3),          # no file at all
    (5000, False, False, False, 0.2),       # the child died before its file stood
    (0, False, True, True, 0.0),            # `done` is there: nothing to wait for
], ids=["still_growing", "no_file", "child_exited", "done"])
def test_the_wait_for_the_xplane_ends_without_one(tmp_path, full, alive, done, want, by):
    trace_dir = str(tmp_path)
    world = Growing(os.path.join(trace_dir, "plugins", "profile", "run", "host.xplane.pb"), full, 100)
    if done:
        open(os.path.join(trace_dir, "done"), "w").close()
    assert bench_run.wait_for_xplane(trace_dir, lambda: alive, 90.0, 2.0, 0.1, world.clock, world.sleep) is want
    assert world.now <= by


def test_the_seconds_of_the_export_are_counted_from_the_stop_file(tmp_path):
    trace_dir = str(tmp_path)
    folder = os.path.join(trace_dir, "plugins", "profile", "run")
    os.makedirs(folder)
    for name, at in (("stop", 1000.0), (os.path.join(folder, "h.xplane.pb"), 1015.5), ("done", 1140.25)):
        path = os.path.join(trace_dir, name)
        open(path, "w").close()
        os.utime(path, (at, at))
    assert bench_run.trace_seconds(trace_dir, 1018.0) == {
        "trace_taken_s": 18.0, "xplane_written_s": 15.5, "done_s": 140.25}
    os.remove(os.path.join(trace_dir, "done"))
    assert bench_run.trace_seconds(trace_dir, 1018.0)["done_s"] is None


WORDS = ["unk", "user", "assistant", "system"] + [f"w{i:03d}" for i in range(60)]
WORD_ID = {w: i for i, w in enumerate(WORDS)}


def answer(tops):
    return {"prompt": "w001 w002 w003", "text": " ".join(WORDS[10 + i] for i in range(len(tops))),
            "top_logprobs": tops}


def test_a_pair_whose_word_the_table_does_not_hold_is_left_out_not_sent_as_id_0():
    """A special token decodes to no text: one empty-string key among a
    position's 20 gives 19 pairs, none of them token 0's."""
    top = {" " + WORDS[4 + i]: -1.0 - i for i in range(19)} | {"": -2.5}
    case, unknown = bench_run.reference_case(answer([top, dict(top)]), WORD_ID)
    assert unknown == [] and case["prompt_ids"] == [5, 6, 7] and case["output_ids"] == [10, 11]
    assert [len(pairs) for pairs in case["top_logprobs"]] == [19, 19]
    assert all(tok != 0 for pairs in case["top_logprobs"] for tok, _ in pairs)
    assert case["top_logprobs"][0][0] == [4, -1.0]
    # an answered word the table does not hold cannot be teacher-forced: it is named
    bad = dict(answer([top]), text="w010 <|nowhere|>")
    assert bench_run.reference_case(bad, WORD_ID)[1] == ["<|nowhere|>"]


@pytest.mark.parametrize("unknown, passes", [(0, True), (24, True), (25, False), (30, False)])
def test_an_answer_that_loses_too_many_of_its_480_pairs_fails(unknown, passes):
    """``correct`` requires 456 of the 480 pairs to have been held against the
    reference; the count and the floor go into the verdict the ``info`` line
    prints."""
    import numpy as np

    n, k, vocab = bench_run.REFERENCE_OUTPUT_TOKENS, bench_run.REFERENCE_TOP_LOGPROBS, len(WORDS)
    logits = np.random.default_rng(7).normal(size=(n, vocab)).astype(np.float32)
    chosen, top = reference_child.answer_of(logits, k)
    tops = [{(" " + WORDS[t]): lp for t, lp in row} for row in top]
    for i in range(unknown):  # the server names a word the table does not hold
        gone = next(iter(tops[i % n]))
        tops[i % n][f"<|special_{i}|>"] = tops[i % n].pop(gone)
    case, _ = bench_run.reference_case(
        {"prompt": "w001", "text": " ".join(WORDS[t] for t in chosen), "top_logprobs": tops}, WORD_ID)
    verdict = reference_child.held_against(logits, np.asarray(case["output_ids"]), case["top_logprobs"], 0.035)
    assert verdict["agrees"] and verdict["logprob_pairs"] == n * k - unknown and verdict["logprob_rms"] < 1e-6
    faults = bench_run.reference_faults(verdict)
    assert verdict["logprob_pairs_floor"] == bench_run.REFERENCE_PAIRS_FLOOR == 456
    assert (faults == []) is passes
    if not passes:
        assert f"{n * k - unknown} of the answer's 24 x 20" in faults[0] and "456" in faults[0]


def test_every_number_compared_stands_beside_its_limit_and_comes_last_in_the_line():
    device = {"memory_peak_bytes": 5_935_131_648}
    verdict = {"tokens": 24, "argmax_matches": 21, "worst_gap": 0.03, "tolerance": 0.31, "logprob_rms": 0.017,
               "logprob_rms_limit": 0.035, "logprob_pairs": 479, "logprob_pairs_floor": 456}
    got = bench_run.numbers_compared([{"text": "a b"}] * 3, {"failed": 0}, [8, 8], device, 3_025_680_036.0, verdict)
    assert got == {
        "probe_texts_distinct": [1, 1], "requests_failed": [0, 0], "jit_recompiles_in_window": [0, 0],
        "memory_peak_bytes_at_least": [5_935_131_648, 3_025_680_036.0],
        "reference_probe_answered": [1, 1], "worst_gap_at_most": [0.03, 0.31],
        "argmax_matches_at_least": [21, 12.0], "logprob_rms_at_most": [0.017, 0.035],
        "logprob_pairs_at_least": [479, 456]}
    # an untraced run holds no answer against the reference
    assert list(bench_run.numbers_compared([{"text": "a"}, {"text": "b"}], {"failed": 2}, [8, 9], device, 1.0, None)
                .items())[:3] == [("probe_texts_distinct", [2, 1]), ("requests_failed", [2, 0]),
                                  ("jit_recompiles_in_window", [1, 0])]
