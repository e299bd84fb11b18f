"""The benchmark's own arithmetic: no server, no JAX compile, under ten seconds.

What is checked: the trace reduction on a trace recorded on the chip, the
traffic generators as pure functions of the seed, the percentile and TPOT
arithmetic, that the data files and the per-metric readers agree with
BENCHMARK.json, and the bytes the roofline share rests on.
"""

import glob
import json
import os
import random
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import bytes_and_flops, stats, trace_reduce, traffic  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

HERE = os.path.join(ROOT, "benchmark")


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


BENCH = load(ROOT, "BENCHMARK.json")
CELLS = sorted(glob.glob(os.path.join(HERE, "workloads", "*.json")))
# an open loop with another length generator than the registered cells name
OPEN_LOOP = {"config": "qwen2.5-1.5b", "traffic": "chat", "preroll_s": 12,
             "arrivals": {"gen": "poisson", "rate_rps": 1.4},
             "prompt_tokens": {"gen": "lognormal_clipped", "median": 256, "sigma": 0.7, "lo": 32, "hi": 1024},
             "output_tokens": {"gen": "uniform_int", "lo": 16, "hi": 64}}


def test_trace_reduction_matches_the_recorded_numbers():
    reduced = trace_reduce.load_reduced(os.path.join(HERE, "testdata", "trace_v5e.json.gz"))
    want = load(HERE, "testdata", "trace_v5e.expected.json")
    dev0 = reduced["devices"]["0"]
    assert trace_reduce.busy_union_ns(dev0["modules"]) == want["busy_union_ns"]
    assert list(trace_reduce.span_ns(reduced)) == want["span_ns"]
    assert trace_reduce.idle_share(reduced) == pytest.approx(want["idle_share"], rel=1e-12)
    medians = trace_reduce.module_medians_ms(reduced)
    assert {k: v["count"] for k, v in medians.items()} == want["module_counts"]
    for kind, ms in want["module_median_ms"].items():
        assert medians[kind]["median_ms"] == pytest.approx(ms, rel=1e-12)
    assert trace_reduce.top_ops(reduced, 3) == want["top_ops"]


def test_collective_share_on_a_trace_recorded_under_tp4():
    reader = bench_run.load_readers("layer_metrics")["collective_share"]
    reduced = trace_reduce.load_reduced(os.path.join(HERE, "testdata", "trace_v5e_tp4.json.gz"))
    want = load(HERE, "testdata", "trace_v5e_tp4.expected.json")
    dev0 = reduced["devices"]["0"]
    assert {k: v["count"] for k, v in trace_reduce.module_medians_ms(reduced).items()} == want["module_counts"]
    collectives = [e for e in dev0["ops"] if reader.is_collective(e[0])]
    kinds = {}
    for name, _, _ in collectives:
        kind = name.lstrip("%").split(".")[0]
        kinds[kind] = kinds.get(kind, 0) + 1
    assert kinds == want["collective_events"]
    assert trace_reduce.busy_union_ns(dev0["modules"]) == want["busy_union_ns"]
    assert trace_reduce.busy_union_ns(collectives) == want["collective_union_ns"]
    assert reader.read({"trace": reduced, "chips": 4}) == pytest.approx(want["collective_share"], rel=1e-12)
    assert reader.read({"trace": reduced, "chips": 4}) == pytest.approx(
        100.0 * want["collective_union_ns"] / want["busy_union_ns"])
    # the rule that names a collective: the instruction's own name, not an operand's
    for name in ("%all-reduce.5 = bf16[16,128,3584] all-reduce(bf16[16,128,3584] %fusion.214), channel_id=1",
                 "%all-gather-start.2 = (bf16[16,9504], bf16[16,38016]) all-gather-start(",
                 "%reduce-scatter.1 = f32[8,128] reduce-scatter(", "%collective-permute-done.7 = bf16[4,128]"):
        assert reader.is_collective(name), name
    for name in ("%fusion.219 = bf16[16,128,3584] fusion(bf16[16,128,3584] %all-reduce.5, bf16[16,128,3584]",
                 "%while.15 = (s32[], bf16[16,128,3584]", "%reduce.4 = f32[16] reduce(", "%copy.9 = bf16[2] copy("):
        assert not reader.is_collective(name), name
    # one chip has no collectives to report, and no trace gives nothing
    assert reader.read({"trace": reduced, "chips": 1}) is None
    assert reader.read({"trace": None, "chips": 4}) is None
    stored = trace_reduce.load_reduced(os.path.join(HERE, "testdata", "trace_v5e.json.gz"))
    assert not any(reader.is_collective(e[0]) for e in stored["devices"]["0"]["ops"])


def test_busy_union_merges_overlaps_and_idle_gaps_are_labelled():
    events = [["jit_a(1)", 0, 10], ["jit_b(2)", 5, 10], ["jit_a(1)", 30, 5], ["jit_c(3)", 31, 1]]
    assert trace_reduce.busy_union_ns(events) == 20
    reduced = {"devices": {"0": {"modules": events, "ops": []}}}
    assert trace_reduce.idle_share(reduced) == pytest.approx(1 - 20 / 35)
    assert trace_reduce.idle_gaps(reduced) == [["before jit_a", 15e-9]]
    assert trace_reduce.idle_share({"devices": {}}) is None


@pytest.mark.parametrize("cell", [load(p) for p in CELLS] + [OPEN_LOOP],
                         ids=[os.path.basename(p)[:-5] for p in CELLS] + ["an_open_loop"])
def test_schedule_is_a_pure_function_of_the_seed_and_respects_its_clips(cell):
    a = traffic.build_schedule(cell, 2147483659, 30.0)
    assert a == traffic.build_schedule(cell, 2147483659, 30.0)
    # another --seed: other words, and the same work in another order
    b = traffic.build_schedule(cell, 7, 30.0)
    assert a["text_seed"] != b["text_seed"]
    n = traffic.BLOCK
    for key in ("prompt_tokens", "output_tokens"):
        lo, hi = cell[key]["lo"], cell[key]["hi"]
        assert all(lo <= x <= hi for x in a[key])
        assert a[key][:n] != b[key][:n] and sorted(a[key][:n]) == sorted(b[key][:n])
    pairs = [sorted(zip(s["prompt_tokens"][:n], s["output_tokens"][:n])) for s in (a, b)]
    assert pairs[0] == pairs[1]  # the same requests, not only the same lengths
    assert max(p + o for p, o in zip(a["prompt_tokens"], a["output_tokens"])) <= 2048
    if a["due"] is not None:
        assert a["due"] == sorted(a["due"]) and a["due"][-1] < 30.0
        assert a["due"][0] >= -cell["preroll_s"]
        rate = len([t for t in a["due"] if t >= 0]) / 30.0
        assert rate == pytest.approx(cell["arrivals"]["rate_rps"], rel=0.15)
        gaps = [[y - x for x, y in zip([-cell["preroll_s"]] + s["due"], s["due"])][:n]
                for s in (a, b)]
        assert gaps[0] != gaps[1]
        assert sorted(gaps[0]) == pytest.approx(sorted(gaps[1]), abs=1e-9)
    else:
        assert a["clients"] == cell["arrivals"]["clients"] and a["n"] >= 4 * traffic.BLOCK


def test_lognormal_median_and_prompt_text_length():
    xs = traffic.lognormal_clipped(
        {"median": 256, "sigma": 0.7, "lo": 32, "hi": 1024}, traffic.BLOCK, random.Random(1))
    assert stats.percentile(xs, 50) == pytest.approx(256, rel=0.03)
    text = traffic.prompt_text(["aa", "bb", "cc"], 17, random.Random(2))
    assert len(text.split()) == 17
    with pytest.raises(KeyError):
        traffic.find_generator("no_such_generator")


def test_percentiles_tpot_and_failures_on_a_hand_made_record_set():
    def rec(due, sent, first, last, n, ok=True, in_window=True):
        return {"due_s": due, "sent_s": sent, "first_s": first, "last_s": last,
                "got_tokens": n, "max_tokens": n, "prompt_tokens": 10, "ok": ok,
                "in_window": in_window, "token_times": [(first, 1), (last, n - 1)]}

    records = [rec(0.0, 0.001, 0.100, 1.100, 11),      # ttft 100, tpot 100
               rec(1.0, 1.000, 1.300, 2.300, 21),      # ttft 300, tpot 50
               rec(2.0, 2.000, 2.200, 9.000, 5),       # last token after the window
               rec(3.0, 3.000, 3.500, 3.500, 1),       # one token: no gap
               rec(4.0, 4.000, 4.100, 4.200, 3, ok=False),
               rec(-1.0, -1.0, -0.5, 0.5, 9, in_window=False)]   # pre-roll
    s = stats.summarize(records, window_s=5.0)
    assert (s["attempted"], s["failed"]) == (5, 1)
    assert s["samples"] == {"ttft": 4, "tpot": 3}
    assert s["ttft_p50_ms"] == pytest.approx(250.0)
    assert s["ttft_mean_ms"] == pytest.approx((100 + 300 + 200 + 500) / 4)
    assert s["tpot_mean_ms"] == pytest.approx((100 + 50 + 1700) / 3)
    assert s["ttft_p90_ms"] == pytest.approx(440.0)
    assert s["tpot_p50_ms"] == pytest.approx(100.0)
    # tokens inside [0, 5): 11 + 21 + 1 (first of the third) + 1 + 3 + 8 (pre-roll request's tail)
    assert s["output_tokens_in_window"] == 45
    assert s["output_tokens_per_s"] == pytest.approx(9.0)
    # the drain may take the longest answer at twice the median gap, 15 s or more
    assert stats.drain_limit_s(records, 1.0) == pytest.approx(2 * 21 * 0.1125)
    assert stats.drain_limit_s(records, 15.0) == 15.0
    assert stats.drain_limit_s([], 15.0) == 15.0
    assert stats.percentile([], 90) is None
    assert stats.percentile([1, 2, 3, 4], 90) == pytest.approx(3.7)


@pytest.mark.parametrize("times, window_s, want_ms", [
    ([[0.1, 0.5, 1.1], [1.3, 2.2, 2.3, 3.5], [3.5, 4.1, 4.2]], 5.0, 1200.0),  # 2.3 -> 3.5
    ([[2.0, 2.1], [-0.5, 6.0]], 5.0, 2900.0),     # the window's own edges count; outside it nothing does
    ([[]], 5.0, None),
], ids=["between_streams", "to_the_edge", "no_token"])
def test_longest_silence_is_the_widest_gap_between_token_arrivals(times, window_s, want_ms):
    records = [{"token_times": [(t, 1) for t in ts]} for ts in times]
    got = stats.longest_silence_ms(records, window_s)
    assert got is None if want_ms is None else got == pytest.approx(want_ms)


def test_every_cell_names_an_existing_configuration_and_generators():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for path in CELLS:
        cell = load(path)
        assert os.path.exists(os.path.join(HERE, "configs", cell["config"] + ".json")), path
        for key in ("arrivals", "prompt_tokens", "output_tokens"):
            assert callable(traffic.find_generator(cell[key]["gen"]))
    for w in BENCH["workloads"]:
        cell = load(HERE, "workloads", w["name"] + ".json")
        cfg = load(ROOT, configs[w["config"]]["file"])
        assert (cell["config"], cell["traffic"]) == (w["config"], w["traffic"])
        assert cfg["serving"]["chips"] == w["chips"]
        assert cfg["source"] == configs[w["config"]]["source"]
        assert reduced_rule_faults(cfg, configs[w["config"]]) == []


def reduced_rule_faults(cfg: dict, entry: dict) -> list:
    """What is wrong with a configuration file's ``reduced`` against its entry
    in BENCHMARK.json. Each item of the file is ``"<key>: <published> ->
    <used>"``, where ``<key>`` is a top-level key of the file that holds
    ``<used>``; the entry lists those keys, in the same order (a name may hold
    no space, so the entry cannot carry the values); a configuration that is
    cut says in ``deployment`` what it stands for."""
    faults, keys = [], []
    for item in cfg["reduced"]:
        m = re.fullmatch(r"([A-Za-z0-9_.\-]+): (\S+) -> (\S+)", item) if isinstance(item, str) else None
        if not m:
            faults.append(f"not '<key>: <published> -> <used>': {item!r}")
            continue
        key, published, used = m.groups()
        keys.append(key)
        if key not in cfg or isinstance(cfg[key], (dict, list)):
            faults.append(f"{key!r} is no top-level value of the file")
        elif json.dumps(cfg[key]) != used:
            faults.append(f"the file holds {key} = {cfg[key]!r}, its reduced item says {used}")
        if published == used:
            faults.append(f"{key!r} is listed as reduced and is unchanged")
    if keys != entry["reduced"]:
        faults.append(f"the file reduces {keys}, BENCHMARK.json lists {entry['reduced']}")
    if cfg["reduced"] and not cfg.get("deployment"):
        faults.append("a cut configuration without a deployment")
    return faults


# OLMoE-1B-7B at 8 of its 16 layers (PERF.md 7): the first cut configuration the reach queue brings
CUT = {"num_hidden_layers": 8, "hidden_size": 2048, "reduced": ["num_hidden_layers: 16 -> 8"],
       "deployment": "8 of 16 layers on one v5e chip"}
CUT_ENTRY = {"reduced": ["num_hidden_layers"]}


@pytest.mark.parametrize("cfg, entry, fault", [
    (CUT, CUT_ENTRY, None),
    ({"reduced": []}, {"reduced": []}, None),
    (CUT, {"reduced": []}, "BENCHMARK.json lists"),
    (dict(CUT, reduced=[]), CUT_ENTRY, "BENCHMARK.json lists"),
    (dict(CUT, reduced=["num_hidden_layers"]), CUT_ENTRY, "<published> -> <used>"),
    (dict(CUT, num_hidden_layers=16), CUT_ENTRY, "the file holds num_hidden_layers = 16"),
    (dict(CUT, reduced=["num_layers: 16 -> 8"]), {"reduced": ["num_layers"]}, "no top-level value"),
    (dict(CUT, reduced=["num_hidden_layers: 8 -> 8"]), CUT_ENTRY, "unchanged"),
    (dict(CUT, deployment=""), CUT_ENTRY, "without a deployment"),
], ids=["a_cut_configuration", "an_uncut_one", "entry_lists_nothing", "file_lists_nothing", "a_bare_key",
        "the_file_holds_the_published_value", "an_unknown_key", "nothing_changed", "no_deployment"])
def test_reduced_in_a_configuration_file_agrees_with_benchmark_json(cfg, entry, fault):
    faults = reduced_rule_faults(cfg, entry)
    if fault is None:
        assert faults == []
    else:
        assert any(fault in f for f in faults), faults


@pytest.mark.parametrize("entry", BENCH["per_layer"], ids=[m["name"] for m in BENCH["per_layer"]])
def test_a_reader_has_its_attributes_and_moves_a_metric_its_cells_report(entry):
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(bench_run.load_readers("end_to_end")) == set(e2e)
    mod = bench_run.load_readers("layer_metrics")[entry["name"]]
    for attr in ("NAME", "UNIT", "LAYER", "MOVES", "read"):
        assert hasattr(mod, attr), (entry["name"], attr)
    assert (mod.UNIT, mod.LAYER, mod.MOVES) == (entry["unit"], entry["layer"], entry["moves"])
    moved = e2e[mod.MOVES]
    assert set(entry.get("workloads", cells)) <= set(moved.get("workloads", cells)) <= cells


def test_bytes_and_flops_reproduce_the_sizes_the_issue_states():
    def shape(name):
        return load(HERE, "configs", name + ".json")

    small = shape("qwen2.5-1.5b")
    # Qwen2.5-7B-Instruct's published config.json: the cell that runs it is PERF.md's first open row
    big = {"hidden_size": 3584, "intermediate_size": 18944, "num_hidden_layers": 28,
           "num_attention_heads": 28, "num_key_value_heads": 4, "vocab_size": 152064,
           "tie_word_embeddings": False, "torch_dtype": "bfloat16"}
    assert bytes_and_flops.kv_bytes_per_token(small) == 28672
    assert bytes_and_flops.kv_bytes_per_token(big) == 57344
    assert bytes_and_flops.weight_bytes(small) == small["memory_account_bytes"]["weights"]
    assert round(bytes_and_flops.weight_bytes(small) / 1e9, 2) == 3.09
    assert round(bytes_and_flops.weight_bytes(big) / 1e9, 1) == 15.2
    # a [32, 128] chunk with no history: 12.6 TFLOP
    assert round(bytes_and_flops.prefill_chunk_flops(small, 4096, 0) / 1e12, 1) == 12.6
    # one step at 32 lanes x 400 tokens of context: the weights once plus the live KV
    assert bytes_and_flops.decode_step_stream_bytes(small, 32 * 400) == (
        bytes_and_flops.weight_bytes(small) + 32 * 400 * 28672)
    assert bytes_and_flops.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        bytes_and_flops.load_peaks("TPU v9 imaginary")


def test_a_configuration_that_names_its_own_counts_gets_them(monkeypatch):
    import types

    small = load(HERE, "configs", "qwen2.5-1.5b.json")
    assert "bytes_and_flops" not in small and bytes_and_flops.for_config(small) is bytes_and_flops
    assert bytes_and_flops.for_config(small).decode_step_stream_bytes(small, 32 * 400) == (
        small["memory_account_bytes"]["weights"] + 32 * 400 * 28672)
    stub = types.ModuleType("benchmark.counts_of_a_stub")
    stub.param_count = lambda shape: 7
    stub.kv_bytes_per_token = lambda shape: 11
    stub.decode_step_stream_bytes = lambda shape, live, chips=1: (7 * 2 + live * 11) / chips
    stub.prefill_chunk_flops = lambda shape, positions, context: 13.0 * positions
    monkeypatch.setitem(sys.modules, "benchmark.counts_of_a_stub", stub)
    named = dict(small, bytes_and_flops="counts_of_a_stub")
    assert bytes_and_flops.for_config(named) is stub
    # the roofline reader takes its bytes from the module the file names
    reader = bench_run.load_readers("layer_metrics")["decode_step_roofline"]
    ctx = {"trace": {"devices": {"0": {"modules": [["jit_decode(1)", 0, 4_000_000]], "ops": []}}},
           "peaks": {"hbm_bytes_per_s": 1e9}, "engine_samples": [{"request_active_slots": 2}],
           "summary": {"mean_prompt_tokens": 90.0, "mean_output_tokens": 20.0},
           "config": named, "shape": small, "chips": 2}
    assert reader.read(ctx) == pytest.approx(100.0 * (14 + 2 * 100 * 11) / 2 / 1e9 / 1e-3)
    with pytest.raises(ModuleNotFoundError):
        bytes_and_flops.for_config(dict(small, bytes_and_flops="no_such_counts"))


TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


@pytest.mark.parametrize("account, chips, dev, engine, names", [
    # a config.json mapped onto a 0.7 GB dense impostor under a 7.1 GB model's name
    ({"weights": 7_130_000_000}, 1, TPU, {"device_memory": [{"peak_bytes_in_use": 700_000_000}]},
     ["700000000", "7130000000"]),
    # the registered cell's own figures (my chip runs, PR 25)
    (load(HERE, "configs", "qwen2.5-1.5b.json")["memory_account_bytes"], 1, TPU,
     {"device_memory": [{"peak_bytes_in_use": 5_935_000_000}]}, None),
    # four chips hold a quarter each; the fullest chip is the one read
    (load(HERE, "configs", "qwen2.5-7b-tp4.json")["memory_account_bytes"], 4, dict(TPU, count=4),
     {"device_memory": [{"peak_bytes_in_use": 4_400_000_000}, {"peak_bytes_in_use": 4_512_451_328}]}, None),
    (load(HERE, "configs", "qwen2.5-7b-tp4.json")["memory_account_bytes"], 4, dict(TPU, count=4),
     {"device_memory": [{"peak_bytes_in_use": 3_000_000_000}] * 4}, ["3000000000", "15231233024"]),
    # on a TPU a missing figure is itself a reason; a rehearsal on the CPU has nothing to hold
    ({"weights": 7_130_000_000}, 1, TPU, {"device_memory": [{}]}, ["no memory_peak_bytes", "7130000000"]),
    ({"weights": 7_130_000_000}, 1, {"platform": "cpu", "kind": "cpu", "count": 1}, {}, None),
], ids=["an_impostor", "the_registered_cell", "a_quarter_a_chip", "a_quarter_missing", "no_figure", "a_rehearsal"])
def test_the_server_holds_the_weights_its_configuration_accounts_for(account, chips, dev, engine, names):
    device = bench_run.device_report(dev, engine)
    reason = bench_run.weights_not_held(account, chips, device)
    if names is None:
        assert reason is None
    else:  # the reason is what turns `correct` false, and it names both figures
        assert reason and all(n in reason for n in names), reason


def test_benchmark_json_uses_only_the_allowed_characters():
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [w["name"] for w in BENCH["workloads"]] + [c["name"] for c in BENCH["configs"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    assert all(name.match(n) for n in names), names
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    assert all(unit.match(m["unit"]) for m in BENCH["end_to_end"] + BENCH["per_layer"])
    assert all(m["better"] in ("lower", "higher") for m in BENCH["end_to_end"] + BENCH["per_layer"])
    assert all(0.01 <= m["bound"] <= 0.1 for m in BENCH["end_to_end"])
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    for w in BENCH["workloads"] + BENCH["configs"]:
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    for folder in BENCH["paths"]:
        for dirpath, _, files in os.walk(os.path.join(ROOT, folder)):
            if "__pycache__" in dirpath:
                continue
            for f in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(dirpath, f)


def test_a_workload_without_a_file_fails_the_run_and_a_parked_one_is_known_as_such():
    with pytest.raises(bench_run.BenchFailure, match="no.such.cell"):
        bench_run.Launch("no.such.cell", 1, False, True)
    registered = {w["name"] for w in BENCH["workloads"]}
    parked = {os.path.basename(p)[:-5] for p in CELLS} - registered
    assert registered <= {os.path.basename(p)[:-5] for p in CELLS}
    assert parked == {"chat.qwen2.5-1.5b"}  # PERF.md 7 says why; a cell registered later leaves this set


def test_a_registered_metric_without_a_reader_fails_the_run():
    bench = {"per_layer": [{"name": "no_such_metric", "unit": "ms"}]}
    with pytest.raises(bench_run.BenchFailure, match="no_such_metric"):
        bench_run.read_metrics(bench, "per_layer", "any.cell", {})


def test_plain_reference_agrees_with_the_program_at_a_tiny_width():
    """The yardstick's own forward pass against ``models.llama.forward`` on the
    CPU in float32: GQA, q/k/v bias, rope, tied head."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference
    from dynamo_tpu.models.llama import LlamaConfig, forward, init_params, make_kv_cache

    shape = dict(load(HERE, "rehearse.json")["shape"], vocab_size=96)
    cfg = LlamaConfig(
        vocab_size=96, hidden_size=shape["hidden_size"],
        intermediate_size=shape["intermediate_size"], num_layers=shape["num_hidden_layers"],
        num_heads=shape["num_attention_heads"], num_kv_heads=shape["num_key_value_heads"],
        head_dim=shape["head_dim"], rope_theta=shape["rope_theta"],
        rms_norm_eps=shape["rms_norm_eps"], tie_embeddings=True, qkv_bias=True,
        dtype=jnp.float32)
    assert cfg.num_heads > cfg.num_kv_heads > 1
    params = init_params(jax.random.PRNGKey(3), cfg)
    params["layers"]["bq"] = jax.random.normal(jax.random.PRNGKey(4), params["layers"]["bq"].shape)
    params["layers"]["bk"] = jax.random.normal(jax.random.PRNGKey(5), params["layers"]["bk"].shape)
    tokens = np.arange(5, 5 + 24, dtype=np.int32) % 96
    with jax.default_matmul_precision("highest"):
        want = forward(params, cfg, jnp.asarray(tokens)[None], jnp.arange(24)[None],
                       make_kv_cache(cfg, 2, 16), jnp.arange(2, dtype=jnp.int32)[None],
                       use_pallas=False)[0][0]
    got = reference.logits(params, shape, jnp.asarray(tokens), jnp.arange(24))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


@pytest.mark.timeout(400)
@pytest.mark.parametrize("cell, trace", [("chat.qwen2.5-1.5b", 0), ("batch.qwen2.5-7b-tp4", 1)])
def test_a_new_cell_rehearses_on_the_cpu(cell, trace):
    """The whole control flow of a run at ``rehearse.json``'s tiny shape: the
    open loop and its pre-roll (``chat.qwen2.5-1.5b`` is parked: its file is
    there, BENCHMARK.json does not list it, PERF.md 7; ``run.py`` runs such a
    cell by hand); the four-chip cell on four virtual CPU devices,
    traced, so that the reference child makes its weights in their shardings.
    A rehearsal prints no device metric. The CPU engine does not sustain the
    open loop's rate at 32 lanes, so requests cut by the drain are no fault there."""
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell, "--seed", "2147483777",
         "--seconds", "6", "--trace", str(trace), "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=380)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(x) for x in done.stdout.splitlines() if x.startswith("{")]
    info, line = lines[-2]["info"], lines[-1]
    faults = [r for r in info["not_correct_because"] if trace or "requests failed" not in r]
    assert not faults and line["correct"] == (not info["not_correct_because"])
    assert line["attempted"] > 0 and line["metrics"] == {}
    chips = load(HERE, "configs", load(HERE, "workloads", cell + ".json")["config"] + ".json")["serving"]["chips"]
    assert (line["device"]["platform"], line["device"]["count"]) == ("cpu", chips)
    if trace:
        verdict = info["against_reference"]
        assert verdict["agrees"] and verdict["mesh"]["tp"] == chips
    else:
        assert info["end_to_end"]["client_lag_p90_ms"] is not None and "setup_s" in line["rehearsal"]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_int8_control_in_the_programs_place_comes_out_as_not_correct(seed):
    """``correct``'s control, at a width a test run can hold (hidden 256, four
    layers, bf16 weights): the program's own bf16 forward pass agrees with the
    float32 reference under every registered configuration's limit, and
    ``reference_control`` (the same pass in int8) does not, by the number that
    separates them on the chip too (PERF.md 2): 3 x or more apart. The tokens
    alone would pass both."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference, reference_child, reference_control
    from dynamo_tpu.models.llama import LlamaConfig, forward, init_params, make_kv_cache

    shape = dict(load(HERE, "rehearse.json")["shape"], hidden_size=256, intermediate_size=512,
                 num_hidden_layers=4, head_dim=32, vocab_size=4096)
    cfg = LlamaConfig(
        vocab_size=4096, hidden_size=256, intermediate_size=512, num_layers=4, num_heads=8,
        num_kv_heads=4, head_dim=32, rope_theta=shape["rope_theta"],
        rms_norm_eps=shape["rms_norm_eps"], tie_embeddings=True, qkv_bias=True, dtype=jnp.bfloat16)
    params = init_params(jax.random.PRNGKey(3), cfg)
    n, answered = 64, 24
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, 4096)
    at = jnp.arange(n - answered, n)
    program = forward(params, cfg, tokens[None], jnp.arange(n)[None], make_kv_cache(cfg, 8, 16),
                      jnp.arange(8, dtype=jnp.int32)[None], use_pallas=False)[0][0][n - answered:]
    want = np.asarray(reference.logits(params, shape, tokens, at))
    control = np.asarray(reference_control.logits(params, shape, tokens, at))
    limits = [load(ROOT, c["file"])["correct_limits"]["logprob_rms"] for c in BENCH["configs"]]
    assert limits and all(0 < x < 0.1 for x in limits)
    for limit in limits:
        sound = reference_child.held_against(
            want, *reference_child.answer_of(np.asarray(program, np.float32), 20), limit)
        lower = reference_child.held_against(want, *reference_child.answer_of(control, 20), limit)
        assert sound["agrees"] and sound["logprob_pairs"] == 20 * answered, sound
        assert not lower["agrees"] and lower["logprob_rms"] > 3 * sound["logprob_rms"], (sound, lower)
        # ... and it is the log-probabilities that tell them apart, not the tokens
        assert reference_child.held_against(want, control.argmax(axis=-1))["agrees"]
    # an answer without log-probabilities cannot pass a configuration that sets a limit
    assert not reference_child.held_against(want, want.argmax(axis=-1), (), limits[0])["agrees"]
