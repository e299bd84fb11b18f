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
# no open-loop cell is registered yet (PERF.md 7): the generator a later cell's file will name is tested on this one
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
        assert cfg["source"] == configs[w["config"]]["source"] and cfg["reduced"] == []


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
