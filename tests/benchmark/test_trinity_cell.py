"""What ``long.trinity-large-preview`` brings to the benchmark beside its data:
the module that counts the configuration's bytes and operations against the
program's own parameters, pool and rings (at the cell's shape, by shapes
alone); the memory account's arithmetic; the cell's file against its entry, the
parameters ISSUE 65 names and the catalog row; the reference and the control of
``correct`` at a width a test can hold; and the three new readers
(``swa_history_read_share``, ``swa_live_read_share``, ``prefill_chunk_mfu``) on
made-up counters.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import bytes_and_flops_trinity as baf  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

CELL, CONFIG = "long.trinity-large-preview", "trinity-large-preview"

# the cut's structure at a width a test can hold: three window layers to one full and one more,
# the first feed-forward dense, 8 held of 32 experts, 4 a token, beside a shared expert; 8 heads over 2
SMALL = {
    "model_type": "afmoe", "hidden_size": 256, "intermediate_size": 512, "num_hidden_layers": 5,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention", "sliding_attention"],
    "global_attn_every_n_layers": 4, "sliding_window": 32, "num_dense_layers": 1,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 32,
    "moe_intermediate_size": 128, "num_experts": 8, "num_experts_published": 32, "first_expert": 0,
    "num_experts_per_tok": 4, "num_shared_experts": 1, "score_func": "sigmoid", "route_norm": True,
    "route_scale": 2.448, "n_group": 1, "topk_group": 1, "num_expert_groups": 1, "num_limited_groups": 1,
    "mup_enabled": True, "rms_norm_eps": 1e-5, "rope_theta": 10000, "rope_scaling": None,
    "hidden_act": "silu", "tie_word_embeddings": False, "vocab_size": 4096,
}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def the_configuration():
    return load("benchmark", "configs", CONFIG + ".json")


def made(shape, what):
    """The shapes ``models/trinity.py`` makes for ``shape`` (nothing is made)."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine_jax.weights import afmoe_config
    from dynamo_tpu.models import trinity

    cfg = afmoe_config(shape, jnp.bfloat16)
    return jax.tree.leaves(jax.eval_shape(lambda: {
        "params": lambda: trinity.init_params(jax.random.PRNGKey(0), cfg),
        "pool": lambda: trinity.make_kv_cache(cfg, 6144, 16),
        "rings": lambda: trinity.make_slot_state(cfg, 8),
    }[what]()))


@pytest.mark.parametrize("which", ["small", "configuration"])
def test_param_count_is_the_number_of_elements_the_program_makes(which):
    """At the cell's shape (shapes only, nothing is made): 4,321,903,872,
    ISSUE 65's count by hand, and ``memory_account_bytes`` is the module's
    counts: the weights in bf16, the pool and the rings as the program
    allocates them."""
    shape = SMALL if which == "small" else the_configuration()
    assert baf.param_count(shape) == sum(int(a.size) for a in made(shape, "params"))
    if which == "configuration":
        account = shape["memory_account_bytes"]
        assert baf.param_count(shape) == 4_321_903_872
        assert (baf.attention_params(shape), baf.dense_ffn_params(shape), baf.expert_params(shape),
                baf.router_params(shape)) == (62_914_816, 113_246_208, 28_311_552, 786_432 + 256)
        dense_layer, expert_layer = 62_914_816 + 12_288 + 113_246_208, 62_914_816 + 12_288 + 786_688 + 33 * 28_311_552
        assert (dense_layer, expert_layer) == (176_173_312, 997_995_008)
        assert dense_layer + 4 * expert_layer + 2 * 25024 * 3072 + 3072 == 4_321_903_872
        # an expert layer WHOLE: 256 routed experts and the shared one, 14.7 GB in bf16: no chip holds one
        assert 2 * (expert_layer + 224 * 28_311_552) == 14_679_565_312
        assert account["weights"] == baf.weight_bytes(shape) == 8_643_807_744
        assert account["kv_bytes_per_token"] == baf.kv_bytes_per_token(shape) == 8_192
        assert account["kv_block"] == 16 * account["kv_bytes_per_token"]
        assert account["kv_pool"] == sum(a.size * a.dtype.itemsize for a in made(shape, "pool")) == 805_306_368
        assert [a.shape for a in made(shape, "pool")] == [(1, 6144, 16, 8, 128)] * 2
        # the window layers' cache: 8 slots x 4 layers x (4,096 + one block) positions, K and V
        assert [a.shape for a in made(shape, "rings")] == [(8, 8, 4096 + 16, 128)] * 8
        assert account["slot_state"] == 8 * baf.ring_bytes_per_slot(shape) == 1_077_936_128
        assert account["slot_state"] == sum(a.size * a.dtype.itemsize for a in made(shape, "rings"))
        assert account["dense_history_buffer"] == 1 * 8 * 8192 * 8192
        # one lifetime for every layer: five layers of pages where the two lifetimes hold one and four rings
        assert 5 * account["kv_pool"] == 4_026_531_840 > account["kv_pool"] + account["slot_state"] == 1_883_242_496
        held = account["weights"] + account["kv_pool"] + account["slot_state"]
        assert 0.65 < held / account["hbm"] < 0.67 and account["weights"] > 0.25 * account["hbm"]


def test_a_decode_step_streams_the_window_and_not_the_lane_and_a_chunk_counts_each_product_once():
    """A decode step: every weight outside the routed experts and the embedding
    once, the routed experts ONE lane hits (charged low), a lane's rings as far
    as the window reaches and its full layer's pages whole. A chunk: every
    product once whatever the parts, the held share of a token's 4 experts, a
    window layer's attention capped at the window."""
    shape = the_configuration()
    assert baf.lanes_of(shape) == 8
    routed = 4 * 32 * baf.expert_params(shape) * 2
    outside = baf.weight_bytes(shape) - routed - 25024 * 3072 * 2  # the embedding: by row
    assert (routed, outside) == (7_247_757_312, 1_242_302_976)
    one_lane = 1 - (1 - 4 / 256)
    at_rest = baf.decode_step_stream_bytes(shape, 0.0)
    assert at_rest == pytest.approx(outside + one_lane * routed)
    short = baf.decode_step_stream_bytes(shape, 8 * 1000.0) - at_rest
    assert short == pytest.approx(8 * 1000 * 5 * 8192)  # under the window every layer reads the lane whole
    long = baf.decode_step_stream_bytes(shape, 8 * 6000.0) - at_rest
    assert long == pytest.approx(8 * (4 * 4096 + 6000) * 8192)  # past it the window layers read 4,096
    assert baf.windowed_context(shape, 1000.0) == 1000.0
    assert baf.windowed_context(shape, 2944.0) == pytest.approx(4096 - 4096 ** 2 / (4 * 2944))
    per_token = (5 * (62_914_816 - 256) + 113_246_208
                 + 4 * ((1 + 4 * 32 / 256) * 28_311_552 + 3072 * 256))
    assert baf.prefill_chunk_flops(shape, 1024, 0.0) == pytest.approx(1024 * 2 * per_token)
    attn = baf.prefill_chunk_flops(shape, 1024, 2944.0) - baf.prefill_chunk_flops(shape, 1024, 0.0)
    assert attn == pytest.approx(1024 * 4 * 48 * 128 * (2944 + 4 * baf.windowed_context(shape, 2944.0)))


def test_the_cells_file_and_its_entry_agree():
    """The traffic ISSUE 65 names: closed, 8 clients = slots, pre-roll 16 s,
    prompts uniform 5,120-6,656, outputs uniform 32-96, no sharing; one chip;
    the depth, the leading dense layers, the held experts and the vocabulary
    reduced and nothing else: every width as published; and every number of the
    catalog row under its key."""
    bench, cell, cfg = load("BENCHMARK.json"), load("benchmark", "workloads", CELL + ".json"), the_configuration()
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (cell["config"], cell["traffic"], 1)
    assert (cell["config"], cell["traffic"]) == (CONFIG, "long")
    assert cell["arrivals"] == {"gen": "closed", "clients": 8} and cell["preroll_s"] == 16
    assert cell["prompt_tokens"] == {"gen": "uniform_int", "lo": 5120, "hi": 6656}
    assert cell["output_tokens"] == {"gen": "uniform_int", "lo": 32, "hi": 96}
    assert cell["sharing"].startswith("none")
    for said in ("1.25-1.6 windows", "wraps every", "long-document", "8 callers and not the 16", "16 rows an expert",
                 "No second cell", "short and long prompts in one", "prefix reuse", "long_reference_probe"):
        assert said in cell["why"], said
    conf = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == ["num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    # the README's form: "<key>: <published> -> <used>", <key> a top-level key of the file holding <used>
    assert cfg["reduced"] == ["num_hidden_layers: 60 -> 5", "num_dense_layers: 6 -> 1", "num_experts: 256 -> 32",
                              "vocab_size: 200192 -> 25024"]
    for line in cfg["reduced"]:
        key, change = line.split(": ")
        published, used = change.split(" -> ")
        assert cfg[key] == int(used) and cfg[key + "_published"] == int(published)
    assert conf["source"] == cfg["source"] and conf["file"] == f"benchmark/configs/{CONFIG}.json"
    assert cfg["serving"]["chips"] == 1 and cfg["first_expert"] == 0
    listed = {m["name"]: m for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    # the `moe_*` lists are pinned `==` by tests under tests/benchmark/ that a PR of this kind edits not
    # (test_window_readers.py, test_kimi_linear_cell.py): a `benchmark` PR appends this cell (ROADMAP B11)
    assert set(listed) == {"swa_history_read_share", "swa_live_read_share", "prefill_chunk_mfu",
                           "chunk_history_read_share"}
    for name, unit, better, source, layer in (
            ("swa_history_read_share", "%", "lower", "program_counter", "model, window attention"),
            ("swa_live_read_share", "%", "higher", "program_counter", "model, window attention"),
            ("prefill_chunk_mfu", "%", "higher", "device_trace", "model, prompt processing")):
        assert listed[name] == {"name": name, "unit": unit, "better": better, "source": source, "layer": layer,
                                "moves": "ttft_mean_ms", "workloads": [CELL]}
        reader = bench_run.load_readers("layer_metrics")[name]
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) == (name, unit, layer, "ttft_mean_ms")
    for said in ("over 8 TPU v5e chips", "32 of a layer's 256", "all 48 query heads", "25,024 of 200,192",
                 "12 pipeline stages", "96 chips", "16 rows an expert", "No width is cut"):
        assert said in cfg["deployment"], said
    flags = cfg["serving"]["server_flags"]
    assert flags == ["--max-batch-size", "8", "--max-model-len", "8192", "--kv-block-size", "16"]
    assert cfg["serving"]["engine_args"] == {"decode_steps": 4, "seed": 0} and cfg["serving"]["ready_timeout_s"] == 900
    assert (cfg["reference"], cfg["bytes_and_flops"]) == ("reference_trinity", "bytes_and_flops_trinity")
    assert cfg["layer_types"] == ["sliding_attention"] * 3 + ["full_attention", "sliding_attention"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(line) for line in f if '"Trinity-Large-Preview"' in line)
        assert row["source_url"] == cfg["source"]
        differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
        # layer_types is the published list's first five: its length IS num_hidden_layers
        assert differs == set(conf["reduced"]) | {"layer_types"}, differs
        assert cfg["layer_types"] == row["config"]["layer_types"][:5]
        assert all(cfg[k] == row["config"][k] for k in (
            "hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim", "num_attention_heads",
            "num_key_value_heads", "num_experts_per_tok", "sliding_window", "route_scale", "rope_theta"))
    assumed = " ".join(cfg["assumed"])
    for said in ("BEFORE any rotation", "rotates NOTHING", "p - 4095 .. p", "sigmoid(g)", "sqrt(hidden_size)",
                 "1e-20", "SMALL SEEDED VALUES", "the published code wins", "THREE bfloat16 parts", "8192"):
        assert said in assumed, said
    limit = cfg["correct_limits"]["logprob_rms"]
    assert 0.0139 <= limit <= 0.0434  # what tests/benchmark/test_benchmark.py allows a configuration


def test_the_schedule_is_the_seeds_and_fits_the_configurations_positions():
    """What ``test_benchmark.py`` holds every cell's schedule to, with the
    bound read from the cell's own configuration (``--max-model-len 8192``)
    where that test says 2,048 (``conftest.py``): a pure function of the seed,
    another seed the same 32 (prompt, output) pairs in another order, every
    length inside its range, every prompt past the window and no request past
    the positions the server keeps."""
    from benchmark import traffic

    cell, cfg = load("benchmark", "workloads", CELL + ".json"), the_configuration()
    flags = cfg["serving"]["server_flags"]
    positions = int(flags[flags.index("--max-model-len") + 1])
    a = traffic.build_schedule(cell, 2147483659, 30.0)
    assert a == traffic.build_schedule(cell, 2147483659, 30.0)
    b = traffic.build_schedule(cell, 7, 30.0)
    assert a["text_seed"] != b["text_seed"] and a["due"] is None and a["clients"] == 8
    n = traffic.BLOCK
    for key in ("prompt_tokens", "output_tokens"):
        lo, hi = cell[key]["lo"], cell[key]["hi"]
        assert all(lo <= x <= hi for x in a[key])
        assert a[key][:n] != b[key][:n] and sorted(a[key][:n]) == sorted(b[key][:n])
    pairs = [sorted(zip(s["prompt_tokens"][:n], s["output_tokens"][:n])) for s in (a, b)]
    assert pairs[0] == pairs[1]
    assert min(a["prompt_tokens"]) > cfg["sliding_window"] + 16  # every lane wraps every ring
    assert positions == 8192 and max(p + o for p, o in zip(a["prompt_tokens"], a["output_tokens"])) <= positions
    assert sum(a["prompt_tokens"][:n]) / n == pytest.approx(5888, abs=1)


@pytest.mark.timeout(300)
def test_the_int8_control_in_the_programs_place_comes_out_as_not_correct():
    """The program's own pass at hidden 256 (bf16 weights, float32 activations
    in three bfloat16 parts, chunks of 16 through the full layer's pages and
    the window layers' rings, 96 positions through a window of 32 and a ring of
    48) agrees with the float32 reference under the configuration's limit;
    ``reference_control_trinity`` (every product against a weight in int8, the
    router float32) does not, 3 x and more apart."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_child, reference_control_trinity, reference_trinity
    from dynamo_tpu.engine_jax.weights import afmoe_config
    from dynamo_tpu.models import trinity

    cfg = afmoe_config(SMALL, jnp.bfloat16)
    params = trinity.init_params(jax.random.PRNGKey(3), cfg)
    n, answered, chunk = 96, 24, 16
    tokens = jax.random.randint(jax.random.PRNGKey(0), (n,), 0, SMALL["vocab_size"])
    at = jnp.arange(n - answered, n)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference_trinity.logits(params, SMALL, tokens, at))
        control = np.asarray(reference_control_trinity.logits(params, SMALL, tokens, at))
    cache, state = trinity.make_kv_cache(cfg, 16, 16), trinity.make_slot_state(cfg, 2)
    step = jax.jit(lambda toks, pos, cache, state: trinity.forward_chunk(
        params, cfg, toks, pos, cache, jnp.arange(1, 9, dtype=jnp.int32)[None], state, jnp.asarray([1])))
    out = []
    for lo in range(0, n, chunk):
        h, cache, state, _ = step(tokens[None, lo:lo + chunk], jnp.arange(lo, lo + chunk)[None], cache, state)
        out.append(trinity.lm_head(params, cfg, h[0]))
    program = np.asarray(jnp.concatenate(out), np.float32)[n - answered:]
    limit = the_configuration()["correct_limits"]["logprob_rms"]
    sound = reference_child.held_against(want, *reference_child.answer_of(program, 20), limit)
    lower = reference_child.held_against(want, *reference_child.answer_of(control, 20), limit)
    assert sound["agrees"] and sound["logprob_pairs"] == 20 * answered, sound
    assert not lower["agrees"] and lower["logprob_rms"] > 3 * sound["logprob_rms"], (sound, lower)


def test_the_three_readers_read_made_up_counters_and_nothing_without_them():
    """The two window shares are rises of the module's counters over the
    window's samples (else the two ends of the run); ``prefill_chunk_mfu`` is
    ``prefill_chunk_flops`` of the tokens the traced chunk dispatches took over
    their device seconds and the chip's peak. Each returns None from a parent
    without the module (no such counter), without a trace, or where nothing
    rose."""
    readers = bench_run.load_readers("layer_metrics")
    share, live, mfu = (readers[n].read for n in ("swa_history_read_share", "swa_live_read_share", "prefill_chunk_mfu"))
    ends = {"engine_before": {"swa_history_positions_read": 1_000, "swa_history_positions_live": 500,
                              "swa_history_positions_whole": 2_000},
            "engine_after": {"swa_history_positions_read": 83_240, "swa_history_positions_live": 66_292,
                             "swa_history_positions_whole": 102_000}}
    assert share(ends) == pytest.approx(82.24) and live(ends) == pytest.approx(80.0)
    samples = [{"swa_history_positions_read": 10_000 * k, "swa_history_positions_live": 9_000 * k,
                "swa_history_positions_whole": 16_000 * k, "t": 0.5 * k} for k in (1, 2, 3)]
    assert share({**ends, "engine_samples": samples}) == 62.5  # the window's own ends win
    assert live({**ends, "engine_samples": samples}) == 90.0
    for read in (share, live):
        assert read({"engine_before": {"x": 1}, "engine_after": {"x": 2}}) is None  # a parent without the module
        assert read({}) is None
        assert read({"engine_before": ends["engine_before"], "engine_after": dict(ends["engine_before"])}) is None

    cfg = the_configuration()
    shape = {k: v for k, v in cfg.items() if not isinstance(v, dict)}
    trace = {"devices": {"0": {"modules": [["jit_chunk(1)", 0, 80_000_000], ["jit_decode(2)", 90_000_000, 20_000_000],
                                           ["jit_chunk(1)", 120_000_000, 70_000_000]], "ops": []}}, "host": []}
    counted = [{"chunk_tokens_fed": 10_000, "chunk_dispatches_by_rows": {"2": 3, "8": 7}, "t": 0.5},
               {"chunk_tokens_fed": 80_000, "chunk_dispatches_by_rows": {"1": 5, "2": 15, "8": 90}, "t": 47.5}]
    ctx = {"trace": trace, "peaks": {"bf16_flops_per_s": 197e12}, "engine_samples": counted, "config": cfg,
           "shape": shape, "summary": {"mean_prompt_tokens": 5888.0}}
    tokens = 70_000 / 100 * 2  # a dispatch's mean tokens x the two traced
    want = 100.0 * baf.prefill_chunk_flops(shape, tokens, 2944.0) / 0.150 / 197e12
    assert mfu(ctx) == pytest.approx(want) and 3.0 < want < 12.0
    assert mfu({**ctx, "trace": None}) is None and mfu({**ctx, "peaks": None}) is None
    assert mfu({**ctx, "engine_samples": [], "engine_before": {"x": 1}, "engine_after": {"x": 2}}) is None
    no_chunk = {"devices": {"0": {"modules": [["jit_decode(2)", 0, 5]], "ops": []}}, "host": []}
    assert mfu({**ctx, "trace": no_chunk}) is None
