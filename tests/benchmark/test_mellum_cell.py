"""What ``code.mellum2-12b-a2.5b-tp4`` brings to the benchmark beside its data:
the module that counts the configuration's bytes and operations against the
program's own parameters, pool and rings (at the cell's shape, by shapes
alone) and against ``param_shardings`` on a four-device mesh; the memory
account's arithmetic; the cell's file against its entry, the parameters
ISSUE 68 names and the catalog row; the reference and the control of
``correct`` at a width a test can hold; and the four new readers
(``prefill_chunk_mfu_per_chip``, ``moe_fullest_shard_share``,
``exchange_bytes_per_token``, ``exchange_share``) on made-up counters and a
made-up trace.
"""

import json
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import bytes_and_flops_mellum as baf  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

CELL, CONFIG = "code.mellum2-12b-a2.5b-tp4", "mellum2-12b-a2.5b-tp4"
SOURCE = "https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json"

ROPE = {"full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                           "original_max_position_embeddings": 64, "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
# the structure at a width a test can hold: two periods of three window layers and a full one,
# 16 experts, 4 a token; 8 heads over 4 KV heads
SMALL = {
    "model_type": "mellum", "hidden_size": 256, "intermediate_size": 512, "num_hidden_layers": 8,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2, "mlp_layer_types": ["sparse"] * 8,
    "sliding_window": 32, "use_sliding_window": True, "max_window_layers": 0, "num_attention_heads": 8,
    "num_key_value_heads": 4, "head_dim": 32, "attention_bias": False, "moe_intermediate_size": 128,
    "num_experts": 16, "num_experts_per_tok": 4, "norm_topk_prob": True, "hidden_act": "silu",
    "rms_norm_eps": 1e-6, "rope_parameters": ROPE, "tie_word_embeddings": False, "vocab_size": 4096,
    "max_position_embeddings": 131072,
}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def the_configuration():
    return load("benchmark", "configs", CONFIG + ".json")


def config_of(shape):
    import jax.numpy as jnp

    from dynamo_tpu.engine_jax.weights import mellum_config

    return mellum_config(shape, jnp.bfloat16)


def made(shape, what):
    """The shapes ``models/mellum.py`` makes for ``shape`` (nothing is made)."""
    import jax

    from dynamo_tpu.models import mellum

    cfg = config_of(shape)
    return jax.eval_shape(lambda: {
        "params": lambda: mellum.init_params(jax.random.PRNGKey(0), cfg),
        "pool": lambda: mellum.make_kv_cache(cfg, 6144, 16),
        "rings": lambda: mellum.make_slot_state(cfg, 16),
    }[what]())


def nbytes(tree):
    import jax

    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


@pytest.mark.parametrize("which", ["small", "configuration"])
def test_param_count_is_the_number_of_elements_the_program_makes(which):
    """At the cell's shape (shapes only, nothing is made): 12,149,923,072
    parameters in 24,308,380,672 B, ISSUE 68's count by hand, of which
    ``param_shardings`` leaves 6,089,896,960 B on each of four chips; and
    ``memory_account_bytes`` is the module's counts: the pool and the rings as
    the program allocates them, one KV head a chip."""
    import jax

    from dynamo_tpu.models import mellum
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    shape = SMALL if which == "small" else the_configuration()
    params = made(shape, "params")
    assert baf.param_count(shape) == sum(int(a.size) for a in jax.tree.leaves(params))
    assert baf.weight_bytes(shape) == nbytes(params)
    shardings = mellum.param_shardings(config_of(shape), make_mesh(MeshConfig(tp=4)))
    a_chip = sum(math.prod(s.shard_shape(a.shape)) * a.dtype.itemsize for a, s in zip(
        jax.tree.leaves(params), jax.tree.leaves(shardings, is_leaf=lambda s: hasattr(s, "shard_shape"))))
    assert baf.weight_bytes_per_chip(shape, 4) == a_chip
    if which == "configuration":
        account = shape["memory_account_bytes"]
        assert baf.param_count(shape) == 12_149_923_072
        assert (baf.attention_matrices(shape), baf.expert_params(shape)) == (21_233_664, 6_193_152)
        layer = 21_233_664 + 64 * 6_193_152 + 147_456 + 4_608 + 256
        assert layer == 417_747_712 and 28 * layer + 2 * 226_492_416 + 2_304 == 12_149_923_072
        # what a token goes through: the name's A2.5B
        assert 28 * (21_233_664 + 147_456 + 8 * 6_193_152) + 2 * 226_492_416 == 2_438_922_240
        assert account["weights"] == baf.weight_bytes(shape) == 24_308_380_672
        assert account["weights_per_chip"] == a_chip == 6_072_827_904 + 17_069_056 == 6_089_896_960
        assert account["kv_bytes_per_token"] == baf.kv_bytes_per_token(shape) == 28_672
        assert account["kv_block"] == 16 * account["kv_bytes_per_token"]
        assert account["kv_pool"] == nbytes(made(shape, "pool")) == 2_818_572_288 == 4 * account["kv_pool_per_chip"]
        assert [a.shape for a in jax.tree.leaves(made(shape, "pool"))] == [(7, 6144, 16, 4, 128)] * 2
        assert [a.shape for a in jax.tree.leaves(made(shape, "rings"))] == [(7, 3, 16, 4, 1024 + 16, 128)] * 2
        assert account["slot_state"] == 16 * baf.ring_bytes_per_slot(shape) == 1_431_306_240
        assert account["slot_state"] == nbytes(made(shape, "rings")) == 4 * account["slot_state_per_chip"]
        assert account["dense_history_buffer"] == 7 * 16 * 4096 * 4096 == 4 * account["dense_history_per_chip"]
        held = account["weights_per_chip"] + account["kv_pool_per_chip"] + account["slot_state_per_chip"]
        assert 0.44 < held / account["hbm"] < 0.46 and account["weights_per_chip"] > 0.25 * account["hbm"]
        # no chip holds it, one host does
        assert account["weights"] > account["hbm"] and account["weights"] < 4 * account["hbm"]


def test_a_decode_step_streams_a_chips_share_and_a_chunk_counts_each_product_once():
    """A decode step on one of four chips: a quarter of every matrix outside the
    experts and the embedding, the routers and norms whole, the share of its
    experts the deployment's 16 lanes hit under even routing (ISSUE 68's charge;
    ``lanes=`` for a caller that knows how many decode), a quarter (one KV head) of the lanes'
    rings as far as the window reaches and of the full layers' pages. A chunk:
    every product once whatever the parts, a token's 8 experts, a window layer's
    attention capped at the window; the whole model's."""
    shape = the_configuration()
    experts = 28 * 64 * 6_193_152 * 2
    outside = (28 * 21_233_664 + 226_492_416) * 2
    replicated = baf.replicated_params(shape) * 4
    assert replicated == 17_069_056 and experts + outside + 226_492_416 * 2 + replicated == baf.weight_bytes(shape)
    at_rest = baf.decode_step_stream_bytes(shape, 0.0, 4)
    assert baf.experts_hit_share(shape, 16) == pytest.approx(1 - 0.875 ** 16)
    assert at_rest == pytest.approx((outside + experts * (1 - 0.875 ** 16)) / 4 + replicated)
    assert baf.decode_step_stream_bytes(shape, 0.0, 4, lanes=1) == pytest.approx((outside + experts / 8) / 4 + replicated)
    short = baf.decode_step_stream_bytes(shape, 16 * 500.0, 4) - at_rest
    assert short == pytest.approx(16 * 500 * 28 * 4096 / 4)  # under the window every layer reads the lane whole
    long = baf.decode_step_stream_bytes(shape, 16 * 2304.0, 4) - at_rest
    assert long == pytest.approx(16 * (21 * 1024 + 7 * 2304) * 4096 / 4)  # past it a window layer reads 1,024
    assert baf.windowed_context(shape, 500.0) == 500.0
    assert baf.windowed_context(shape, 1152.0) == pytest.approx(1024 - 1024 ** 2 / (4 * 1152))
    per_token = 28 * (21_233_664 + 147_456 + 8 * 6_193_152)
    assert baf.prefill_chunk_flops(shape, 1024, 0.0) == pytest.approx(1024 * 2 * per_token)
    attn = baf.prefill_chunk_flops(shape, 1024, 1152.0) - baf.prefill_chunk_flops(shape, 1024, 0.0)
    assert attn == pytest.approx(1024 * 4 * 32 * 128 * (7 * 1152 + 21 * baf.windowed_context(shape, 1152.0)))


def test_the_cells_file_and_its_entry_agree():
    """The traffic ISSUE 68 names: closed, 16 clients = slots, pre-roll 16 s,
    prompts uniform 1,536-3,072 (the issue's range, kept: the file gives the spread
    it read), outputs uniform 16-64, no sharing; four chips;
    NOTHING reduced; and every number of the catalog row under its key."""
    bench, cell, cfg = load("BENCHMARK.json"), load("benchmark", "workloads", CELL + ".json"), the_configuration()
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (cell["config"], cell["traffic"], 4)
    assert (cell["config"], cell["traffic"]) == (CONFIG, "code")
    assert cell["arrivals"] == {"gen": "closed", "clients": 16} and cell["preroll_s"] == 16
    assert cell["prompt_tokens"] == {"gen": "uniform_int", "lo": 1536, "hi": 3072}
    assert cell["output_tokens"] == {"gen": "uniform_int", "lo": 16, "hi": 64}
    assert cell["sharing"].startswith("none")
    for said in ("1.5-3 windows", "ISSUE 68's range, kept", "4.6 %", "wraps every ring", "code-completion", "ONE KV head a chip", "128 rows",
                 "No second cell", "short and long prompts in one", "prefix reuse", "long_reference_probe"):
        assert said in cell["why"], said
    conf = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == [] == cfg["reduced"]
    assert conf["source"] == cfg["source"] == SOURCE and conf["file"] == f"benchmark/configs/{CONFIG}.json"
    assert cfg["serving"]["chips"] == 4
    assert cfg["serving"]["server_flags"] == ["--tensor-parallel-size", "4", "--max-batch-size", "16",
                                              "--max-model-len", "4096", "--kv-block-size", "16"]
    assert sum(1 for w in bench["workloads"] if w["chips"] == 4) <= len(bench["workloads"]) // 4
    listed = {m["name"]: m for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    # the `moe_*`, `swa_*` and `prefill_chunk_mfu` lists are pinned `==` by tests under tests/benchmark/ that a
    # PR of this kind edits not: a `benchmark` PR appends this cell (ROADMAP B11)
    # ... and `collective_share` knows a collective by the compiler's name for it, which a `shard_map`'s `psum`
    # has not: `exchange_share` is this cell's one share of the collectives (ROADMAP B11 folds the two readers)
    assert set(listed) == {"chunk_history_read_share", "prefill_chunk_mfu_per_chip",
                           "moe_fullest_shard_share", "exchange_bytes_per_token", "exchange_share"}
    for name, unit, better, source, layer in (
            ("prefill_chunk_mfu_per_chip", "%", "higher", "device_trace", "model, prompt processing"),
            ("moe_fullest_shard_share", "%", "lower", "program_counter", "model, expert layer"),
            ("exchange_bytes_per_token", "KB", "lower", "program_counter", "sharding"),
            ("exchange_share", "%", "lower", "device_trace", "sharding")):
        assert listed[name] == {"name": name, "unit": unit, "better": better, "source": source, "layer": layer,
                                "moves": "ttft_mean_ms", "workloads": [CELL]}
    # every key of the catalog row's config, at the top level, as published
    row = next(json.loads(line) for line in open("/opt/skills/guides/model-configs/architectures.jsonl")
               if '"Mellum2-12B-A2.5B-Instruct"' in line) if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else None
    if row is not None:
        assert row["source_url"] == SOURCE
        for key, value in row["config"].items():
            assert cfg[key] == value, key
    # ... and the two rotary sections spelt flat beside the group, for a config.json of scalars and lists
    for kind, group in cfg["rope_parameters"].items():
        for key, value in group.items():
            assert cfg[f"rope_parameters_{kind}_{key}"] == value
    served = {k: v for k, v in cfg.items() if not isinstance(v, dict)}
    assert config_of(served) == config_of({**served, "rope_parameters": cfg["rope_parameters"]})


def test_the_schedule_is_the_seeds_and_fits_the_configurations_positions():
    """What ``test_benchmark.py`` holds every cell's schedule to, with the
    bound read from the cell's own configuration (``--max-model-len 4096``)
    where that test says 2,048 (``tests/conftest.py`` marks that one case as
    waiting, as ``tests/benchmark/conftest.py`` marks Trinity's): a pure function
    of the seed, another seed the same 32 (prompt, output) pairs in another
    order, every length inside its range, every prompt past the window and no
    request past the positions the server keeps."""
    from benchmark import traffic

    cell, cfg = load("benchmark", "workloads", CELL + ".json"), the_configuration()
    flags = cfg["serving"]["server_flags"]
    positions = int(flags[flags.index("--max-model-len") + 1])
    a = traffic.build_schedule(cell, 2147483659, 30.0)
    assert a == traffic.build_schedule(cell, 2147483659, 30.0)
    b = traffic.build_schedule(cell, 7, 30.0)
    assert a["text_seed"] != b["text_seed"] and a["due"] is None and a["clients"] == 16
    n = traffic.BLOCK
    for key in ("prompt_tokens", "output_tokens"):
        lo, hi = cell[key]["lo"], cell[key]["hi"]
        assert all(lo <= x <= hi for x in a[key])
        assert a[key][:n] != b[key][:n] and sorted(a[key][:n]) == sorted(b[key][:n])
    pairs = [sorted(zip(s["prompt_tokens"][:n], s["output_tokens"][:n])) for s in (a, b)]
    assert pairs[0] == pairs[1]
    assert min(a["prompt_tokens"]) > cfg["sliding_window"] + 16  # every lane wraps every ring
    assert positions == 4096 and max(p + o for p, o in zip(a["prompt_tokens"], a["output_tokens"])) <= positions
    assert sum(a["prompt_tokens"][:n]) / n == pytest.approx(2304, abs=1)


@pytest.mark.timeout(300)
def test_the_int8_control_in_the_programs_place_comes_out_as_not_correct():
    """The program's own pass at hidden 256 (bf16 weights, float32 activations
    in three bfloat16 parts, chunks of 16 through the full layers' pages and the
    window layers' rings, 96 positions through a window of 32 and a ring of 48)
    agrees with the float32 reference under the configuration's limit;
    ``reference_control_mellum`` (every product against a weight in int8, the
    router float32) does not, 3 x and more apart."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_child, reference_control_mellum, reference_mellum
    from dynamo_tpu.models import mellum

    cfg = config_of(SMALL)
    params = mellum.init_params(jax.random.PRNGKey(3), cfg)
    n, answered, chunk = 96, 24, 16
    tokens = jax.random.randint(jax.random.PRNGKey(0), (n,), 0, SMALL["vocab_size"])
    at = jnp.arange(n - answered, n)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference_mellum.logits(params, SMALL, tokens, at))
        control = np.asarray(reference_control_mellum.logits(params, SMALL, tokens, at))
    cache, state = mellum.make_kv_cache(cfg, 16, 16), mellum.make_slot_state(cfg, 2)
    step = jax.jit(lambda toks, pos, cache, state: mellum.forward_chunk(
        params, cfg, toks, pos, cache, jnp.arange(1, 9, dtype=jnp.int32)[None], state, jnp.asarray([1])))
    out = []
    for lo in range(0, n, chunk):
        h, cache, state, _ = step(tokens[None, lo:lo + chunk], jnp.arange(lo, lo + chunk)[None], cache, state)
        out.append(mellum.lm_head(params, cfg, h[0]))
    program = np.asarray(jnp.concatenate(out), np.float32)[n - answered:]
    limit = the_configuration()["correct_limits"]["logprob_rms"]
    sound = reference_child.held_against(want, *reference_child.answer_of(program, 20), limit)
    lower = reference_child.held_against(want, *reference_child.answer_of(control, 20), limit)
    assert sound["agrees"] and sound["logprob_pairs"] == 20 * answered, sound
    assert not lower["agrees"] and lower["logprob_rms"] > 3 * sound["logprob_rms"], (sound, lower)


def test_the_reference_child_makes_the_weights_in_the_modules_own_shardings():
    """``reference_child.py`` imports ``models/llama.py:param_shardings`` by
    name for every model: a configuration that is no ``LlamaConfig`` is handed
    on to its own module's."""
    from dynamo_tpu.models import mellum
    from dynamo_tpu.models.llama import param_shardings
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    cfg, mesh = config_of(SMALL), make_mesh(MeshConfig(tp=4))
    assert param_shardings(cfg, mesh) == mellum.param_shardings(cfg, mesh)
    assert str(param_shardings(cfg, mesh)["layers"]["w_gate"].spec) == str(
        mellum._param_specs("tp")["layers"]["w_gate"])


def test_exchange_share_finds_a_collective_by_its_operation_and_not_by_its_name():
    """``collective_share`` knows a collective by the name the compiler gives the
    instruction it inserts; one a module writes under ``shard_map`` is named after
    the JAX primitive (``%psum.283 = ... all-reduce(``). ``exchange_share`` reads
    the operation: the same share on a trace of the compiler's own collectives,
    and the module's where the other sees none. Nothing on one chip."""
    readers = bench_run.load_readers("layer_metrics")
    by_name, by_operation = readers["collective_share"], readers["exchange_share"]
    for line, found in (
            ("%psum.283 = f32[8,128,2304] all-reduce(f32[8,128,2304] %bitcast.4380), channel_id=1, replica_gro", True),
            ("%all_gather.7 = f32[16,98304] all-gather(f32[16,24576] %dot.3), channel_id=9", True),
            ("%all-reduce-start.1 = f32[16,1,2304] all-reduce-start(f32[16,1,2304] %add.5)", True),
            ("%all-reduce-done.1 = f32[16,1,2304] all-reduce-done(f32[16,1,2304] %all-reduce-start.1)", True),
            ("%ar.2 = (f32[6], s32[6]) all-reduce(f32[6] %a, s32[6] %b), channel_id=2", True),
            ("%fusion.12 = f32[8,128,2304] fusion(f32[8,128,2304] %all-reduce.4), kind=kLoop", False),
            ("%while.762 = (s32[], f32[8,128,2304], s32[6], s32[2], f32[7,4,16,128,1,128], /*index=5*/f32[7,4,", False),
            ("%conditional.3 = (s32[14], f32[7,4,16,128,1,128]) conditional(pred[] %p, (f32[2]) %all-reduce.1", False)):
        assert by_operation.is_collective(line) is found, line
    ops = [["%psum.283 = f32[8,128,2304] all-reduce(f32[8,128,2304] %bitcast.4380), channel_id=1", 100, 300],
           ["%all-reduce.5 = f32[16,98304] all-reduce(f32[16,98304] %dot.1)", 350, 100],
           ["%fusion.1 = f32[8,128,2304] fusion(f32[8,128,2304] %p)", 500, 400]]
    trace = {"devices": {"0": {"modules": [["jit_chunk(1)", 0, 1000]], "ops": ops}}, "host": []}
    ctx = {"trace": trace, "chips": 4}
    assert by_name.read(ctx) == pytest.approx(10.0) and by_operation.read(ctx) == pytest.approx(35.0)
    assert by_operation.read({**ctx, "chips": 1}) is None and by_operation.read({"trace": None, "chips": 4}) is None
    assert by_operation.read({"trace": {"devices": {}}, "chips": 4}) is None


def test_the_three_readers_read_made_up_counters_and_nothing_without_them():
    """``moe_fullest_shard_share`` and ``exchange_bytes_per_token`` are rises of
    the module's counters over the window's samples (else the two ends of the
    run); ``prefill_chunk_mfu_per_chip`` is ``prefill_chunk_flops`` of the
    tokens the traced chunk dispatches took, over the cell's chips, their
    device seconds and one chip's peak. Each returns None from a parent without
    the module (no such counter), without a trace, or where nothing rose."""
    readers = bench_run.load_readers("layer_metrics")
    fullest, exchange, mfu = (readers[n].read for n in (
        "moe_fullest_shard_share", "exchange_bytes_per_token", "prefill_chunk_mfu_per_chip"))
    cfg = the_configuration()
    shape = {k: v for k, v in cfg.items() if not isinstance(v, dict)}
    ends = {"engine_before": {"moe_pairs_fullest_shard": 1_000, "moe_pairs_all_shards": 3_000,
                              "exchange_rows": 56_000, "chunk_tokens_fed": 800},
            "engine_after": {"moe_pairs_fullest_shard": 28_000, "moe_pairs_all_shards": 103_000,
                             "exchange_rows": 616_000, "chunk_tokens_fed": 8_800},
            "shape": shape, "summary": {"output_tokens_in_window": 2_000}}
    assert fullest(ends) == pytest.approx(27.0)
    assert exchange(ends) == pytest.approx(560_000 * 2304 * 4 / 10_000 / 1e3)  # 516 KB: no padding row
    samples = [{"moe_pairs_fullest_shard": 300 * k, "moe_pairs_all_shards": 1_000 * k, "exchange_rows": 112 * k,
                "chunk_tokens_fed": k, "t": 0.5 * k} for k in (1, 2, 3)]
    assert fullest({**ends, "engine_samples": samples}) == 30.0  # the window's own ends win
    for read in (fullest, exchange):
        parent = {"engine_before": {"x": 1}, "engine_after": {"x": 2}, "shape": shape, "summary": {}}
        assert read(parent) is None  # a parent without the module
        assert read({"shape": shape, "summary": {}}) is None
        assert read({**ends, "engine_after": dict(ends["engine_before"])}) is None

    trace = {"devices": {"0": {"modules": [["jit_chunk(1)", 0, 80_000_000], ["jit_decode(2)", 90_000_000, 20_000_000],
                                           ["jit_chunk(1)", 120_000_000, 70_000_000]], "ops": []}}, "host": []}
    counted = [{"chunk_tokens_fed": 10_000, "chunk_dispatches_by_rows": {"2": 3, "16": 7}, "t": 0.5},
               {"chunk_tokens_fed": 80_000, "chunk_dispatches_by_rows": {"2": 8, "4": 12, "16": 90}, "t": 47.5}]
    ctx = {"trace": trace, "peaks": {"bf16_flops_per_s": 197e12}, "engine_samples": counted, "config": cfg,
           "shape": shape, "chips": 4, "summary": {"mean_prompt_tokens": 2304.0}}
    tokens = 70_000 / 100 * 2  # a dispatch's mean tokens x the two traced
    want = 100.0 * baf.prefill_chunk_flops(shape, tokens, 1152.0) / 4 / 0.150 / 197e12
    assert mfu(ctx) == pytest.approx(want) and 0.5 < want < 35.0
    assert mfu({**ctx, "chips": 1}) == pytest.approx(4 * want)
    assert mfu({**ctx, "trace": None}) is None and mfu({**ctx, "peaks": None}) is None
    assert mfu({**ctx, "engine_samples": [], "engine_before": {"x": 1}, "engine_after": {"x": 2}}) is None
    no_chunk = {"devices": {"0": {"modules": [["jit_decode(2)", 0, 5]], "ops": []}}, "host": []}
    assert mfu({**ctx, "trace": no_chunk}) is None
