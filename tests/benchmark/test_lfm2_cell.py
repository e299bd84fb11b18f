"""What ``batch.lfm2-24b-a2b`` brings to the benchmark beside its data: the
module that counts the configuration's bytes and operations against the
program's own parameters, pool and state; the cell's file against its entry;
the control of ``correct`` at a width a test can hold; and the two readers of
the expert layer's counters (``moe_rows_per_held_expert``,
``moe_experts_hit_share``: unregistered, PERF.md 7) on what a rehearsal of this
cell's server counted.
"""

import asyncio
import json
import os
import random
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import bytes_and_flops_lfm2 as baf  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

CELL, CONFIG = "batch.lfm2-24b-a2b", "lfm2-24b-a2b"

# the first six layers of the published pattern (conv, conv, attention, conv,
# conv, conv: the two dense feed-forwards and four expert layers) at a width a
# test can hold; 4 of 16 experts a token; four KV heads of 32 a page row
SMALL = {
    "model_type": "lfm2_moe", "hidden_size": 256, "intermediate_size": 512, "num_hidden_layers": 6,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv"], "num_dense_layers": 2,
    "num_attention_heads": 8, "num_key_value_heads": 4, "conv_L_cache": 3, "conv_bias": False,
    "moe_intermediate_size": 128, "num_experts": 16, "num_experts_per_tok": 4, "norm_topk_prob": True,
    "use_expert_bias": True, "routed_scaling_factor": 1, "norm_eps": 1e-5, "rope_theta": 1000000,
    "vocab_size": 4096, "tie_word_embeddings": True,
}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def the_configuration():
    return load("benchmark", "configs", CONFIG + ".json")


def made(shape, what):
    """The shapes ``models/lfm2.py`` makes for ``shape`` (nothing is made)."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine_jax.weights import lfm2_config
    from dynamo_tpu.models import lfm2

    cfg = lfm2_config(shape, jnp.bfloat16)
    return jax.tree.leaves(jax.eval_shape(lambda: {
        "params": lambda: lfm2.init_params(jax.random.PRNGKey(0), cfg),
        "state": lambda: lfm2.make_slot_state(cfg, 64),
        "pool": lambda: lfm2.make_kv_cache(cfg, 12288, 16),
    }[what]()))


@pytest.mark.parametrize("which", ["small", "configuration"])
def test_param_count_is_the_number_of_elements_the_program_makes(which):
    """At the cell's shape too (shapes only, nothing is made): 5,267,090,176,
    ISSUE 43's count by hand (8 conv and 2 attention mixers, 2 dense and 8
    expert feed-forwards, the norms, the tied embedding), and
    ``memory_account_bytes`` is the module's counts: the weights in bf16, the
    pool and the 64 slots' tails as the program allocates them."""
    shape = SMALL if which == "small" else the_configuration()
    assert baf.param_count(shape) == sum(int(a.size) for a in made(shape, "params"))
    if which == "configuration":
        account = shape["memory_account_bytes"]
        assert baf.param_count(shape) == 5_267_090_176
        assert (baf.conv_mixer_params(shape), baf.attention_mixer_params(shape)) == (16_783_360, 10_485_888)
        assert baf.dense_ffn_params(shape) == 72_351_744
        assert 64 * baf.expert_params(shape) + baf.router_params(shape) == 604_110_912
        assert account["weights"] == baf.weight_bytes(shape) == 10_534_180_352
        assert account["kv_bytes_per_token"] == baf.kv_bytes_per_token(shape) == 8192
        assert account["kv_block"] == 16 * 8192
        assert account["slot_state"] == 64 * baf.slot_state_bytes(shape) == 8_388_608
        assert account["slot_state"] == sum(a.size * a.dtype.itemsize for a in made(shape, "state"))
        assert account["kv_pool"] == sum(a.size * a.dtype.itemsize for a in made(shape, "pool"))
        assert account["dense_history_buffer"] == 64 * 2048 * 8192
        # two KV heads of 64 a page row: nothing of the pool is padding on 128 lanes
        assert all(a.shape[-1] == 128 for a in made(shape, "pool"))


def test_a_decode_step_streams_the_experts_it_hits_and_the_tails_there_and_back():
    """Every weight outside the experts once (0.87 GB), ``experts_hit_share``
    of the 9.66 GB of experts (the configuration's smallest reading on the
    chip, at its own 64 lanes), every lane's tails read and written, and 8 KB a
    token of live K and V. Never the experts HELD: the roofline share must err
    low. Without a reading (another lane count, a shape no file holds) even
    routing stands in."""
    shape = the_configuration()
    read = shape["experts_hit_share"]
    assert baf.lanes_of(shape) == 64 and 0.5 < read["smallest"] <= 1.0 and read["runs"] >= 6
    assert read["smallest"] == min(read["readings"])
    experts = 8 * 64 * baf.expert_params(shape) * 2
    outside = baf.weight_bytes(shape) - experts
    assert (experts, outside) == (9_663_676_416, 870_503_936)
    at_rest = baf.decode_step_stream_bytes(shape, 0.0)
    assert at_rest == outside + read["smallest"] * experts + 2 * 64 * baf.slot_state_bytes(shape)
    assert at_rest < baf.weight_bytes(shape)
    assert baf.decode_step_stream_bytes(shape, 64 * 400.0) - at_rest == 64 * 400 * 8192
    # another lane count has no reading: 1 - (1 - 4/64)^lanes of the experts
    one = baf.decode_step_stream_bytes(shape, 0.0, lanes=1)
    assert one == pytest.approx(outside + experts * 4 / 64 + 2 * baf.slot_state_bytes(shape))
    assert baf.experts_hit_share(SMALL, 64) == pytest.approx(1 - (1 - 4 / 16) ** 64)
    # an untied table is read by row: the lookup's, not the head's
    untied = dict(shape, tie_word_embeddings=False)
    assert baf.param_count(untied) - baf.param_count(shape) == 65536 * 2048
    assert baf.decode_step_stream_bytes(untied, 0.0) == at_rest
    # 8 rows of 128 positions: 4 experts a token, not 64: 0.60 G weights a position goes through
    flops = baf.prefill_chunk_flops(shape, 1024, 0.0)
    assert 0.59e9 < flops / (1024 * 2) < 0.61e9
    assert baf.prefill_chunk_flops(shape, 1024, 256.0) - flops == 1024 * 2 * 2 * 2 * 32 * 64 * 256


def test_the_cells_file_and_its_entry_agree():
    """The traffic ISSUE 43 names: closed, 64 clients = slots, pre-roll 6 s,
    the chat lengths, no sharing; one chip; the depth alone reduced, every
    width, all 64 experts and the whole vocabulary as published."""
    bench, cell, cfg = load("BENCHMARK.json"), load("benchmark", "workloads", CELL + ".json"), the_configuration()
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (cell["config"], cell["traffic"], 1)
    assert (cell["config"], cell["traffic"]) == (CONFIG, "batch")
    assert cell["arrivals"] == {"gen": "closed", "clients": 64} and cell["preroll_s"] == 6
    assert cell["prompt_tokens"] == {"gen": "lognormal_clipped", "median": 256, "sigma": 0.7, "lo": 32, "hi": 1024}
    assert cell["output_tokens"] == {"gen": "lognormal_clipped", "median": 128, "sigma": 0.5, "lo": 16, "hi": 384}
    assert cell["sharing"].startswith("none")
    for other in ("batch.jamba2-3b", "batch.kimi-linear-48b-a3b"):  # the lengths to the digit
        theirs = load("benchmark", "workloads", other + ".json")
        assert all(cell[k] == theirs[k] for k in ("arrivals", "preroll_s", "prompt_tokens", "output_tokens", "sharing"))
    conf = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == ["num_hidden_layers"] and cfg["reduced"] == ["num_hidden_layers: 40 -> 10"]
    assert conf["source"] == cfg["source"] and cfg["num_hidden_layers_published"] == 40
    assert conf["file"] == f"benchmark/configs/{CONFIG}.json" and cfg["serving"]["chips"] == 1
    assert "one layer" in cfg["deployment"] and "pipeline stages" in cfg["deployment"]
    flags = cfg["serving"]["server_flags"]
    assert flags[flags.index("--max-batch-size") + 1] == "64" and cfg["serving"]["engine_args"]["decode_steps"] == 4
    assert (cfg["reference"], cfg["bytes_and_flops"]) == ("reference_lfm2", "bytes_and_flops_lfm2")
    # every number of the catalog row's config, under the same key; the depth cut
    published = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 11776,
                 "max_position_embeddings": 128000, "moe_intermediate_size": 1536, "norm_eps": 1e-05,
                 "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
                 "num_experts_per_tok": 4, "num_key_value_heads": 8, "routed_scaling_factor": 1,
                 "use_expert_bias": True, "vocab_size": 65536, "model_type": "lfm2_moe",
                 "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}}
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_theta"] == cfg["rope_parameters"]["rope_theta"]  # flat beside the group run.py drops
    assert cfg["num_hidden_layers"] == 10 and cfg["layer_types"] == (
        ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 2)
    assumed = " ".join(cfg["assumed"])
    for said in ("B, C, x", "1e-6", "before the rotation", "tied", "selection bias"):
        assert said in assumed, said


@pytest.mark.timeout(300)
def test_the_int8_control_in_the_programs_place_comes_out_as_not_correct():
    """The program's own pass (bf16 weights, float32 activations in
    ``lfm2.PARTS`` bfloat16 parts, chunks of 32 through the tails and the K/V pages)
    agrees with the float32 reference under the configuration's limit;
    ``reference_control_lfm2`` (every product against a weight in int8) does
    not, 3 x and more apart."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_child, reference_control_lfm2, reference_lfm2
    from dynamo_tpu.engine_jax.weights import lfm2_config
    from dynamo_tpu.models import lfm2

    cfg = lfm2_config(SMALL, jnp.bfloat16)
    params = lfm2.init_params(jax.random.PRNGKey(3), cfg)
    n, answered, chunk = 96, 24, 32
    tokens = jax.random.randint(jax.random.PRNGKey(0), (n,), 0, SMALL["vocab_size"])
    at = jnp.arange(n - answered, n)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference_lfm2.logits(params, SMALL, tokens, at))
        control = np.asarray(reference_control_lfm2.logits(params, SMALL, tokens, at))
    cache, state = lfm2.make_kv_cache(cfg, 16, 16), lfm2.make_slot_state(cfg, 2)
    tables, out = jnp.arange(1, 9, dtype=jnp.int32)[None], []
    for lo in range(0, n, chunk):
        h, cache, state, _ = lfm2.forward_chunk(
            params, cfg, tokens[None, lo:lo + chunk], jnp.arange(lo, lo + chunk)[None], cache, tables,
            state, jnp.asarray([0]))
        out.append(lfm2.lm_head(params, cfg, h[0]))
    program = np.asarray(jnp.concatenate(out), np.float32)[n - answered:]
    limit = the_configuration()["correct_limits"]["logprob_rms"]
    sound = reference_child.held_against(want, *reference_child.answer_of(program, 20), limit)
    lower = reference_child.held_against(want, *reference_child.answer_of(control, 20), limit)
    assert sound["agrees"] and sound["logprob_pairs"] == 20 * answered, sound
    assert not lower["agrees"] and lower["logprob_rms"] > 3 * sound["logprob_rms"], (sound, lower)


@pytest.mark.timeout(400)
def test_the_two_expert_readers_read_a_rehearsal_of_this_cell(monkeypatch):
    """``run.py``'s own launch of this cell's server on the CPU (the
    configuration's flags: 64 slots, block 16, 2,048 positions, 4 decode steps;
    ``in=http out=jax`` over a card ``run.py`` wrote, with the flat
    ``rope_theta`` that is left of the ``rope_parameters`` group it drops from
    the configuration's file) at a tiny ``lfm2_moe`` shape
    in ``rehearse.json``'s place, two greedy answers between two snapshots of
    ``/debug/engine``: the two ``moe_*`` readers, as they are, return numbers
    from it. A prompt of 40 tokens is one chunk group (one call a layer) and
    every decode step one more; 2 pairs a token over 8 experts."""
    from benchmark import client, traffic

    # as run.py hands the server the configuration's keys: no dict-valued one, so no rope_parameters
    shape = {**SMALL, "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
             "num_experts": 8, "num_experts_per_tok": 2, "num_attention_heads": 4,
             "num_key_value_heads": 2, "vocab_size": 2048}
    real = bench_run.load_json
    monkeypatch.setattr(bench_run, "load_json", lambda *parts: (
        {"shape": shape} if parts[-1] == "rehearse.json" else real(*parts)))
    go = bench_run.Launch(CELL, 2147483790, False, True)
    try:
        dev = go.wait_ready()
        assert dev["platform"] == "cpu"
        with open(os.path.join(go.model_dir, "config.json")) as f:
            served = json.load(f)
        assert served["model_type"] == "lfm2_moe" and "rope_parameters" not in served and served["rope_theta"] == 1000000
        before = bench_run.engine_state(go.port)
        for k in range(2):
            prompt = traffic.prompt_text(go.plain, 40, random.Random(k))
            probe = asyncio.run(client.probe(go.port, go.model, prompt, 8))
            assert probe["ok"], probe
        after = bench_run.engine_state(go.port)
    finally:
        go.child.stop()
    readers = bench_run.load_readers("layer_metrics")
    ctx = {"engine_samples": [], "engine_before": before, "engine_after": after, "shape": go.shape}
    rows, share = readers["moe_rows_per_held_expert"].read(ctx), readers["moe_experts_hit_share"].read(ctx)
    rise = {k: after[k] - before[k] for k in ("moe_layer_calls", "moe_held_rows", "moe_experts_hit",
                                              "conv_layer_calls", "slot_state_resets")}
    assert rise["slot_state_resets"] == 2 and rise["moe_layer_calls"] >= 4 * 2 * (1 + 7)
    assert rise["conv_layer_calls"] * 4 == rise["moe_layer_calls"] * 5  # 5 conv layers to 4 expert layers
    assert rows == rise["moe_held_rows"] / (rise["moe_layer_calls"] * 8) and 0 < rows
    assert share == 100.0 * rise["moe_experts_hit"] / (rise["moe_layer_calls"] * 8) and 0 < share <= 100
    # the pairs of the two prompts and of every decoded token, each to an expert held here
    assert rise["moe_held_rows"] >= 2 * 4 * 2 * (40 + 7)
