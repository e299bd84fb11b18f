"""What ``batch.qwen3-next-80b-a3b`` brings to the benchmark beside its data:
the module that counts the configuration's bytes and operations against the
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

from benchmark import bytes_and_flops_qwen3_next as baf  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

CELL, CONFIG = "batch.qwen3-next-80b-a3b", "qwen3-next-80b-a3b"

# one period of the published pattern (DeltaNet, DeltaNet, DeltaNet, attention,
# each with its expert layer) at a width a test can hold: 4 held of 16 experts,
# 4 a token; two KV heads of 64 rotated in their first 16 channels
SMALL = {
    "model_type": "qwen3_next", "hidden_size": 256, "num_hidden_layers": 4, "full_attention_interval": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64, "partial_rotary_factor": 0.25,
    "rope_theta": 10000000, "rope_scaling": None, "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 32, "linear_value_head_dim": 32, "linear_conv_kernel_dim": 4,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "moe_intermediate_size": 128,
    "shared_expert_intermediate_size": 128, "num_experts": 4, "num_experts_published": 16,
    "num_experts_per_tok": 4, "norm_topk_prob": True, "rms_norm_eps": 1e-6, "vocab_size": 4096,
    "tie_word_embeddings": False,
}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def the_configuration():
    return load("benchmark", "configs", CONFIG + ".json")


def made(shape, what):
    """The shapes ``models/qwen3_next.py`` makes for ``shape`` (nothing is made)."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine_jax.weights import qwen3_next_config
    from dynamo_tpu.models import qwen3_next

    cfg = qwen3_next_config(shape, jnp.bfloat16)
    return jax.tree.leaves(jax.eval_shape(lambda: {
        "params": lambda: qwen3_next.init_params(jax.random.PRNGKey(0), cfg),
        "state": lambda: qwen3_next.make_slot_state(cfg, 64),
        "pool": lambda: qwen3_next.make_kv_cache(cfg, 12288, 16),
    }[what]()))


@pytest.mark.parametrize("which", ["small", "configuration", "published", "one_period"])
def test_param_count_is_the_number_of_elements_the_program_makes(which):
    """At the cell's shape too (shapes only, nothing is made): 3,667,251,328,
    ISSUE 48's count by hand (6 DeltaNet and 2 attention mixers, 8 expert
    layers of 128 held experts with their router, shared expert and its gate,
    the norms, embedding and head of 37,984 rows), and ``memory_account_bytes``
    is the module's counts: the weights in bf16, the pool and the 64 slots'
    state as the program allocates them. The uncut model counts to ISSUE 48's
    79,674,391,296 and one period to the fallback's 1,911,417,920."""
    shape = SMALL if which == "small" else the_configuration()
    if which == "published":
        shape = dict(shape, num_hidden_layers=48, num_experts=512, vocab_size=151936)
        assert baf.param_count(shape) == 79_674_391_296
        return
    if which == "one_period":
        assert baf.param_count(dict(shape, num_hidden_layers=4)) == 1_911_417_920
        return
    assert baf.param_count(shape) == sum(int(a.size) for a in made(shape, "params"))
    if which == "configuration":
        account = shape["memory_account_bytes"]
        assert baf.param_count(shape) == 3_667_251_328
        assert (baf.gdn_mixer_params(shape), baf.attention_mixer_params(shape)) == (33_718_464, 27_263_488)
        assert (baf.ffn_outside_experts_params(shape), baf.expert_params(shape)) == (4_196_352, 3_145_728)
        assert account["weights"] == baf.weight_bytes(shape) == 7_334_502_656
        assert account["kv_bytes_per_token"] == baf.kv_bytes_per_token(shape) == 8192
        assert account["kv_block"] == 16 * 8192
        assert account["slot_state"] == 64 * baf.slot_state_bytes(shape) == 843_055_104
        assert account["slot_state"] == sum(a.size * a.dtype.itemsize for a in made(shape, "state"))
        assert account["kv_pool"] == sum(a.size * a.dtype.itemsize for a in made(shape, "pool"))
        assert account["dense_history_buffer"] == 64 * 2048 * 8192
        # a head of 256: two registers' lanes, nothing of the pool is padding
        assert all(a.shape == (2, 12288, 16, 2, 256) for a in made(shape, "pool"))


def test_a_decode_step_streams_the_experts_it_hits_and_the_state_there_and_back():
    """Every weight outside the routed experts once (0.74 GB: the mixers, the
    routers, the shared experts, the head's rows held), ``experts_hit_share`` of
    the 6.44 GB of routed experts held (the configuration's smallest reading on
    the chip, at its own 64 lanes), every lane's DeltaNet state and tails read
    and written (1.69 GB), and 8 KB a token of live K and V. Never the experts
    HELD: the roofline share must err low. Without a reading (another lane
    count, a shape no file holds) even routing over all 512 stands in."""
    shape = the_configuration()
    read = shape["experts_hit_share"]
    assert baf.lanes_of(shape) == 64 and 0.4 < read["smallest"] <= 0.717 and read["runs"] >= 6
    assert read["smallest"] == min(read["readings"]) <= min(read["of_all_calls"])
    experts = 8 * 128 * baf.expert_params(shape) * 2
    outside = baf.weight_bytes(shape) - experts - 37984 * 2048 * 2  # the embedding is read by row
    assert (experts, outside) == (6_442_450_944, 736_469_248)
    state = 2 * 64 * baf.slot_state_bytes(shape)
    assert state == 1_686_110_208
    at_rest = baf.decode_step_stream_bytes(shape, 0.0)
    assert at_rest == outside + read["smallest"] * experts + state
    assert at_rest < baf.weight_bytes(shape)
    assert baf.decode_step_stream_bytes(shape, 64 * 400.0) - at_rest == 64 * 400 * 8192
    # another lane count has no reading: 1 - (1 - 10/512)^lanes of the experts held
    one = baf.decode_step_stream_bytes(shape, 0.0, lanes=1)
    assert one == pytest.approx(outside + experts * 10 / 512 + 2 * baf.slot_state_bytes(shape))
    assert baf.experts_hit_share(dict(shape, hidden_size=1), 64) == pytest.approx(1 - (1 - 10 / 512) ** 64)
    assert baf.experts_hit_share(SMALL, 64) == pytest.approx(1 - (1 - 4 / 16) ** 64)
    # a chunk: 2.5 of a token's 10 experts are held; the recurrence is 7 operations an element of
    # a [32, 128, 128] state a DeltaNet layer; attention reads 16 heads of 256
    flops = baf.prefill_chunk_flops(shape, 1024, 0.0)
    per_token = (6 * (2048 * 12288 + 2048 * 64 + 4096 * 2048) + 2 * (2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048)
                 + 8 * (2.5 * 3 * 2048 * 512 + 2048 * 512 + 3 * 2048 * 512 + 2048))
    assert flops == pytest.approx(1024 * (2 * per_token + 6 * 2 * 4 * 8192 + 6 * 7 * 32 * 128 * 128))
    assert baf.prefill_chunk_flops(shape, 1024, 256.0) - flops == 1024 * 2 * 2 * 2 * 16 * 256 * 256


def test_the_cells_file_and_its_entry_agree():
    """The traffic ISSUE 48 names: closed, 64 clients = slots, pre-roll 6 s,
    the chat lengths, no sharing; one chip; the depth, the experts held and the
    vocabulary reduced, every width, the router's 512 outputs, 10 a token and
    every head as published."""
    bench, cell, cfg = load("BENCHMARK.json"), load("benchmark", "workloads", CELL + ".json"), the_configuration()
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (cell["config"], cell["traffic"], 1)
    assert (cell["config"], cell["traffic"]) == (CONFIG, "batch")
    assert cell["arrivals"] == {"gen": "closed", "clients": 64} and cell["preroll_s"] == 6
    assert cell["prompt_tokens"] == {"gen": "lognormal_clipped", "median": 256, "sigma": 0.7, "lo": 32, "hi": 1024}
    assert cell["output_tokens"] == {"gen": "lognormal_clipped", "median": 128, "sigma": 0.5, "lo": 16, "hi": 384}
    assert cell["sharing"].startswith("none")
    for other in ("batch.jamba2-3b", "batch.kimi-linear-48b-a3b", "batch.lfm2-24b-a2b"):  # the lengths to the digit
        theirs = load("benchmark", "workloads", other + ".json")
        assert all(cell[k] == theirs[k] for k in ("arrivals", "preroll_s", "prompt_tokens", "output_tokens", "sharing"))
    conf = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert cfg["reduced"] == ["num_hidden_layers: 48 -> 8", "num_experts: 512 -> 128", "vocab_size: 151936 -> 37984"]
    assert conf["source"] == cfg["source"] and conf["file"] == f"benchmark/configs/{CONFIG}.json"
    assert (cfg["num_hidden_layers_published"], cfg["num_experts_published"], cfg["vocab_size_published"]) == (
        48, 512, 151936)
    assert cfg["serving"]["chips"] == 1 and not [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    for said in ("4 chips of one TPU v5e host", "pipeline stages", "a quarter of the deployment's 256-lane batch"):
        assert said in cfg["deployment"], said
    flags = cfg["serving"]["server_flags"]
    assert flags[flags.index("--max-batch-size") + 1] == "64" and cfg["serving"]["engine_args"] == {
        "decode_steps": 4, "seed": 0}
    assert (cfg["reference"], cfg["bytes_and_flops"]) == ("reference_qwen3_next", "bytes_and_flops_qwen3_next")
    # every number of the catalog row's config, under the same key; depth, experts and vocabulary cut
    published = {"decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu",
                 "hidden_size": 2048, "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
                 "linear_key_head_dim": 128, "linear_num_key_heads": 16, "linear_num_value_heads": 32,
                 "linear_value_head_dim": 128, "max_position_embeddings": 262144, "mlp_only_layers": [],
                 "model_type": "qwen3_next", "moe_intermediate_size": 512, "norm_topk_prob": True,
                 "num_attention_heads": 16, "num_experts_per_tok": 10, "num_key_value_heads": 2,
                 "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None,
                 "rope_theta": 10000000, "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
                 "use_sliding_window": False}
    assert {k: cfg[k] for k in published} == published
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (8, 128, 37984)
    assumed = " ".join(cfg["assumed"])
    for said in ("ZERO-centred", "PLAIN weight", "q, k and v together", "1e-6", "128^-0.5", "log(U(0, 16))",
                 "dt_bias = 1", "[q | gate]", "BEFORE the rotation", "FIRST 64", "its own gate", "BEFORE the choice",
                 "column order", "multi-token-prediction", "By part"):
        assert said in assumed, said
    limit = cfg["correct_limits"]["logprob_rms"]
    assert 0.0139 <= limit <= 0.0434  # what tests/benchmark/test_benchmark.py allows a configuration


@pytest.mark.timeout(300)
def test_the_int8_control_in_the_programs_place_comes_out_as_not_correct():
    """The program's own pass (bf16 weights, float32 activations in
    ``qwen3_next.PARTS`` bfloat16 parts, chunks of 32 through the state, the
    tails and the K/V pages) agrees with the float32 reference under the
    configuration's limit; ``reference_control_qwen3_next`` (every product
    against a weight in int8) does not, 3 x and more apart."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_child, reference_control_qwen3_next, reference_qwen3_next
    from dynamo_tpu.engine_jax.weights import qwen3_next_config
    from dynamo_tpu.models import qwen3_next

    cfg = qwen3_next_config(SMALL, jnp.bfloat16)
    params = qwen3_next.init_params(jax.random.PRNGKey(3), cfg)
    n, answered, chunk = 96, 24, 32
    tokens = jax.random.randint(jax.random.PRNGKey(0), (n,), 0, SMALL["vocab_size"])
    at = jnp.arange(n - answered, n)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference_qwen3_next.logits(params, SMALL, tokens, at))
        control = np.asarray(reference_control_qwen3_next.logits(params, SMALL, tokens, at))
    cache, state = qwen3_next.make_kv_cache(cfg, 16, 16), qwen3_next.make_slot_state(cfg, 2)
    tables, out = jnp.arange(1, 9, dtype=jnp.int32)[None], []
    for lo in range(0, n, chunk):
        h, cache, state, _ = qwen3_next.forward_chunk(
            params, cfg, tokens[None, lo:lo + chunk], jnp.arange(lo, lo + chunk)[None], cache, tables,
            state, jnp.asarray([0]))
        out.append(qwen3_next.lm_head(params, cfg, h[0]))
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
    ``in=http out=jax`` over a card ``run.py`` wrote) at a tiny ``qwen3_next``
    shape in ``rehearse.json``'s place, two greedy answers between two
    snapshots of ``/debug/engine``: the two ``moe_*`` readers, as they are,
    return numbers from it, per expert HELD (``num_experts``: 4 of the 16 the
    router scores). A prompt of 40 tokens is one chunk group (one call a layer)
    and every decode step one more; 4 pairs a token, a quarter of them held."""
    from benchmark import client, traffic

    shape = {**SMALL, "hidden_size": 64, "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
             "head_dim": 32, "linear_key_head_dim": 16, "linear_value_head_dim": 16, "vocab_size": 2048}
    real = bench_run.load_json
    monkeypatch.setattr(bench_run, "load_json", lambda *parts: (
        {"shape": shape} if parts[-1] == "rehearse.json" else real(*parts)))
    go = bench_run.Launch(CELL, 2147483790, False, True)
    try:
        dev = go.wait_ready()
        assert dev["platform"] == "cpu"
        with open(os.path.join(go.model_dir, "config.json")) as f:
            served = json.load(f)
        assert served["model_type"] == "qwen3_next" and served["rope_scaling"] is None
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
    names = ("moe_layer_calls", "moe_held_rows", "moe_experts_hit", "moe_routed_pairs", "moe_rows_computed",
             "moe_expert_reads", "gdn_chunk_tokens", "gdn_state_passes", "slot_state_resets")
    assert all(name in after for name in names)  # the nine counters of /debug/engine
    rise = {k: after[k] - before[k] for k in names}
    assert rise["slot_state_resets"] == 2 and rise["moe_layer_calls"] >= 4 * 2 * (1 + 7)
    assert rise["gdn_chunk_tokens"] == 3 * 2 * 40 and rise["gdn_state_passes"] == 3 * 2
    assert rows == rise["moe_held_rows"] / (rise["moe_layer_calls"] * 4) and 0 < rows
    assert share == 100.0 * rise["moe_experts_hit"] / (rise["moe_layer_calls"] * 4) and 0 < share <= 100
    # the pairs of the two prompts and of every decoded token; some of them to an expert held here
    assert rise["moe_routed_pairs"] >= 2 * 4 * 4 * (40 + 7) and 0 < rise["moe_held_rows"] < rise["moe_routed_pairs"]
