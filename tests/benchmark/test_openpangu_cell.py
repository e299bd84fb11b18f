"""What ``batch.openpangu-ultra-moe-718b`` brings to the benchmark beside its
data: the module that counts the configuration's bytes and operations against
the program's own parameters and pool; the cell's file against its entry and
the catalog row; the control of ``correct`` at a width a test can hold; and
three readers (``mla_history_read_share``, new; ``moe_rows_per_held_expert``
and ``moe_experts_hit_share`` as they are; all unregistered, PERF.md 7) on what
a rehearsal of this cell's server counted.
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

from benchmark import bytes_and_flops_openpangu as baf  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

CELL, CONFIG = "batch.openpangu-ultra-moe-718b", "openpangu-ultra-moe-718b"

# the cut's structure at a width a test can hold: one dense layer, two expert
# layers of 4 held of 16 experts, 4 a token, the prediction module; 4 heads of 32 + 16
SMALL = {
    "model_type": "pangu_ultra_moe", "hidden_size": 256, "intermediate_size": 512, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 96, "kv_lora_rank": 64,
    "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32, "rope_theta": 25600000,
    "first_k_dense_replace": 1, "moe_intermediate_size": 128, "n_routed_experts": 4, "num_experts": 4,
    "n_routed_experts_published": 16, "n_shared_experts": 1, "num_experts_per_tok": 4, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "sandwich_norm": True, "num_nextn_predict_layers": 1, "hidden_act": "silu",
    "rms_norm_eps": 1e-5, "attention_bias": False, "tie_word_embeddings": False, "vocab_size": 4096,
}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def the_configuration():
    return load("benchmark", "configs", CONFIG + ".json")


def made(shape, what):
    """The shapes ``models/openpangu.py`` makes for ``shape`` (nothing is made)."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine_jax.weights import openpangu_config
    from dynamo_tpu.models import openpangu

    cfg = openpangu_config(shape, jnp.bfloat16)
    return jax.tree.leaves(jax.eval_shape(lambda: {
        "params": lambda: openpangu.init_params(jax.random.PRNGKey(0), cfg),
        "pool": lambda: openpangu.make_kv_cache(cfg, 12288, 16),
        "drafting_pool": lambda: openpangu.make_kv_cache(cfg, 12288, 16, drafting=True),
    }[what]()))


@pytest.mark.parametrize("which", ["small", "configuration", "published"])
def test_param_count_is_the_number_of_elements_the_program_makes(which):
    """At the cell's shape too (shapes only, nothing is made): 4,445,337,600,
    ISSUE 52's count by hand, and ``memory_account_bytes`` is the module's
    counts: the weights in bf16 and the pool as the program allocates it. The
    uncut model counts to the name's 718 B (719,093,767,680 without the
    prediction module, 731,537,111,040 with it)."""
    shape = SMALL if which == "small" else the_configuration()
    if which == "published":
        shape = dict(shape, num_hidden_layers=61, first_k_dense_replace=3, n_routed_experts=256, vocab_size=153600)
        assert baf.param_count(shape) == 731_537_111_040
        assert baf.param_count(shape) - baf.mtp_params(shape) == 719_093_767_680
        assert baf.expert_layer_params(shape) == 12_325_355_520  # 24.65 GB: no chip holds one
        return
    assert baf.param_count(shape) == sum(int(a.size) for a in made(shape, "params"))
    if which == "configuration":
        account = shape["memory_account_bytes"]
        assert baf.param_count(shape) == 4_445_337_600
        assert (baf.mla_mixer_params(shape), baf.expert_params(shape)) == (196_577_280, 47_185_920)
        assert (baf.dense_layer_params(shape), baf.expert_layer_params(shape)) == (621_281_280, 623_247_360)
        assert baf.mtp_params(shape) == 741_235_200
        assert account["weights"] == baf.weight_bytes(shape) == 8_890_675_200
        assert baf.kv_bytes_per_token(shape) == 5 * 576 * 4 == 11_520
        assert account["kv_bytes_per_token"] == baf.kv_pool_bytes_per_token(shape) == 5 * 640 * 4
        assert account["kv_block"] == 16 * account["kv_bytes_per_token"]
        assert account["kv_pool"] == sum(a.size * a.dtype.itemsize for a in made(shape, "pool")) == 2_516_582_400
        assert account["dense_history_buffer"] == 5 * 64 * 2048 * 640 * 4
        # ONE member, a row of five whole registers: nothing for the chip's compiler to pad
        assert [a.shape for a in made(shape, "pool")] == [(5, 12288, 16, 640)]
        assert [a.shape for a in made(shape, "drafting_pool")] == [(6, 12288, 16, 640)]
        assert account["weights"] > 0.25 * account["hbm"]  # 55 % of the chip in weights alone


def test_a_decode_step_streams_the_experts_it_hits_and_not_the_prediction_module():
    """Every weight outside the routed experts, the embedding and the
    prediction module once, the experts the lanes hit (the configuration's
    smallest reading where it has one, else even routing: 1 - (31/32)^64 of the
    8 held), and 11,520 B a token of live latent. Never the module's 1.48 GB:
    it is held and not run at ``spec_k`` 0."""
    shape = the_configuration()
    assert baf.lanes_of(shape) == 64
    even = 1 - (1 - 8 / 256) ** 64
    read = shape.get("experts_hit_share")
    share = baf.experts_hit_share(shape, 64)
    assert share == (read["smallest"] if read else pytest.approx(even)) and 0.5 < share <= even + 1e-9
    experts = 4 * 8 * baf.expert_params(shape) * 2
    outside = (baf.weight_bytes(shape) - experts - 2 * baf.mtp_params(shape)
               - 38400 * 7680 * 2)  # the embedding is read by row
    assert (experts, outside) == (3_019_898_880, 3_798_481_920)
    at_rest = baf.decode_step_stream_bytes(shape, 0.0)
    assert at_rest == pytest.approx(outside + share * experts)
    assert at_rest < baf.weight_bytes(shape) - 2 * baf.mtp_params(shape)
    assert baf.decode_step_stream_bytes(shape, 64 * 400.0) - at_rest == pytest.approx(64 * 400 * 11_520)
    one = baf.decode_step_stream_bytes(shape, 0.0, lanes=1)
    assert one == pytest.approx(outside + experts * 8 / 256)
    assert baf.experts_hit_share(SMALL, 64) == pytest.approx(1 - (1 - 4 / 16) ** 64)
    # a chunk: 0.25 of a token's 8 experts are held; the absorbed form reads 2 x 512 + 64 a head and key
    flops = baf.prefill_chunk_flops(shape, 1024, 0.0)
    mixer = 196_577_280 - 1536 - 512
    per_token = (5 * mixer + 3 * 7680 * 18432 + 4 * (7680 * 256 + 47_185_920 + 0.25 * 47_185_920))
    assert flops == pytest.approx(1024 * 2 * per_token)
    assert baf.prefill_chunk_flops(shape, 1024, 256.0) - flops == pytest.approx(
        1024 * 5 * 2 * 128 * (2 * 512 + 64) * 256)


def test_the_cells_file_and_its_entry_agree():
    """The traffic ISSUE 52 names: closed, 64 clients = slots, pre-roll 6 s,
    the chat lengths, no sharing; one chip; the depth, the leading dense
    layers, the experts held and the vocabulary reduced, every width, the
    router's 256 outputs, 8 a token and every head as published; and every
    number of the catalog row under its key."""
    bench, cell, cfg = load("BENCHMARK.json"), load("benchmark", "workloads", CELL + ".json"), the_configuration()
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (cell["config"], cell["traffic"], 1)
    assert (cell["config"], cell["traffic"]) == (CONFIG, "batch")
    assert cell["arrivals"] == {"gen": "closed", "clients": 64} and cell["preroll_s"] == 6
    assert cell["prompt_tokens"] == {"gen": "lognormal_clipped", "median": 256, "sigma": 0.7, "lo": 32, "hi": 1024}
    assert cell["output_tokens"] == {"gen": "lognormal_clipped", "median": 128, "sigma": 0.5, "lo": 16, "hi": 384}
    assert cell["sharing"].startswith("none")
    for other in ("batch.jamba2-3b", "batch.kimi-linear-48b-a3b", "batch.qwen3-next-80b-a3b"):  # to the digit
        theirs = load("benchmark", "workloads", other + ".json")
        assert all(cell[k] == theirs[k] for k in ("arrivals", "preroll_s", "prompt_tokens", "output_tokens", "sharing"))
    for said in ("long context", "prefix reuse", "speculation", "exchange", "64 rows an expert", "2 rows an expert"):
        assert said in cell["why"], said
    conf = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert bench["configs"][-1] is conf and bench["workloads"][-1] is entry  # appended, nothing moved
    assert conf["reduced"] == ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size"]
    assert cfg["reduced"] == ["num_hidden_layers: 61 -> 5", "first_k_dense_replace: 3 -> 1",
                              "n_routed_experts: 256 -> 8", "vocab_size: 153600 -> 38400"]
    assert conf["source"] == cfg["source"] and conf["file"] == f"benchmark/configs/{CONFIG}.json"
    assert (cfg["num_hidden_layers_published"], cfg["n_routed_experts_published"], cfg["vocab_size_published"],
            cfg["first_k_dense_replace_published"]) == (61, 256, 153600, 3)
    assert cfg["serving"]["chips"] == 1 and not [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    assert "mla_history_read_share" not in [m["name"] for m in bench["per_layer"]]  # PERF.md 7: B6 (ii)
    for said in ("32 chips", "8 of the 256 routed experts", "pipeline stages", "data-parallel attention",
                 "quartered", "a thirty-second"):
        assert said in cfg["deployment"], said
    flags = cfg["serving"]["server_flags"]
    assert flags[flags.index("--max-batch-size") + 1] == "64" and cfg["serving"]["engine_args"] == {
        "decode_steps": 4, "seed": 0}  # nothing about speculation: the served default
    assert (cfg["reference"], cfg["bytes_and_flops"]) == ("reference_openpangu", "bytes_and_flops_openpangu")
    # every number of the catalog row's config, under the same key; the four cuts apart
    published = {"attention_bias": False, "hidden_act": "silu", "hidden_size": 7680, "intermediate_size": 18432,
                 "kv_lora_rank": 512, "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
                 "moe_intermediate_size": 2048, "n_shared_experts": 1, "norm_topk_prob": True,
                 "num_attention_heads": 128, "num_experts_per_tok": 8, "num_key_value_heads": 128,
                 "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_nope_head_dim": 128,
                 "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_theta": 25600000,
                 "routed_scaling_factor": 2.5, "sandwich_norm": True, "tie_word_embeddings": False,
                 "v_head_dim": 128}
    assert {k: cfg[k] for k in published} == published
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"], cfg["n_routed_experts"], cfg["vocab_size"]) == (
        5, 1, 8, 38400)
    assert cfg["num_experts"] == cfg["n_routed_experts"]  # the name the two moe_* readers take the held count under
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(line) for line in f if '"openPangu-Ultra-MoE-718B"' in line)
        assert row["source_url"] == cfg["source"]
        differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differs == set(conf["reduced"]), differs
    assumed = " ".join(cfg["assumed"])
    for said in ("NO selection bias", "NO expert groups", "sandwich", "half-split", "no softmax-scale correction",
                 "DeepSeek-V3 form", "[embedding ; hidden]", "BEFORE the final norm", "640 wide"):
        assert said in assumed, said
    limit = cfg["correct_limits"]["logprob_rms"]
    assert 0.0139 <= limit <= 0.0434  # what tests/benchmark/test_benchmark.py allows a configuration


@pytest.mark.timeout(300)
def test_the_int8_control_in_the_programs_place_comes_out_as_not_correct():
    """The program's own pass (bf16 weights, float32 activations in three
    bfloat16 parts, chunks of 32 through the latent pages, absorbed) agrees
    with the float32 reference under the configuration's limit, the main
    logits and the prediction module's; ``reference_control_openpangu`` (every
    product against a weight in int8) does not, 3 x and more apart."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_child, reference_control_openpangu, reference_openpangu
    from dynamo_tpu.engine_jax.weights import openpangu_config
    from dynamo_tpu.models import openpangu

    cfg = openpangu_config(SMALL, jnp.bfloat16)
    params = openpangu.init_params(jax.random.PRNGKey(3), cfg)
    n, answered, chunk = 96, 24, 32
    tokens = jax.random.randint(jax.random.PRNGKey(0), (n + 1,), 0, SMALL["vocab_size"])
    at = jnp.arange(n - answered, n)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference_openpangu.logits(params, SMALL, tokens[:n], at))
        want_draft = np.asarray(reference_openpangu.draft_logits(params, SMALL, tokens, at))
        control = np.asarray(reference_control_openpangu.logits(params, SMALL, tokens[:n], at))
    cache = openpangu.make_kv_cache(cfg, 16, 16, drafting=True)
    tables, out, drafts = jnp.arange(1, 9, dtype=jnp.int32)[None], [], []
    for lo in range(0, n, chunk):
        pos = jnp.arange(lo, lo + chunk)[None]
        x, cache, _, _ = openpangu.forward_chunk(
            params, cfg, tokens[None, lo:lo + chunk], pos, cache, tables, None, jnp.asarray([0]), raw=True)
        out.append(openpangu.lm_head(params, cfg, openpangu.final_norm(params, cfg, x)[0]))
        hd, cache, _ = openpangu.draft_chunk(params, cfg, x, tokens[None, lo + 1:lo + chunk + 1], pos, cache, tables)
        drafts.append(openpangu.lm_head(params, cfg, hd[0]))
    program = np.asarray(jnp.concatenate(out), np.float32)[n - answered:]
    module = np.asarray(jnp.concatenate(drafts), np.float32)[n - answered:]
    limit = the_configuration()["correct_limits"]["logprob_rms"]
    sound = reference_child.held_against(want, *reference_child.answer_of(program, 20), limit)
    drafted = reference_child.held_against(want_draft, *reference_child.answer_of(module, 20), limit)
    lower = reference_child.held_against(want, *reference_child.answer_of(control, 20), limit)
    assert sound["agrees"] and sound["logprob_pairs"] == 20 * answered, sound
    assert drafted["agrees"], drafted
    assert not lower["agrees"] and lower["logprob_rms"] > 3 * sound["logprob_rms"], (sound, lower)


@pytest.mark.timeout(400)
def test_the_three_readers_read_a_rehearsal_of_this_cell(monkeypatch):
    """``run.py``'s own launch of this cell's server on the CPU (the
    configuration's flags: 64 slots, block 16, 2,048 positions, 4 decode steps;
    ``in=http out=jax`` over a card ``run.py`` wrote) at a tiny
    ``pangu_ultra_moe`` shape in ``rehearse.json``'s place, two greedy answers
    between two snapshots of ``/debug/engine``: ``mla_history_read_share`` and
    the two ``moe_*`` readers, as they are, return numbers from it. The same
    prompt asked again is served from its cached latent pages (the hit moves
    ``prefix_hit_tokens``: an own-programs module without state reuses)."""
    from benchmark import client, traffic

    shape = {**SMALL, "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
             "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
             "v_head_dim": 16, "vocab_size": 2048}
    real = bench_run.load_json
    monkeypatch.setattr(bench_run, "load_json", lambda *parts: (
        {"shape": shape} if parts[-1] == "rehearse.json" else real(*parts)))
    go = bench_run.Launch(CELL, 2147483790, False, True)
    try:
        dev = go.wait_ready()
        assert dev["platform"] == "cpu"
        with open(os.path.join(go.model_dir, "config.json")) as f:
            served = json.load(f)
        assert served["model_type"] == "pangu_ultra_moe" and served["n_routed_experts_published"] == 16
        before = bench_run.engine_state(go.port)
        for k in (0, 1, 1):  # the third asks the second's prompt again
            prompt = traffic.prompt_text(go.plain, 40, random.Random(k))
            probe = asyncio.run(client.probe(go.port, go.model, prompt, 8))
            assert probe["ok"], probe
        after = bench_run.engine_state(go.port)
    finally:
        go.child.stop()
    readers = bench_run.load_readers("layer_metrics")
    ctx = {"engine_samples": [], "engine_before": before, "engine_after": after, "shape": go.shape}
    live = readers["mla_history_read_share"].read(ctx)
    rows, share = readers["moe_rows_per_held_expert"].read(ctx), readers["moe_experts_hit_share"].read(ctx)
    names = ("moe_layer_calls", "moe_held_rows", "moe_experts_hit", "moe_routed_pairs", "moe_rows_computed",
             "moe_expert_reads", "mla_layer_calls", "mla_history_positions_read", "mla_history_positions_live",
             "mtp_layer_calls")
    assert all(name in after for name in names)  # the ten counters of /debug/engine
    rise = {k: after[k] - before[k] for k in names + ("prefix_hit_tokens", "spec_drafted_tokens")}
    assert rise["mtp_layer_calls"] == 0 and rise["spec_drafted_tokens"] == 0  # the served default
    assert rise["prefix_hit_tokens"] == 32  # two blocks of 16 of the 40 tokens asked again
    assert rise["mla_layer_calls"] >= 3 * 3 * (1 + 7) and rise["moe_layer_calls"] >= 2 * 3 * (1 + 7)
    assert live == 100.0 * rise["mla_history_positions_live"] / rise["mla_history_positions_read"]
    assert 0 < live < 5  # some 40-48 positions of a table of 2,048
    assert rows == rise["moe_held_rows"] / (rise["moe_layer_calls"] * 4) and 0 < rows
    assert share == 100.0 * rise["moe_experts_hit"] / (rise["moe_layer_calls"] * 4) and 0 < share <= 100
    assert 0 < rise["moe_held_rows"] < rise["moe_routed_pairs"]
    # a parent without the module has no such counter: the reader returns nothing and does not raise
    assert readers["mla_history_read_share"].read({"engine_before": {"x": 1}, "engine_after": {"x": 2}}) is None
    assert readers["mla_history_read_share"].read({}) is None
