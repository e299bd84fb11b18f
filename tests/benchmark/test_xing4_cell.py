"""What ``batch.xing4.0-29b-a4b`` brings to the benchmark beside its data: the
module that counts the configuration's bytes and operations against the
program's own parameters and pool (at the cell's shape and at the published
one); the memory account's arithmetic; the cell's file against its entry, the
parameters ISSUE 61 names and the catalog row; the control of ``correct`` at a
width a test can hold; and ``mhc_rows_per_mix`` (new) beside the expert and
latent readers as they are, on what a rehearsal of this cell's server counted.
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

from benchmark import bytes_and_flops_xing4 as baf  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

CELL, CONFIG = "batch.xing4.0-29b-a4b", "xing4.0-29b-a4b"

# the cut's structure at a width a test can hold: four streams, one dense layer, two expert layers of 16
# experts all held, 4 a token, the prediction module; 4 heads of 32 + 16 under YaRN
SMALL = {
    "model_type": "xing4_0", "hidden_size": 256, "intermediate_size": 512, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 96, "kv_lora_rank": 64,
    "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32, "rope_theta": 10000,
    "rope_scaling_type": "yarn", "rope_scaling_factor": 64, "rope_scaling_beta_fast": 32, "rope_scaling_beta_slow": 1,
    "rope_scaling_mscale": 1, "rope_scaling_mscale_all_dim": 1, "rope_scaling_original_max_position_embeddings": 64,
    "first_k_dense_replace": 1, "moe_intermediate_size": 128, "n_routed_experts": 16, "num_experts": 16,
    "n_shared_experts": 1, "num_experts_per_tok": 4, "n_group": 1, "topk_group": 1, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "norm_topk_prob": True, "routed_scaling_factor": 2, "num_nextn_predict_layers": 1,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "hidden_act": "silu", "rms_norm_eps": 1e-6, "attention_bias": False, "tie_word_embeddings": False,
    "vocab_size": 4096,
}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def the_configuration():
    return load("benchmark", "configs", CONFIG + ".json")


def made(shape, what):
    """The shapes ``models/xing4.py`` makes for ``shape`` (nothing is made)."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine_jax.weights import xing4_config
    from dynamo_tpu.models import xing4

    cfg = xing4_config(shape, jnp.bfloat16)
    return jax.tree.leaves(jax.eval_shape(lambda: {
        "params": lambda: xing4.init_params(jax.random.PRNGKey(0), cfg),
        "pool": lambda: xing4.make_kv_cache(cfg, 12288, 16),
        "drafting_pool": lambda: xing4.make_kv_cache(cfg, 12288, 16, drafting=True),
    }[what]()))


@pytest.mark.parametrize("which", ["small", "configuration", "published"])
def test_param_count_is_the_number_of_elements_the_program_makes(which):
    """At the cell's shape (shapes only, nothing is made): 4,818,370,692,
    ISSUE 61's count by hand, and ``memory_account_bytes`` is the module's
    counts: the weights in bf16 and the pool as the program allocates it. At
    the published shape (40 layers, two leading dense ones) the program makes
    30,276,195,174 elements: 29,505,505,264 without the prediction module, the
    name's 29 B."""
    shape = SMALL if which == "small" else the_configuration()
    if which == "published":
        shape = dict(shape, num_hidden_layers=40, first_k_dense_replace=2)
    assert baf.param_count(shape) == sum(int(a.size) for a in made(shape, "params"))
    if which == "published":
        assert baf.param_count(shape) == 30_276_195_174
        assert baf.param_count(shape) - baf.mtp_params(shape) == 29_505_505_264
        assert 2 * baf.expert_layer_params(shape) == 1_489_978_092  # 1.49 GB: a chip holds a layer WHOLE
        active = baf.param_count(shape) - baf.mtp_params(shape) - 38 * (64 - 4) * baf.expert_params(shape)
        assert active == 4_402_595_824  # 4 of a layer's 64 experts: the name's A4B
    if which == "configuration":
        account = shape["memory_account_bytes"]
        assert baf.param_count(shape) == 4_818_370_692
        assert (baf.mla_mixer_params(shape), baf.expert_params(shape), baf.mhc_params(shape)) == (
            28_411_136, 11_010_048, 344_091)
        assert (baf.dense_layer_params(shape), baf.expert_layer_params(shape)) == (128_196_918, 744_989_046)
        assert baf.mtp_params(shape) == 770_689_910
        # the account's arithmetic: 1 dense + 4 expert layers + the module + embedding, head and the final norm
        assert 128_196_918 + 4 * 744_989_046 + 770_689_910 + 2 * 131072 * 3584 + 3584 == 4_818_370_692
        assert account["weights"] == baf.weight_bytes(shape) == 9_636_741_384
        assert baf.kv_bytes_per_token(shape) == 5 * 576 * 4 == 11_520
        assert account["kv_bytes_per_token"] == baf.kv_pool_bytes_per_token(shape) == 5 * 640 * 4
        assert account["kv_block"] == 16 * account["kv_bytes_per_token"]
        assert account["kv_pool"] == sum(a.size * a.dtype.itemsize for a in made(shape, "pool")) == 2_516_582_400
        assert account["dense_history_buffer"] == 5 * 64 * 2048 * 640 * 4
        assert [a.shape for a in made(shape, "pool")] == [(5, 12288, 16, 640)]
        assert [a.shape for a in made(shape, "drafting_pool")] == [(6, 12288, 16, 640)]
        assert 0.75 < (account["weights"] + account["kv_pool"]) / account["hbm"] < 0.77  # the account: 76 % of the chip
        assert account["weights"] > 0.25 * account["hbm"]  # 60 % in weights alone


def test_a_decode_step_streams_the_experts_it_hits_and_the_residual_path_moves_its_streams():
    """Every weight outside the routed experts, the embedding and the
    prediction module once, the experts the lanes hit (the configuration's
    smallest reading where it has one, else even routing: 1 - (15/16)^64 of the
    64), and 11,520 B a token of live latent; the residual path of one sublayer
    moves (3 x 4 + 2) x 3,584 float32 values a token and ``φ`` once a call."""
    shape = the_configuration()
    assert baf.lanes_of(shape) == 64
    even = 1 - (1 - 4 / 64) ** 64
    read = shape.get("experts_hit_share")
    share = baf.experts_hit_share(shape, 64)
    assert share == (read["smallest"] if read else pytest.approx(even)) and 0.8 < share <= even + 1e-9
    experts = 4 * 64 * baf.expert_params(shape) * 2
    outside = baf.weight_bytes(shape) - experts - 2 * baf.mtp_params(shape) - 131072 * 3584 * 2  # the embedding: by row
    assert (experts, outside) == (5_637_144_576, 1_518_692_892)
    at_rest = baf.decode_step_stream_bytes(shape, 0.0)
    assert at_rest == pytest.approx(outside + share * experts)
    assert baf.decode_step_stream_bytes(shape, 64 * 400.0) - at_rest == pytest.approx(64 * 400 * 11_520)
    assert baf.decode_step_stream_bytes(shape, 0.0, lanes=1) == pytest.approx(outside + experts * 4 / 64)
    assert baf.mhc_bytes_per_token(shape) == 14 * 3584 * 4 == 200_704
    assert baf.mhc_phi_bytes(shape) == 14336 * 24 * 2 == 688_128
    # a chunk: the mixer's five matrices, two sublayers' phi and mixing, the dense feed-forward, and in an
    # expert layer the router, the shared expert and 4 experts
    flops = baf.prefill_chunk_flops(shape, 1024, 0.0)
    mixer = 28_411_136 - 768 - 512
    residual = 2 * (14336 * 24 + 24 * 3584)
    per_token = 5 * (mixer + residual) + 3 * 3584 * 9216 + 4 * (3584 * 64 + 5 * 11_010_048)
    assert flops == pytest.approx(1024 * 2 * per_token)
    assert baf.prefill_chunk_flops(shape, 1024, 256.0) - flops == pytest.approx(
        1024 * 5 * 2 * 32 * (2 * 512 + 64) * 256)


def test_the_cells_file_and_its_entry_agree():
    """The traffic ISSUE 61 names: closed, 64 clients = slots, pre-roll 6 s,
    the chat lengths, no sharing; one chip; the depth and the leading dense
    layers reduced and nothing else: every width, all 64 experts, 32 heads and
    131,072 vocabulary rows as published; and every number of the catalog row
    under its key."""
    bench, cell, cfg = load("BENCHMARK.json"), load("benchmark", "workloads", CELL + ".json"), the_configuration()
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (cell["config"], cell["traffic"], 1)
    assert (cell["config"], cell["traffic"]) == (CONFIG, "batch")
    assert cell["arrivals"] == {"gen": "closed", "clients": 64} and cell["preroll_s"] == 6
    assert cell["prompt_tokens"] == {"gen": "lognormal_clipped", "median": 256, "sigma": 0.7, "lo": 32, "hi": 1024}
    assert cell["output_tokens"] == {"gen": "lognormal_clipped", "median": 128, "sigma": 0.5, "lo": 16, "hi": 384}
    assert cell["sharing"].startswith("none")
    for other in ("batch.openpangu-ultra-moe-718b", "batch.lfm2-24b-a2b"):  # the cells it reads off against, to the digit
        theirs = load("benchmark", "workloads", other + ".json")
        assert all(cell[k] == theirs[k] for k in ("arrivals", "preroll_s", "prompt_tokens", "output_tokens", "sharing"))
    for said in ("residual path", "40 DEPENDENT normalisations", "4 rows each", "long context", "prefix reuse",
                 "speculation", "No second cell"):
        assert said in cell["why"], said
    conf = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == ["num_hidden_layers", "first_k_dense_replace"]
    # the README's form: "<key>: <published> -> <used>", <key> a top-level key of the file holding <used>
    assert cfg["reduced"] == ["num_hidden_layers: 40 -> 5", "first_k_dense_replace: 2 -> 1"]
    for line in cfg["reduced"]:
        key, change = line.split(": ")
        published, used = change.split(" -> ")
        assert cfg[key] == int(used) and cfg[key + "_published"] == int(published)
    assert conf["source"] == cfg["source"] and conf["file"] == f"benchmark/configs/{CONFIG}.json"
    assert cfg["serving"]["chips"] == 1
    listed = {m["name"]: m for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    # ISSUE 61 asked for the three `moe_*` readers and `mla_history_read_share` here too (each read a number in
    # this cell on the chip): tests/benchmark/test_window_readers.py, test_kimi_linear_cell.py and
    # test_openpangu_cell.py pin those four lists to the cells they had, and a PR of this kind edits no file
    # the benchmark has (PERF.md 7: a `benchmark` PR's)
    assert set(listed) == {"mhc_rows_per_mix", "chunk_history_read_share"}
    assert listed["mhc_rows_per_mix"] == {
        "name": "mhc_rows_per_mix", "unit": "rows", "better": "higher", "source": "program_counter",
        "layer": "model, residual path", "moves": "ttft_mean_ms", "workloads": [CELL]}
    for said in ("ep_size 1", "all 64 routed experts", "all 32 heads", "131,072", "pipeline stages", "4 rows an expert"):
        assert said in cfg["deployment"], said
    flags = cfg["serving"]["server_flags"]
    assert flags == ["--max-batch-size", "64", "--max-model-len", "2048", "--kv-block-size", "16"]
    assert cfg["serving"]["engine_args"] == {"decode_steps": 4, "seed": 0} and cfg["serving"]["ready_timeout_s"] == 900
    assert (cfg["reference"], cfg["bytes_and_flops"]) == ("reference_xing4", "bytes_and_flops_xing4")
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"], cfg["n_routed_experts"], cfg["vocab_size"],
            cfg["num_attention_heads"], cfg["num_nextn_predict_layers"]) == (5, 1, 64, 131072, 32, 1)
    assert cfg["num_experts"] == cfg["n_routed_experts"]  # the name the moe_* readers take the held count under
    # the nested group whole, and its flat spelling beside it: run.py writes a config.json of scalar keys
    assert cfg["rope_scaling"] == {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
                                   "original_max_position_embeddings": 4096, "type": "yarn"}
    assert {k[len("rope_scaling_"):]: v for k, v in cfg.items() if k.startswith("rope_scaling_")} == cfg["rope_scaling"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(line) for line in f if '"Xing4.0-29B-A4B"' in line)
        assert row["source_url"] == cfg["source"]
        differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differs == set(conf["reduced"]), differs
    assumed = " ".join(cfg["assumed"])
    for said in ("NO weight", "ADDED to the Sinkhorn denominators", "COLUMNS first", "BEFORE exp", "SUM",
                 "pre-norm", "half-split", "SMALL SEEDED VALUES", "DeepSeek-V3 form", "ALIVE", "the published code wins"):
        assert said in assumed, said
    limit = cfg["correct_limits"]["logprob_rms"]
    assert 0.0139 <= limit <= 0.0434  # what tests/benchmark/test_benchmark.py allows a configuration


@pytest.mark.timeout(300)
def test_the_int8_control_in_the_programs_place_comes_out_as_not_correct():
    """The program's own pass (bf16 weights and mHC sets, float32 activations
    in three bfloat16 parts, chunks of 32 through the latent pages, absorbed)
    agrees with the float32 reference under the configuration's limit, the main
    logits and the prediction module's; ``reference_control_xing4`` (every
    product against a weight in int8, the router and the maps float32) does
    not, 3 x and more apart."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_child, reference_control_xing4, reference_xing4
    from dynamo_tpu.engine_jax.weights import xing4_config
    from dynamo_tpu.models import xing4

    cfg = xing4_config(SMALL, jnp.bfloat16)
    params = xing4.init_params(jax.random.PRNGKey(3), cfg)
    n, answered, chunk = 96, 24, 32
    tokens = jax.random.randint(jax.random.PRNGKey(0), (n + 1,), 0, SMALL["vocab_size"])
    at = jnp.arange(n - answered, n)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference_xing4.logits(params, SMALL, tokens[:n], at))
        want_draft = np.asarray(reference_xing4.draft_logits(params, SMALL, tokens, at))
        control = np.asarray(reference_control_xing4.logits(params, SMALL, tokens[:n], at))
    cache = xing4.make_kv_cache(cfg, 16, 16, drafting=True)
    tables, out, drafts = jnp.arange(1, 9, dtype=jnp.int32)[None], [], []
    for lo in range(0, n, chunk):
        pos = jnp.arange(lo, lo + chunk)[None]
        x, cache, _, _ = xing4.forward_chunk(
            params, cfg, tokens[None, lo:lo + chunk], pos, cache, tables, None, jnp.asarray([0]), raw=True)
        out.append(xing4.lm_head(params, cfg, xing4.final_norm(params, cfg, x)[0]))
        hd, cache, _ = xing4.draft_chunk(params, cfg, x, tokens[None, lo + 1:lo + chunk + 1], pos, cache, tables)
        drafts.append(xing4.lm_head(params, cfg, hd[0]))
    program = np.asarray(jnp.concatenate(out), np.float32)[n - answered:]
    module = np.asarray(jnp.concatenate(drafts), np.float32)[n - answered:]
    limit = the_configuration()["correct_limits"]["logprob_rms"]
    sound = reference_child.held_against(want, *reference_child.answer_of(program, 20), limit)
    drafted = reference_child.held_against(want_draft, *reference_child.answer_of(module, 20), limit)
    lower = reference_child.held_against(want, *reference_child.answer_of(control, 20), limit)
    assert sound["agrees"] and sound["logprob_pairs"] == 20 * answered, sound
    assert drafted["agrees"], drafted
    assert not lower["agrees"] and lower["logprob_rms"] > 3 * sound["logprob_rms"], (sound, lower)


def test_mhc_rows_per_mix_reads_a_fixture_and_nothing_without_the_counters():
    """The rise of ``mhc_rows_mixed`` over the rise of ``mhc_mix_calls``: over
    the window's samples where they carry the counters, else the two ends of
    the run; None from a parent without the module, and where no call was
    made."""
    read = bench_run.load_readers("layer_metrics")["mhc_rows_per_mix"].read
    ends = {"engine_before": {"mhc_mix_calls": 100, "mhc_rows_mixed": 6_400},
            "engine_after": {"mhc_mix_calls": 1_100, "mhc_rows_mixed": 102_400}}
    assert read(ends) == 96.0
    samples = [{"mhc_mix_calls": 200, "mhc_rows_mixed": 20_000, "t": 0.5},
               {"mhc_mix_calls": 400, "mhc_rows_mixed": 40_000, "t": 1.0},
               {"mhc_mix_calls": 700, "mhc_rows_mixed": 60_000, "t": 1.5}]
    assert read({**ends, "engine_samples": samples}) == 80.0  # the window's own ends win
    assert read({"engine_before": {"x": 1}, "engine_after": {"x": 2}}) is None  # a parent without the module
    assert read({}) is None
    still = {"mhc_mix_calls": 5, "mhc_rows_mixed": 50}
    assert read({"engine_before": still, "engine_after": dict(still)}) is None  # no call made


@pytest.mark.timeout(400)
def test_the_readers_read_a_rehearsal_of_this_cell(monkeypatch):
    """``run.py``'s own launch of this cell's server on the CPU (the
    configuration's flags; ``in=http out=jax`` over a card ``run.py`` wrote,
    the YaRN group in its flat spelling) at a tiny ``xing4_0`` shape in
    ``rehearse.json``'s place, two greedy answers between two snapshots of
    ``/debug/engine``: ``mhc_rows_per_mix`` and the expert and latent readers,
    as they are, return numbers from it."""
    from benchmark import client, traffic

    shape = {**SMALL, "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
             "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
             "v_head_dim": 16, "vocab_size": 2048}
    real = bench_run.load_json
    monkeypatch.setattr(bench_run, "load_json", lambda *parts: (
        {"shape": shape} if parts[-1] == "rehearse.json" else real(*parts)))
    go = bench_run.Launch(CELL, 2147483791, False, True)
    try:
        dev = go.wait_ready()
        assert dev["platform"] == "cpu"
        with open(os.path.join(go.model_dir, "config.json")) as f:
            served = json.load(f)
        assert served["model_type"] == "xing4_0" and served["rope_scaling_type"] == "yarn"
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
    names = ("moe_layer_calls", "moe_held_rows", "moe_experts_hit", "moe_routed_pairs", "moe_rows_computed",
             "moe_expert_reads", "mla_layer_calls", "mla_history_positions_read", "mla_history_positions_live",
             "mtp_layer_calls", "mhc_mix_calls", "mhc_rows_mixed")
    assert all(name in after for name in names)  # the twelve counters of /debug/engine
    rise = {k: after[k] - before[k] for k in names + ("prefix_hit_tokens", "spec_drafted_tokens")}
    assert rise["mtp_layer_calls"] == 0 and rise["spec_drafted_tokens"] == 0  # the served default
    assert rise["prefix_hit_tokens"] == 32  # two blocks of 16 of the 40 tokens asked again
    mixed = readers["mhc_rows_per_mix"].read(ctx)
    assert mixed == rise["mhc_rows_mixed"] / rise["mhc_mix_calls"] and 1 <= mixed <= 40
    assert rise["mhc_mix_calls"] == 2 * rise["mla_layer_calls"]  # two sublayers a layer, the mixer one of them
    assert 0 < readers["mla_history_read_share"].read(ctx) < 5
    rows, share = readers["moe_rows_per_held_expert"].read(ctx), readers["moe_experts_hit_share"].read(ctx)
    assert rows == rise["moe_held_rows"] / (rise["moe_layer_calls"] * 16) and 0 < rows
    assert share == 100.0 * rise["moe_experts_hit"] / (rise["moe_layer_calls"] * 16) and 0 < share <= 100
    assert rise["moe_held_rows"] == rise["moe_routed_pairs"] > 0  # every expert is held: every pair is computed here
