"""What ``batch.jamba2-3b`` brings to the benchmark beside its data: the module
that counts the configuration's bytes and operations against the program's own
parameters and state, the cell's file against its entry, the reader of the
state-space layers' counters on hand-made snapshots (ready for an entry,
unregistered: PERF.md 7), and the control of ``correct`` at a width a test can
hold.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import bytes_and_flops_jamba as baf  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

CELL, CONFIG = "batch.jamba2-3b", "jamba2-3b"

# both kinds of layer and a run of two Mamba layers, at a width a test can hold
SMALL = {
    "model_type": "jamba", "hidden_size": 256, "intermediate_size": 512, "num_hidden_layers": 6,
    "num_attention_heads": 8, "num_key_value_heads": 1, "attn_layer_period": 3,
    "attn_layer_offset": 1, "num_experts": 1, "num_experts_per_tok": 1, "mamba_expand": 2,
    "mamba_d_state": 16, "mamba_dt_rank": 16, "mamba_d_conv": 4, "rms_norm_eps": 1e-6,
    "vocab_size": 4096, "tie_word_embeddings": True,
}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def the_configuration():
    return load("benchmark", "configs", CONFIG + ".json")


def made(shape, what):
    """The shapes ``models/jamba.py`` makes for ``shape`` (nothing is made)."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine_jax.weights import jamba_config
    from dynamo_tpu.models import jamba

    cfg = jamba_config(shape, jnp.bfloat16)
    return jax.tree.leaves(jax.eval_shape(lambda: {
        "params": lambda: jamba.init_params(jax.random.PRNGKey(0), cfg),
        "state": lambda: jamba.make_slot_state(cfg, 64),
        "pool": lambda: jamba.make_kv_cache(cfg, 12288, 16),
    }[what]()))


@pytest.mark.parametrize("which", ["small", "configuration"])
def test_param_count_is_the_number_of_elements_the_program_makes(which):
    """At the published shape too (shapes only, nothing is made): 3,029,337,472,
    ISSUE 41's count by hand, and ``memory_account_bytes`` is the module's
    counts: the weights in bf16, the pool and the 64 slots' state as the
    program allocates them."""
    shape = SMALL if which == "small" else the_configuration()
    assert baf.param_count(shape) == sum(int(a.size) for a in made(shape, "params"))
    if which == "configuration":
        account = shape["memory_account_bytes"]
        assert baf.param_count(shape) == 3_029_337_472
        assert account["weights"] == baf.weight_bytes(shape) == 6_058_674_944
        assert account["kv_bytes_per_token"] == baf.kv_bytes_per_token(shape) == 1024
        assert account["kv_block"] == 16 * 1024
        assert account["slot_state"] == 64 * baf.slot_state_bytes(shape) == 647_495_680
        assert account["slot_state"] == sum(a.size * a.dtype.itemsize for a in made(shape, "state"))
        assert account["kv_pool"] == sum(a.size * a.dtype.itemsize for a in made(shape, "pool"))
        assert account["dense_history_buffer"] == 64 * 2048 * 1024


def test_a_decode_step_streams_the_weights_once_and_every_lanes_state_there_and_back():
    """7.35 GB at the configuration's 64 lanes (from its ``--max-batch-size``)
    before any context: 8.98 ms at 819 GB/s; live K and V on top, 1 KB a token."""
    shape = the_configuration()
    assert baf.lanes_of(shape) == 64
    at_rest = baf.decode_step_stream_bytes(shape, 0.0)
    assert at_rest == baf.weight_bytes(shape) + 2 * 64 * baf.slot_state_bytes(shape) == 7_353_666_304
    assert baf.decode_step_stream_bytes(shape, 64 * 400.0) - at_rest == 64 * 400 * 1024
    assert baf.decode_step_stream_bytes(shape, 0.0, lanes=1) == (
        baf.weight_bytes(shape) + 2 * baf.slot_state_bytes(shape))
    # an untied table is read by row: the lookup's, not the head's
    untied = dict(shape, tie_word_embeddings=False)
    assert baf.param_count(untied) - baf.param_count(shape) == 65536 * 2560
    assert baf.decode_step_stream_bytes(untied, 0.0) == at_rest
    # 8 rows of 128 positions: 5.9 TFLOP, nearly all of it products
    flops = baf.prefill_chunk_flops(shape, 1024, 0.0)
    assert 5.8e12 < flops < 5.95e12
    assert baf.prefill_chunk_flops(shape, 1024, 256.0) - flops == 1024 * 2 * 2 * 2 * 20 * 128 * 256


def test_the_cells_file_and_its_entry_agree():
    """The traffic ISSUE 41 names: closed, 64 clients = slots, pre-roll 6 s,
    the chat lengths, no sharing; one chip; nothing reduced."""
    bench, cell, cfg = load("BENCHMARK.json"), load("benchmark", "workloads", CELL + ".json"), the_configuration()
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (cell["config"], cell["traffic"], 1)
    assert (cell["config"], cell["traffic"]) == (CONFIG, "batch")
    assert cell["arrivals"] == {"gen": "closed", "clients": 64} and cell["preroll_s"] == 6
    assert cell["prompt_tokens"] == {"gen": "lognormal_clipped", "median": 256, "sigma": 0.7, "lo": 32, "hi": 1024}
    assert cell["output_tokens"] == {"gen": "lognormal_clipped", "median": 128, "sigma": 0.5, "lo": 16, "hi": 384}
    assert cell["sharing"].startswith("none")
    conf = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == cfg["reduced"] == [] and conf["source"] == cfg["source"]
    assert conf["file"] == f"benchmark/configs/{CONFIG}.json" and cfg["serving"]["chips"] == 1
    flags = cfg["serving"]["server_flags"]
    assert flags[flags.index("--max-batch-size") + 1] == "64" and cfg["serving"]["engine_args"]["decode_steps"] == 4
    assert (cfg["reference"], cfg["bytes_and_flops"]) == ("reference_jamba", "bytes_and_flops_jamba")
    # every number of the catalog row's config, under the same key
    published = {"attn_layer_offset": 7, "attn_layer_period": 14, "hidden_size": 2560, "intermediate_size": 8192,
                 "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2,
                 "num_attention_heads": 20, "num_experts": 1, "num_hidden_layers": 28,
                 "num_key_value_heads": 1, "vocab_size": 65536, "rms_norm_eps": 1e-6}
    assert {k: cfg[k] for k in published} == published


BASE = {"request_active_slots": 64, "request_total_slots": 64, "kv_active_blocks": 9, "kv_total_blocks": 64}


def snap(tokens, passes):
    return BASE | {"ssm_chunk_tokens": tokens, "ssm_state_passes": passes}


@pytest.mark.parametrize("before, after, want", [
    # a program without the counters (the parent, another model): nothing to read, no error
    (BASE, BASE, None),
    (None, None, None),
    (BASE, snap(800, 800), None),
    # the rise over the rise: a token scan passes the state once a token; a chunk kernel once a row
    (snap(1000, 1000), snap(1000 + 26 * 4000, 1000 + 26 * 4000), 1.0),
    (snap(1000, 1000), snap(1000 + 26 * 4000, 1000 + 26 * 40), 100.0),
    # no pass between the snapshots
    (snap(1000, 1000), snap(1000, 1000), None),
], ids=["parent", "nothing", "one_end", "token_scan", "chunk_kernel", "no_pass"])
def test_the_reader_takes_the_rise_of_tokens_over_the_rise_of_passes(before, after, want):
    reader = bench_run.load_readers("layer_metrics")["ssm_tokens_per_state_pass"]
    ctx = {"engine_samples": [], "engine_before": before, "engine_after": after, "shape": {}}
    assert reader.read(ctx) == want


def test_the_reader_fits_the_entry_a_benchmark_pr_registers_it_with():
    """``BENCHMARK.json`` registers the cell and not the reader (no per-layer
    entry can be added before ``test_chunk_history_read_share.py:48`` looks its
    entry up by name: PERF.md 7). An entry of its name, once there, is this."""
    bench = load("BENCHMARK.json")
    reader = bench_run.load_readers("layer_metrics")["ssm_tokens_per_state_pass"]
    want = {"name": "ssm_tokens_per_state_pass", "unit": "tokens", "better": "higher",
            "source": "program_counter", "layer": "model, state-space layers",
            "moves": "ttft_mean_ms", "workloads": [CELL]}
    assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) == (
        want["name"], want["unit"], want["layer"], want["moves"])
    assert want["moves"] in {m["name"] for m in bench["end_to_end"]}
    assert [m for m in bench["per_layer"] if m["name"] == want["name"]] in ([], [want])


@pytest.mark.timeout(300)
def test_the_int8_control_in_the_programs_place_comes_out_as_not_correct():
    """The program's own pass (bf16 weights and activations, chunks of 32
    through slot state and K/V pages) agrees with the float32 reference under
    the configuration's limit; ``reference_control_jamba`` (every product
    against a weight in int8) does not, 3 x and more apart."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_child, reference_control_jamba, reference_jamba
    from dynamo_tpu.engine_jax.weights import jamba_config
    from dynamo_tpu.models import jamba

    cfg = jamba_config(SMALL, jnp.bfloat16)
    params = jamba.init_params(jax.random.PRNGKey(3), cfg)
    n, answered, chunk = 96, 24, 32
    tokens = jax.random.randint(jax.random.PRNGKey(0), (n,), 0, SMALL["vocab_size"])
    at = jnp.arange(n - answered, n)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference_jamba.logits(params, SMALL, tokens, at))
        control = np.asarray(reference_control_jamba.logits(params, SMALL, tokens, at))
    cache, state = jamba.make_kv_cache(cfg, 16, 16), jamba.make_slot_state(cfg, 2)
    tables, out = jnp.arange(1, 9, dtype=jnp.int32)[None], []
    for lo in range(0, n, chunk):
        h, cache, state, _ = jamba.forward_chunk(
            params, cfg, tokens[None, lo:lo + chunk], jnp.arange(lo, lo + chunk)[None], cache, tables,
            state, jnp.asarray([0]))
        out.append(jamba.lm_head(params, cfg, h[0]))
    program = np.asarray(jnp.concatenate(out), np.float32)[n - answered:]
    limit = the_configuration()["correct_limits"]["logprob_rms"]
    sound = reference_child.held_against(want, *reference_child.answer_of(program, 20), limit)
    lower = reference_child.held_against(want, *reference_child.answer_of(control, 20), limit)
    assert sound["agrees"] and sound["logprob_pairs"] == 20 * answered, sound
    assert not lower["agrees"] and lower["logprob_rms"] > 3 * sound["logprob_rms"], (sound, lower)
