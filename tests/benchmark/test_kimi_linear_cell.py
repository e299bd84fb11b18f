"""What ``batch.kimi-linear-48b-a3b`` brings to the benchmark beside its data:
the module that counts the configuration's bytes and operations against the
program's own parameters, the two readers of the expert layer's counters on
hand-made snapshots (ready for an entry, unregistered: PERF.md 7), and the
control of ``correct`` at a width a test can hold.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import bytes_and_flops_kimi_linear as baf  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

CELL, CONFIG = "batch.kimi-linear-48b-a3b", "kimi-linear-48b-a3b"

# the five-layer pattern at a width a test can hold; 8 experts of 64 chosen, 32 held
SMALL = {
    "model_type": "kimi_linear", "hidden_size": 256, "intermediate_size": 1024,
    "num_hidden_layers": 5, "num_attention_heads": 4, "kv_lora_rank": 64,
    "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
    "linear_attn_num_heads": 4, "linear_attn_head_dim": 32, "short_conv_kernel_size": 4,
    "kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8],
    "first_k_dense_replace": 1, "moe_intermediate_size": 128, "num_experts": 32,
    "num_experts_published": 64, "num_experts_per_token": 8, "num_shared_experts": 1,
    "routed_scaling_factor": 2.446, "moe_renormalize": True, "rms_norm_eps": 1e-5,
    "vocab_size": 4096, "tie_word_embeddings": False,
}


def the_configuration():
    with open(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")) as f:
        return json.load(f)


def elements(shape):
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine_jax.weights import kimi_linear_config
    from dynamo_tpu.models import kimi_linear

    made = jax.eval_shape(lambda: kimi_linear.init_params(
        jax.random.PRNGKey(0), kimi_linear_config(shape, jnp.bfloat16)))
    return sum(int(a.size) for a in jax.tree.leaves(made))


@pytest.mark.parametrize("which", ["small", "configuration"])
def test_param_count_is_the_number_of_elements_the_program_makes(which):
    """At the configuration's shape too (shapes only, nothing is made): 4,283 M
    within 1 %, and ``memory_account_bytes.weights`` is that count in bf16."""
    shape = SMALL if which == "small" else the_configuration()
    assert baf.param_count(shape) == elements(shape)
    if which == "configuration":
        assert abs(baf.param_count(shape) / 4.283e9 - 1) < 0.01
        assert shape["memory_account_bytes"]["weights"] == baf.weight_bytes(shape)
        assert shape["memory_account_bytes"]["kv_bytes_per_token"] == baf.kv_bytes_per_token(shape) == 2304
        assert shape["memory_account_bytes"]["slot_state"] == 64 * baf.slot_state_bytes(shape)


def test_a_decode_step_streams_the_experts_it_hits_and_the_lanes_state():
    """The lanes come from the configuration's ``--max-batch-size``; more lanes
    hit more of the held experts and carry more state, never more than all."""
    shape = the_configuration()
    assert baf.lanes_of(shape) == 64
    assert 0.86 * 128 < baf.experts_hit(shape, 64) < 0.88 * 128
    one, all_ = (baf.decode_step_stream_bytes(shape, 0.0, lanes=n) for n in (1, 64))
    assert one < all_ == baf.decode_step_stream_bytes(shape, 0.0) < baf.weight_bytes(shape) + 2 * 64 * baf.slot_state_bytes(shape)
    assert baf.decode_step_stream_bytes(shape, 1000.0) - all_ == 1000 * 2304
    # 583 M weights a position goes through (4 of its 8 experts are held), 2 operations each
    assert 0.55e9 < baf.prefill_chunk_flops(shape, 128, 64.0) / (128 * 2) < 0.65e9


BASE = {"request_active_slots": 64, "request_total_slots": 64, "kv_active_blocks": 9, "kv_total_blocks": 64}


def snap(calls, rows, hit):
    return BASE | {"moe_layer_calls": calls, "moe_held_rows": rows, "moe_experts_hit": hit}


@pytest.mark.parametrize("before, after, rows, share", [
    # a program without the counters (the parent, a dense model): nothing to read, no error
    (BASE, BASE, None, None),
    (None, None, None, None),
    (BASE, snap(8, 100, 50), None, None),
    # the rise over the rise: 40 calls x 128 held experts
    (snap(10, 1000, 500), snap(50, 1000 + 40 * 256, 500 + 40 * 112), 2.0, 87.5),
    # no call between the snapshots
    (snap(10, 1000, 500), snap(10, 1000, 500), None, None),
], ids=["parent", "nothing", "one_end", "rise", "no_call"])
def test_the_expert_readers_take_the_rise_of_rows_and_of_experts_hit_over_the_rise_of_calls(
        before, after, rows, share):
    readers = bench_run.load_readers("layer_metrics")
    ctx = {"engine_samples": [], "engine_before": before, "engine_after": after,
           "shape": {"num_experts": 128}}
    assert readers["moe_rows_per_held_expert"].read(ctx) == rows
    assert readers["moe_experts_hit_share"].read(ctx) == share


def test_the_two_readers_fit_the_entries_a_benchmark_pr_registers_them_with():
    """``BENCHMARK.json`` registers the cell and not the two readers: an entry
    put last turns ``test_chunk_history_read_share.py:48`` red, and one put
    before the last reads to the driver as a change to the last (PERF.md 7).
    An entry of either name, once there, is the one held here."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    readers = bench_run.load_readers("layer_metrics")
    for name, unit, better in (("moe_rows_per_held_expert", "rows", "higher"),
                               ("moe_experts_hit_share", "%", "lower")):
        want = {"name": name, "unit": unit, "better": better, "source": "program_counter",
                "layer": "model, expert layer", "moves": "ttft_mean_ms", "workloads": [CELL]}
        reader = readers[name]
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) == (
            want["name"], want["unit"], want["layer"], want["moves"])
        assert want["moves"] in {m["name"] for m in bench["end_to_end"]}
        assert [m for m in bench["per_layer"] if m["name"] == name] in ([], [want])
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "batch", 1)


@pytest.mark.timeout(300)
def test_the_int8_control_in_the_programs_place_comes_out_as_not_correct():
    """The program's own pass (bf16 weights, chunks of 32 through slot state
    and latent pages) agrees with the float32 reference under the
    configuration's limit; ``reference_control_kimi_linear`` (every product
    against a weight in int8) does not, 3 x and more apart."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_child, reference_control_kimi_linear, reference_kimi_linear
    from dynamo_tpu.engine_jax.weights import kimi_linear_config
    from dynamo_tpu.models import kimi_linear as kl

    cfg = kimi_linear_config(SMALL, jnp.bfloat16)
    params = kl.init_params(jax.random.PRNGKey(3), cfg)
    n, answered, chunk = 96, 24, 32
    tokens = jax.random.randint(jax.random.PRNGKey(0), (n,), 0, SMALL["vocab_size"])
    at = jnp.arange(n - answered, n)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference_kimi_linear.logits(params, SMALL, tokens, at))
        control = np.asarray(reference_control_kimi_linear.logits(params, SMALL, tokens, at))
    cache, state = kl.make_kv_cache(cfg, 16, 16), kl.make_slot_state(cfg, 2)
    tables, out = jnp.arange(1, 9, dtype=jnp.int32)[None], []
    for lo in range(0, n, chunk):
        h, cache, state, _ = kl.forward_chunk(
            params, cfg, tokens[None, lo:lo + chunk], jnp.arange(lo, lo + chunk)[None], cache, tables,
            state, jnp.asarray([0]))
        out.append(kl.lm_head(params, cfg, h[0]))
    program = np.asarray(jnp.concatenate(out), np.float32)[n - answered:]
    limit = the_configuration()["correct_limits"]["logprob_rms"]
    sound = reference_child.held_against(want, *reference_child.answer_of(program, 20), limit)
    lower = reference_child.held_against(want, *reference_child.answer_of(control, 20), limit)
    assert sound["agrees"] and sound["logprob_pairs"] == 20 * answered, sound
    assert not lower["agrees"] and lower["logprob_rms"] > 3 * sound["logprob_rms"], (sound, lower)
