"""Kimi-Linear on the served path, at a tiny size on the CPU in float32
(hidden 64, 2 KDA heads of 16, 8 experts top-2 holding 4, the five-layer
pattern KDA+dense, KDA, KDA, MLA, KDA of ``kimi-linear-48b-a3b``).

The program (``models/kimi_linear.py``: chunked prefill through per-slot state
and latent pages, then decode) is held against the benchmark's plain reference
(``benchmark/reference_kimi_linear.py``: one sequence, token by token, no
cache); the expert layer (``ops/moe.py``) against the share rule of the
model-configs guide; the engine against both, and against the refusals a model
with per-slot state owes whatever would hand its pages over without it.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_kimi_linear as ref
from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
from dynamo_tpu.engine_jax.weights import config_from_card
from dynamo_tpu.kv.pages import MigrationRejected, StateNotPortable
from dynamo_tpu.models import kimi_linear as kl
from dynamo_tpu.models import llama, module_for
from dynamo_tpu.ops import moe
from dynamo_tpu.ops.pallas.kda_scan import kda_scan

from .delta_harness import KIMI_SHAPE as SHAPE
from .step_programs import (  # noqa: F401  (highest_precision: autouse, for this file's tests)
    answer, card, chunk_program, decode_program, highest_precision, prompt_of, reference_program, run_out, served,
    step, submit,
)

# ATOL: float32 on the CPU, at the highest matmul precision on both sides. The
# program and the reference order their sums differently (absorbed against
# expanded latent attention, experts' rows batched against every token through
# every expert, a chunk's convolution against the whole sequence's): 2e-4 on
# logits of magnitude 4 is what test_plain_reference_agrees_with_the_program_
# at_a_tiny_width allows the dense decoder for the same reason, and a wrong
# state, page or expert moves a logit by 1e-1 and more.
ATOL = 2e-4

ENGINE_CFG = EngineConfig(max_slots=4, kv_block_size=8, max_model_len=96,
                          prefill_chunk=16, decode_steps=4, top_logprobs=5)


@pytest.fixture(scope="module")
def cfg():
    return config_from_card(card(SHAPE), jnp.float32)


@pytest.fixture(scope="module")
def params(cfg):
    return kl.init_params(jax.random.PRNGKey(3), cfg)


@pytest.fixture(scope="module")
def engine(cfg, params):
    eng = JaxServingEngine(cfg, params, ENGINE_CFG)
    yield eng
    eng.close()


def test_the_five_layers_are_the_published_pattern(cfg):
    assert kl.layer_kinds(cfg) == ("kda", "kda", "kda", "mla", "kda")
    assert [kl.is_expert_layer(cfg, i) for i in range(5)] == [False, True, True, True, True]
    assert module_for(cfg) is kl and module_for(llama.LLAMA_PRESETS["tiny"]) is llama


@pytest.mark.parametrize("chunks", [(16, 16, 5), (7, 16, 14), (16, 9)],
                         ids=["full_chunks", "a_short_first_chunk", "two_chunks"])
def test_chunked_prefill_then_decode_agrees_with_the_plain_reference(cfg, params, chunks):
    """(a) A prompt fed in chunks whose boundaries lie inside it, each starting
    from the slot's KDA state and the latent pages the last one left, then
    three decode steps off the same state, against the reference's one pass
    over the whole sequence. A second row of the chunk is padding, and the
    other slots stay as they were."""
    n_prompt, n_decode = sum(chunks), 3
    tokens = np.asarray(prompt_of(n_prompt + n_decode, salt=len(chunks)), np.int32)
    want = np.asarray(reference_program(ref, SHAPE)(params, jnp.asarray(tokens), jnp.arange(len(tokens))))
    slots, c, bs, mb, slot = 4, 16, 8, 8, 2
    cache = kl.make_kv_cache(cfg, 32, bs)
    state = jax.tree.map(lambda a: a + 7.0, kl.make_slot_state(cfg, slots))  # stale, every slot
    tables = np.zeros((2, mb), np.int32)
    tables[0] = np.arange(1, 9)
    got, at = [], 0
    for n in chunks:
        toks, pos = np.zeros((2, c), np.int32), np.full((2, c), -1, np.int32)
        toks[0, :n], pos[0, :n] = tokens[at:at + n], np.arange(at, at + n)
        h, cache, state, sums = chunk_program(kl, cfg)(
            params, jnp.asarray(toks), jnp.asarray(pos), cache, jnp.asarray(tables),
            state, jnp.asarray([slot, slots], jnp.int32))
        got.append(kl.lm_head(params, cfg, h[0, :n]))
        assert int(sums[kl.COUNTERS.index("slot_state_resets")]) == (at == 0)  # the first chunk resets the slot, once
        at += n
    np.testing.assert_allclose(np.concatenate(got), want[:n_prompt], atol=ATOL)
    assert float(state["s"][0][0].min()) == 7.0  # another slot's state is untouched

    lanes_tables = np.zeros((slots, mb), np.int32)
    lanes_tables[slot] = tables[0]
    toks, pos = np.zeros((slots,), np.int32), np.full((slots,), -1, np.int32)
    toks[slot], pos[slot] = tokens[n_prompt], n_prompt

    forcing = np.zeros((slots, bs * mb), np.int32)  # teacher forcing: the sequence's own next token
    forcing[slot, :len(tokens)] = tokens
    out = decode_program(kl, cfg, n_decode, 95)(
        params, jnp.asarray(toks), jnp.asarray(pos), cache, jnp.asarray(lanes_tables), state, jnp.asarray(forcing))
    np.testing.assert_allclose(np.asarray(out[3])[:, slot], want[n_prompt:], atol=ATOL)
    assert float(out[5]["s"][0][0].min()) == 7.0 and int(out[1][slot]) == n_prompt + n_decode


def expert_weights(key, experts=8, e=16, f=8):
    k = jax.random.split(key, 6)
    return {"x": jax.random.normal(k[0], (37, e)),
            "router": jax.random.normal(k[1], (e, experts)),
            "router_bias": 0.3 * jax.random.normal(k[2], (experts,)),
            "w_gate": jax.random.normal(k[3], (experts, e, f)) / 4,
            "w_up": jax.random.normal(k[4], (experts, e, f)) / 4,
            "w_down": jax.random.normal(k[5], (experts, f, e)) / 3}


def test_the_expert_shares_add_up_to_the_uncut_reference_layer():
    """(b) Experts 0-3 and 4-7 as the two shares of a 2-chip deployment: their
    partial sums, with the shared expert counted once, are the reference's
    uncut layer. The model-configs guide's one test of the cut."""
    w = expert_weights(jax.random.PRNGKey(0))
    shape = {"num_experts_per_token": 2, "routed_scaling_factor": 2.446}
    shared = {"ws_gate": w["w_gate"][0], "ws_up": w["w_up"][0], "ws_down": w["w_down"][0]}
    whole = ref.expert_layer({**w, **shared}, shape, w["x"])
    ids, weights = moe.route_sigmoid_topk(w["x"], w["router"], w["router_bias"], 2, 2.446)
    parts = [moe.dropless_experts(
        w["x"], ids, weights, w["w_gate"][lo:lo + 4], w["w_up"][lo:lo + 4], w["w_down"][lo:lo + 4],
        first_expert=lo, num_experts_total=8) for lo in (0, 4)]
    once = ref.swiglu(w["x"], shared["ws_gate"], shared["ws_up"], shared["ws_down"])
    np.testing.assert_allclose(parts[0][0] + parts[1][0] + once, whole, atol=1e-5)
    # each share counted its own pairs, and together every pair routed
    assert int(parts[0][1][1]) + int(parts[1][1][1]) == int(parts[0][1][3]) == 37 * 2
    # ... and the reference given one share is that share
    half = ref.expert_layer({**w, **shared, **{k: w[k][4:] for k in ("w_gate", "w_up", "w_down")}},
                            shape, w["x"], first_expert=4)
    np.testing.assert_allclose(parts[1][0] + once, half, atol=1e-5)


def test_no_token_is_dropped_when_every_token_goes_to_one_expert():
    """(c) 37 tokens all routed to expert 0 and expert 1: runs of 37 rows, more
    than the 16 of a tile, so a run spans tiles, and every token still gets its
    full output. The products ran over the five tiles that hold the 74 rows
    (the third holds the end of one run and the start of the next, and is
    computed once for each: six visits) and read two experts, once each."""
    w = expert_weights(jax.random.PRNGKey(1))
    x = w["x"]
    ids = jnp.zeros((37, 2), jnp.int32).at[:, 1].set(1)
    weights = jnp.full((37, 2), 0.5)
    assert moe.rows_per_tile(37, 2, 8) == 16 < 37
    got, sums = moe.dropless_experts(x, ids, weights, w["w_gate"], w["w_up"], w["w_down"])
    want = sum(0.5 * ref.swiglu(x, w["w_gate"][e], w["w_up"][e], w["w_down"][e]) for e in (0, 1))
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert sums.tolist() == [1, 74, 2, 74, 6 * 16, 2]
    # a padding token computes nothing and counts nowhere
    valid = jnp.arange(37) < 30
    got, sums = moe.dropless_experts(x, ids, weights, w["w_gate"], w["w_up"], w["w_down"],
                                     token_valid=valid)
    np.testing.assert_allclose(got[:30], want[:30], atol=1e-5)
    assert float(jnp.abs(got[30:]).max()) == 0.0 and sums.tolist() == [1, 60, 2, 60, 5 * 16, 2]


def test_ragged_runs_in_one_call_are_the_reference_layer():
    """Held experts 2-5 of 8 in one call: expert 2 without a row, expert 3
    with one, expert 4 with 21 (more than a tile's 16), expert 5 with 9; ids of
    experts held elsewhere (0, 1, 6, 7) and padding tokens mixed in. Against
    the reference's layer given the same share; the counters from the runs."""
    w = expert_weights(jax.random.PRNGKey(2))
    x, lo = w["x"], 2
    first = np.asarray([3] + [4] * 21 + [5] * 9 + [0, 1, 6, 7, 0, 7], np.int32)  # 37 tokens
    second = np.asarray([0] * 31 + [4, 4, 5, 5, 3, 3], np.int32)  # the last six: padding
    ids = jnp.stack([jnp.asarray(first), jnp.asarray(second)], axis=1)
    weights = jax.random.uniform(jax.random.PRNGKey(5), (37, 2), jnp.float32, 0.1, 1.0)
    valid = jnp.arange(37) < 31
    held = {k: w[k][lo:lo + 4] for k in ("w_gate", "w_up", "w_down")}
    got, sums = moe.dropless_experts(x, ids, weights, held["w_gate"], held["w_up"], held["w_down"],
                                     first_expert=lo, num_experts_total=8, token_valid=valid)
    want = jnp.zeros_like(x)
    for e in (3, 4, 5):
        mine = jnp.where((ids == e) & valid[:, None], weights, 0.0).sum(axis=1)
        want = want + mine[:, None] * ref.swiglu(x, w["w_gate"][e], w["w_up"][e], w["w_down"][e])
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert float(jnp.abs(got[31:]).max()) == 0.0
    # 31 held pairs in runs of 0, 1, 21, 9: rows 0-30 lie in two tiles of 16;
    # the first is visited by experts 3 and 4, the second by 4 and 5
    assert sums.tolist() == [1, 31, 3, 62, 4 * 16, 3]
    # the reference's own layer (its router, its share) under a selection bias
    # that sends every token to expert 4 and none to expert 2
    bias = w["router_bias"].at[4].set(100.0).at[2].set(-100.0)
    shape = {"num_experts_per_token": 2, "routed_scaling_factor": 2.446}
    ids, weights = moe.route_sigmoid_topk(x, w["router"], bias, 2, 2.446)
    got, sums = moe.dropless_experts(x, ids, weights, held["w_gate"], held["w_up"], held["w_down"],
                                     first_expert=lo, num_experts_total=8, token_valid=valid)
    zero = jnp.zeros_like(w["w_gate"][0])
    whole = ref.expert_layer(
        {**held, "router": w["router"], "router_bias": bias, "ws_gate": zero, "ws_up": zero,
         "ws_down": zero.T}, shape, x, first_expert=lo)
    np.testing.assert_allclose(got[:31], whole[:31], atol=1e-5)
    assert int((ids == 4).sum()) == 37 and int((ids == 2).sum()) == 0 and int(sums[1]) >= 31


def test_the_three_part_arithmetic_goes_through_the_grouped_product():
    """bf16 weights and float32 rows in three bfloat16 parts, as the model
    hands them over (``ops/parts.py:operand_parts``), against the same layer at float32's
    highest precision: 24 bits of the activation reach every product."""
    w = expert_weights(jax.random.PRNGKey(4), e=32, f=16)
    w = {k: v.astype(jnp.bfloat16) if k.startswith("w_") else v for k, v in w.items()}
    x = w["x"]
    ids, weights = moe.route_sigmoid_topk(x, w["router"], w["router_bias"], 2, 2.446)
    got, _ = moe.dropless_experts(x, ids, weights, w["w_gate"], w["w_up"], w["w_down"],
                                  parts_of=kl._expert_parts)
    one, _ = moe.dropless_experts(x, ids, weights, w["w_gate"], w["w_up"], w["w_down"])
    f32 = {k: w[k].astype(jnp.float32) for k in ("w_gate", "w_up", "w_down")}
    want = jnp.zeros_like(x)
    for e in range(8):
        mine = jnp.where(ids == e, weights, 0.0).sum(axis=1)
        want = want + mine[:, None] * ref.swiglu(x, f32["w_gate"][e], f32["w_up"][e], f32["w_down"][e])
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= 1e-6 * scale
    # one bfloat16 part, the layer's own default, is what three are held against
    assert float(jnp.abs(one - want).max()) > 1e-3 * scale


def test_experts_are_chosen_by_score_plus_bias_and_weighed_by_score():
    """(d) A bias large enough to change the choice leaves the chosen experts'
    weights what their scores alone make them."""
    x = jnp.eye(3, 4)
    router = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [0.0, 0.1, 0.2, 0.3], [1.0, 1.0, 1.0, 1.0], [0.0] * 4])
    bias = jnp.asarray([0.0, 0.0, 0.0, 5.0])  # expert 3 is always chosen, whatever it scores
    ids, weights = moe.route_sigmoid_topk(x, router, bias, 2, 2.0)
    scores = jax.nn.sigmoid(x @ router)
    assert (np.sort(np.asarray(ids), axis=-1)[:, 1] == 3).all()
    assert np.sort(np.asarray(ids), axis=-1)[0, 0] == 0  # the best of the unbiased rest
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(ids), axis=-1)
    np.testing.assert_allclose(weights, 2.0 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    # without the bias expert 3 is the last choice of token 0
    assert 3 not in np.asarray(moe.route_sigmoid_topk(x, router, 0 * bias, 2, 2.0)[0])[0]
    np.testing.assert_allclose(
        ref.route({"router": router, "router_bias": bias},
                  {"num_experts_per_token": 2, "routed_scaling_factor": 2.0}, x)[np.arange(3)[:, None], ids],
        weights, rtol=1e-6)


@pytest.mark.parametrize("spelling", ["nested", "flat"])
def test_config_from_card_reads_either_spelling_of_the_kda_group(cfg, spelling):
    """(f) The published nested ``linear_attn_config`` and the flat keys the
    benchmark's harness can write give one configuration."""
    flat = ("linear_attn_num_heads", "linear_attn_head_dim", "short_conv_kernel_size",
            "kda_layers", "full_attn_layers")
    shape = dict(SHAPE)
    if spelling == "nested":
        shape = {k: v for k, v in SHAPE.items() if k not in flat}
        shape["linear_attn_config"] = {
            "num_heads": 2, "head_dim": 16, "short_conv_kernel_size": 4,
            "kda_layers": SHAPE["kda_layers"], "full_attn_layers": SHAPE["full_attn_layers"]}
    assert config_from_card(card(shape), jnp.float32) == cfg
    assert cfg.num_experts == 4 and cfg.num_experts_published == 8
    assert ref.sizes(shape) == ref.sizes(SHAPE)


def test_a_card_that_says_num_experts_is_no_dense_impostor():
    """(f) ``num_experts`` under a ``model_type`` no module runs is refused;
    the Mixtral spelling still loads the llama module's expert option."""
    with pytest.raises(ValueError, match="num_experts"):
        config_from_card(card({"model_type": "some_moe", "num_experts": 64, "hidden_size": 64}))
    mixtral = config_from_card(card({"model_type": "mixtral", "num_local_experts": 8, "hidden_size": 64}))
    assert isinstance(mixtral, llama.LlamaConfig) and mixtral.num_experts == 8
    assert isinstance(config_from_card(card({"model_type": "qwen2"})), llama.LlamaConfig)


def scanned(q, k, v, log_decay, beta, s0, n_valid):
    """What the kernel replaces: ``lax.scan`` of ``_kda_step`` over the tokens,
    a row's state kept where its valid tokens end."""
    def token(s, xs):
        q, k, v, log_decay, beta, i = xs
        new, o = kl._kda_step(s, q, k, v, log_decay, beta)
        ok = i < n_valid
        return jnp.where(ok[:, None, None, None], new, s), jnp.where(ok[:, None, None], o, 0.0)

    per_token = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, log_decay, beta))
    s, o = jax.lax.scan(token, s0, (*per_token, jnp.arange(q.shape[1])))
    return jnp.moveaxis(o, 0, 1), s


def assert_float32_equal(got, want):
    """Equal to float32 rounding: ``rtol`` 1e-6 of an element, and of the
    largest one where a sum cancelled to something small."""
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6 * float(jnp.abs(want).max()))


def recurrence_inputs(rows, t=128, h=4, d=128, decay=(-1.6, -0.001), seed=0):
    """Inputs as ``_kda_inputs`` makes them: unit keys, queries of length
    ``d ** -0.5``, beta in (0, 1), log-decay in ``decay``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v = (jax.random.normal(ks[i], (rows, t, h, d), jnp.float32) for i in range(3))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    log_decay = jax.random.uniform(ks[3], (rows, t, h, d), jnp.float32, *decay)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (rows, t, h), jnp.float32))
    carried = jax.random.normal(ks[5], (rows, h, d, d), jnp.float32)
    return (q, k, v, log_decay, beta), carried


@pytest.mark.parametrize("rows", [8, 16])
@pytest.mark.parametrize("start", ["zero", "carried"])
def test_the_chunk_kernel_is_the_scanned_step(rows, start):
    """Rows with 0, 1, 22 and 128 valid tokens of 128 in one call, at the
    published head size: state and outputs are the scan's to float32 rounding,
    outputs past a row's valid tokens are zeros, and a row without a valid
    token gets its state back bit for bit."""
    xs, carried = recurrence_inputs(rows, seed=rows)
    s0 = carried if start == "carried" else jnp.zeros_like(carried)
    n = jnp.asarray(([0, 1, 22, 128] * 4)[:rows], jnp.int32)
    o, s = kda_scan(*xs, s0, n, interpret=True)
    want_o, want_s = scanned(*xs, s0, n)
    assert_float32_equal(o, want_o)
    assert_float32_equal(s, want_s)
    idle = np.asarray(n) == 0
    assert np.array_equal(np.asarray(s)[idle], np.asarray(s0)[idle])
    past = np.arange(128)[None, :] >= np.asarray(n)[:, None]
    assert float(jnp.abs(jnp.where(past[:, :, None, None], o, 0.0)).max()) == 0.0


def test_the_chunk_kernel_takes_a_chunk_that_is_no_whole_source_register():
    """12 tokens of 2 heads of 16 channels (two heads a step, 8 tokens a source
    register: the chunk is padded to 16), as a tiny engine's chunk may be."""
    xs, carried = recurrence_inputs(3, t=12, h=2, d=16, seed=2)
    n = jnp.asarray([12, 5, 0], jnp.int32)
    o, s = kda_scan(*xs, carried, n, interpret=True)
    want_o, want_s = scanned(*xs, carried, n)
    assert o.shape == want_o.shape
    assert_float32_equal(o, want_o)
    assert_float32_equal(s, want_s)


def test_the_chunk_kernel_stands_a_decay_of_minus_twenty_a_token():
    """Log-decay down to -20 a token over all 128 tokens, whose sum over a block
    no factored form holds in float32: the kernel decays token by token as
    the step does, so nothing overflows and it is the scan still."""
    xs, carried = recurrence_inputs(4, decay=(-20.0, -15.0), seed=5)
    n = jnp.full((4,), 128, jnp.int32)
    o, s = kda_scan(*xs, carried, n, interpret=True)
    want_o, want_s = scanned(*xs, carried, n)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s).all())
    assert_float32_equal(o, want_o)
    assert_float32_equal(s, want_s)


def test_the_engine_serves_the_reference_greedy_tokens_and_logprobs(engine, params):
    """(g) Through ``JaxServingEngine``: admission, three chunk dispatches,
    pipelined decode dispatches of 4 steps, sampling and log-probabilities."""
    prompt = prompt_of(37)
    toks, lps, finish = served(engine, prompt, 10, logprobs=5)
    seq = jnp.asarray(prompt + toks[:-1], jnp.int32)
    want = np.asarray(reference_program(ref, SHAPE)(params, seq, jnp.arange(len(prompt) - 1, len(seq))))
    assert toks == want.argmax(-1).tolist() and len(toks) == 10 and finish == "length"
    logp = want - np.log(np.exp(want).sum(-1, keepdims=True))
    np.testing.assert_allclose(lps, logp[np.arange(10), toks], atol=ATOL)
    snap = engine.metrics_snapshot()
    assert snap["moe_layer_calls"] > 0 and snap["slot_state_resets"] >= 1
    # every routed pair is counted, and about half of them are held here
    assert 0 < snap["moe_held_rows"] < snap["moe_routed_pairs"]
    assert snap["moe_experts_hit"] <= 4 * snap["moe_layer_calls"]
    # the products ran over whole tiles of 16 rows, and read a hit expert once
    assert snap["moe_rows_computed"] % 16 == 0 and snap["moe_rows_computed"] >= snap["moe_held_rows"]
    assert snap["moe_expert_reads"] == snap["moe_experts_hit"]
    # the chunk program reads every table's full width, and the counters say so
    assert snap["chunk_history_tiles_read"] == snap["chunk_history_tiles_full"] > 0
    # state per slot beside the pages, handed from row to row inside the kernel: a lane may fill several
    # rows of a dispatch (here the ladder is [1, 4]: no rung under the full width holds two)
    assert kl.LANE_TAKES_ROWS and engine._lane_rows and engine._chunk_rungs == [1, 4]
    assert snap["chunk_rows_live"] == snap["chunk_lanes_fed"] > 0 and snap["kda_state_handovers"] == 0
    # the decode program walks the tiles its blocks of lanes hold: here ONE block of four lanes under a table
    # of one tile, so a dispatch counts the four pairs that are all there are (an engine of 16 slots reads a
    # quarter: test_a_served_prompt_passes_its_state_once_a_chunk)
    dispatches = snap["decode_history_tiles_full"] // ENGINE_CFG.max_slots
    assert 0 < snap["decode_history_tiles_read"] == dispatches * kl.decode_history_tiles(
        np.asarray([len(prompt), -1, -1, -1]), ENGINE_CFG.kv_block_size, ENGINE_CFG.max_blocks_per_seq)
    tiers = list(snap["attention_tiers"].values())
    assert tiers and all(t == {"tier": "dense", "interpret": False} for t in tiers)


def test_a_served_prompt_passes_its_state_once_a_chunk(engine):
    """Through the engine (``prefill_chunk`` 16): a prompt of 40 tokens is three
    chunk dispatches on the ladder [1, 4] (a row a dispatch), and each of the
    four KDA layers reads and writes its slot's state once a LANE of a
    dispatch: 40 tokens advanced to 3 passes a layer, no row handed its state
    to the row under it. On the ladder [2, 4, 16] the three pieces share a
    dispatch of four rows: ONE pass a layer and two handovers."""
    def rise(eng):
        before = eng.metrics_snapshot()
        served(eng, prompt_of(40, salt=11), 4)
        after = eng.metrics_snapshot()
        return tuple(after[k] - before[k] for k in ("kda_chunk_tokens", "kda_state_passes", "kda_state_handovers"))

    assert rise(engine) == (4 * 40, 4 * 3, 0)
    wide = JaxServingEngine(engine.model_config, engine.params, dataclasses.replace(ENGINE_CFG, max_slots=16))
    try:
        assert rise(wide) == (4 * 40, 4 * 1, 2)
        # one lane decodes among 16: the decode program walks its block of four lanes' one tile and none of
        # the three idle blocks', and the host's count says so
        snap = wide.metrics_snapshot()
        assert 0 < 4 * snap["decode_history_tiles_read"] == snap["decode_history_tiles_full"]
    finally:
        wide.close()


def test_a_reused_slot_gives_what_the_request_gives_alone(engine, cfg, params):
    """(e) Four requests fill every slot and leave their state behind; a fifth
    admitted into a used slot, beside another that still decodes, answers as
    it does alone on a new engine: the slot was zeroed on admission."""
    fresh = JaxServingEngine(cfg, params, ENGINE_CFG)
    alone = served(fresh, prompt_of(21, salt=9), 8)[0]
    fresh.close()
    before = engine.metrics_snapshot()["slot_state_resets"]
    for salt in range(4):
        submit(engine, prompt_of(30 + salt, salt=salt), 6)
    run_out(engine)
    long_one = submit(engine, prompt_of(25, salt=5), 24)
    for _ in range(4):
        step(engine)
    assert long_one.slot is not None
    late = submit(engine, prompt_of(21, salt=9), 8)
    run_out(engine)
    assert answer(late)[0] == alone
    assert engine.metrics_snapshot()["slot_state_resets"] == before + 6


def test_a_repeated_prompt_takes_no_prefix_hit(engine):
    """(h) The pages of a prompt served before are in the prefix cache; the
    state that goes with them is not, so the hit is declined, the prompt
    prefills from position 0, and the answer is the first one's."""
    prompt = prompt_of(40, salt=3)
    first = served(engine, prompt, 6)[0]
    declined, resets = engine.prefix_hits_declined, engine.model_counters["slot_state_resets"]
    seq = submit(engine, prompt, 6)
    step(engine)
    assert seq.alloc.cached_tokens == 0 and seq.alloc.declined_tokens == 32
    assert seq.prefix_declined == 32  # what the request's prefill span carries
    run_out(engine)
    assert answer(seq)[0] == first
    assert engine.prefix_hits_declined == declined + 1
    assert engine.model_counters["slot_state_resets"] == resets + 1
    assert engine.metrics_snapshot()["prefix_hits_declined"] == declined + 1


def test_what_would_hand_pages_over_without_the_state_is_refused_by_name(engine, cfg, params):
    """(h) Migration, disaggregated prefill, page transfer and the host tier
    each raise ``StateNotPortable`` (a ``MigrationRejected``) with the reason."""
    assert issubclass(StateNotPortable, MigrationRejected)
    for refused in (engine.export_migratable,
                    lambda: engine.stage_migration({"token_ids": [1, 2, 3]}, {}),
                    lambda: engine.set_remote_prefill_policy(object()),
                    lambda: engine.extract_blocks([0]),
                    lambda: engine.seed_external_prefix([1] * 8, {})):
        with pytest.raises(StateNotPortable, match="state per slot"):
            refused()
    with pytest.raises(StateNotPortable, match="the host tier"):
        JaxServingEngine(cfg, params, EngineConfig(
            max_slots=2, kv_block_size=8, max_model_len=64, host_cache_blocks=4))
    with pytest.raises(ValueError, match="one device"):
        JaxServingEngine(cfg, params, ENGINE_CFG, mesh=object())


def test_the_step_programs_carry_the_three_scopes(engine):
    """The device trace finds the mechanisms by name: ``kda``, ``mla`` and
    ``moe`` are scopes of both step programs."""
    def sd(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    s, c, mb = ENGINE_CFG.max_slots, ENGINE_CFG.prefill_chunk, ENGINE_CFG.max_blocks_per_seq
    pool = (jax.tree.map(sd, engine.params), jax.tree.map(sd, engine.cache),
            jax.tree.map(sd, engine.slot_state), sd(engine._dummy_counts))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    wd = (i32(),) if engine._watchdog else ()
    chunk = engine._build_chunk_fn(False, False, False).lower(
        *pool, i32(s, c), i32(s, c), i32(s, mb), i32(s), i32(s), i32(), i32(2, s), f32(4, s), *wd)
    decode = engine._build_decode_fn(False, False, False).lower(
        *pool, i32(s), i32(s), i32(s, mb), i32(), i32(2, s), f32(4, s), *wd)
    for program in (chunk, decode):
        # "jit(chunk)/kda/dot_general"; inside the decode loop's body "kda/dot_general"
        names = set(re.findall(r'loc\("(?:[^"]*/)?(kda|mla|moe)/', program.as_text(debug_info=True)))
        assert names == {"kda", "mla", "moe"}, names
