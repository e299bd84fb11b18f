"""Qwen3-Next (``model_type: qwen3_next``) on the served path, at a tiny size on
the CPU (hidden 64, one period of four layers: three Gated DeltaNet layers of
two key heads and four value heads of 16, one gated attention layer of four
query heads over two KV heads of 32 rotated in their first 8 channels; after
every mixer 4 held of 16 experts, 4 a token by softmax, beside a gated shared
expert).

The program (``models/qwen3_next.py``: chunked prefill through the per-slot
state and the K/V pages, then decode) is held against the benchmark's plain
reference (``benchmark/reference_qwen3_next.py``: one sequence, the whole prompt
at once, the recurrence token by token, every held expert for every token, no
cache); the expert layer against the share rule of the model-configs guide; the
engine against both, and against the refusals a model with per-slot state owes
whatever would hand its pages over without it.
"""

import dataclasses
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_qwen3_next as ref
from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
from dynamo_tpu.engine_jax.weights import config_from_card
from dynamo_tpu.kv.pages import MigrationRejected, StateNotPortable
from dynamo_tpu.models import llama, module_for
from dynamo_tpu.models import qwen3_next as qn
from dynamo_tpu.ops import moe
from dynamo_tpu.ops.pallas.kda_scan import kda_scan, kda_step

from .delta_harness import QWEN3_NEXT_SHAPE as SHAPE, louder_qwen3_next
from .step_programs import (  # noqa: F401  (highest_precision: autouse, for this file's tests)
    answer, card, chunk_program, decode_program, highest_precision, patched, prompt_of, published_shape,
    reference_program, run_out, served, step, submit,
)

# ATOL, the float32 build: float32 on the CPU at the highest matmul precision
# on both sides, so the program and the reference differ by the order of their
# sums alone (flash partials against one softmax, sorted rows of an expert
# against every expert for every token, the kernel's sums over key channels
# against the reference's): 5e-4 on logits of magnitude 4 (measured here: 4e-5
# over a prompt's chunks, 3e-4 in a decode step that follows them: the
# recurrence carries a row's rounding from token to token, which the other
# modules' 2e-4 did not have to hold). A wrong state, tail, page or rotation
# moves a logit by 1e-1 and more, and each wrong form a test below names fails
# this tolerance by a factor.
ATOL = 5e-4
# ATOL_BF16, the served build (bfloat16 weights, float32 activations, pages and
# state, every product before a router in three bfloat16 parts, the head in
# one) against the float32 reference over the same weights: all of it the
# head's rounding of its input (measured 0.007 on logits of magnitude 3.6),
# held at three times that. One swapped expert reads tenths. The benchmark's
# comparison (logprob_rms) is the tight one for this build.
ATOL_BF16 = 0.02

N_GDN, N_LAYERS = 3, 4
ENGINE_CFG = EngineConfig(max_slots=4, kv_block_size=8, max_model_len=96,
                          prefill_chunk=16, decode_steps=4, top_logprobs=5)
PUBLISHED = "benchmark/configs/qwen3-next-80b-a3b.json"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def cfg():
    return config_from_card(card(SHAPE), jnp.float32)


def seeded_params(cfg):
    return louder_qwen3_next(qn.init_params(jax.random.PRNGKey(3), cfg))


@pytest.fixture(scope="module")
def params(cfg):
    return seeded_params(cfg)


@pytest.fixture(scope="module")
def engine(cfg, params):
    eng = JaxServingEngine(cfg, params, ENGINE_CFG)
    yield eng
    eng.close()


@pytest.mark.parametrize("which", ["published", "tiny", "a_list_of_layer_types"])
def test_the_layer_kinds_follow_full_attention_interval(which, cfg, params):
    """Layer ``i`` attends where ``(i + 1) % 4 == 0``: 3 and 7 of the 8 layers
    held (3, 7, 11, ... of 48), the other 6 Gated DeltaNet; every layer has a
    router over the 512 published experts, the 128 held, and a gated shared
    expert; the pages are ``[2, N, bs, 2, 256]``. A card that lists
    ``layer_types`` is taken at its word."""
    if which == "published":
        with open(os.path.join(ROOT, PUBLISHED)) as f:
            c = config_from_card(card(json.load(f)))
        made = jax.eval_shape(lambda: qn.init_params(jax.random.PRNGKey(0), c))
        assert [i for i, k in enumerate(c.layer_types) if k == "full_attention"] == [3, 7]
        assert c.layer_types.count("linear_attention") == 6 and c.num_layers == 8
        assert (c.head_dim, c.num_heads, c.num_kv_heads, c.rotary_dim, c.rope_theta) == (256, 16, 2, 64, 1e7)
        assert (c.num_experts, c.num_experts_published, c.num_experts_per_tok) == (128, 512, 10)
        assert (c.key_dim, c.value_dim, c.conv_dim, c.vocab_size) == (2048, 4096, 8192, 37984)
        assert made["layers"][0]["w_qkvz"].shape == (2048, 12288) and made["layers"][0]["w_ba"].shape == (2048, 64)
        assert made["layers"][3]["wq"].shape == (2048, 8192) and made["layers"][3]["wk"].shape == (2048, 512)
        assert made["layers"][3]["w_gate"].shape == (128, 2048, 512) and made["layers"][0]["router"].shape == (2048, 512)
        assert jax.eval_shape(lambda: qn.make_kv_cache(c, 4, 16))["k"].shape == (2, 4, 16, 2, 256)
        state = jax.eval_shape(lambda: qn.make_slot_state(c, 64))
        assert [a.shape for a in state["s"]] == [(64, 32, 128, 128)] * 6
        assert [a.shape for a in state["conv"]] == [(64, 3 * 8192)] * 6
    elif which == "tiny":
        c, made = cfg, params
        assert c.layer_types == ("linear_attention",) * 3 + ("full_attention",)
        assert qn.make_kv_cache(c, 4, 8)["k"].shape == (1, 4, 8, 2, 32) and c.rotary_dim == 8
    else:
        kinds = ["full_attention", "linear_attention", "linear_attention", "full_attention"]
        c = config_from_card(card({**SHAPE, "layer_types": kinds}), jnp.float32)
        made = jax.eval_shape(lambda: qn.init_params(jax.random.PRNGKey(0), c))
        assert c.layer_types == tuple(kinds)
    assert qn.layer_kinds(48, 4).count("full_attention") == 12 and qn.layer_kinds(48, 4)[3] == "full_attention"
    for lp, kind in zip(made["layers"], c.layer_types):
        assert ("w_qkvz" in lp) == (kind == "linear_attention") != ("wq" in lp)
        assert {"router", "w_gate", "ws_gate", "shared_gate"} <= set(lp)
    assert module_for(c) is qn and module_for(llama.LLAMA_PRESETS["tiny"]) is llama


def prefill_then_decode(cfg, params, chunks, n_decode=3, between=None):
    """Logits ``[sum(chunks) + n_decode, V]`` of a prompt fed in ``chunks``
    into slot 2 of 4 (the chunk's second row is padding) and decoded from the
    state and pages they left; (tokens, logits, state, cache, the chunks'
    counters)."""
    n_prompt = sum(chunks)
    tokens = np.asarray(prompt_of(n_prompt + n_decode, salt=len(chunks)), np.int32)
    slots, c, bs, mb, slot = 4, 16, 8, 8, 2
    cache = qn.make_kv_cache(cfg, 32, bs)
    state = jax.tree.map(lambda a: a + 7.0, qn.make_slot_state(cfg, slots))  # stale, every slot
    tables = np.zeros((2, mb), np.int32)
    tables[0] = np.arange(1, 9)
    got, sums, at = [], [], 0
    for n in chunks:
        toks, pos = np.zeros((2, c), np.int32), np.full((2, c), -1, np.int32)
        toks[0, :n], pos[0, :n] = tokens[at:at + n], np.arange(at, at + n)
        h, cache, state, counted = chunk_program(qn, cfg)(
            params, jnp.asarray(toks), jnp.asarray(pos), cache, jnp.asarray(tables),
            state, jnp.asarray([slot, slots], jnp.int32))
        got.append(np.asarray(qn.lm_head(params, cfg, h[0, :n]), np.float32))
        sums.append(np.asarray(counted))
        at += n
        if between is not None:
            state = between(state)
    lanes_tables = np.zeros((slots, mb), np.int32)
    lanes_tables[slot] = tables[0]
    toks, pos = np.zeros((slots,), np.int32), np.full((slots,), -1, np.int32)
    toks[slot], pos[slot] = tokens[n_prompt], n_prompt

    forcing = np.zeros((slots, bs * mb), np.int32)  # teacher forcing: the sequence's own next token
    forcing[slot, :len(tokens)] = tokens
    out = decode_program(qn, cfg, n_decode, 95)(
        params, jnp.asarray(toks), jnp.asarray(pos), cache, jnp.asarray(lanes_tables), state, jnp.asarray(forcing))
    counted = dict(zip(qn.COUNTERS, np.asarray(out[6]).tolist()))
    assert int(out[1][slot]) == n_prompt + n_decode
    # a decode step advances no chunk and resets nothing; one lane routes 4 pairs a layer and step
    assert counted["gdn_chunk_tokens"] == counted["gdn_state_passes"] == counted["slot_state_resets"] == 0
    assert counted["moe_layer_calls"] == n_decode * N_LAYERS
    assert counted["moe_routed_pairs"] == 4 * n_decode * N_LAYERS >= counted["moe_held_rows"]
    got.append(np.asarray(out[3], np.float32)[:, slot])
    return tokens, np.concatenate(got), out[5], out[4], sums


def reference_of(params, tokens, shape=SHAPE):
    return np.asarray(reference_program(ref, shape)(params, jnp.asarray(tokens), jnp.arange(len(tokens))))


@pytest.mark.parametrize("dtype, atol", [(jnp.float32, ATOL), (jnp.bfloat16, ATOL_BF16)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("chunks", [(16,), (16, 16, 5), (7, 16, 14)],
                         ids=["one_chunk", "a_prompt_that_ends_mid_chunk", "a_short_first_chunk"])
def test_chunked_prefill_then_decode_agrees_with_the_plain_reference(chunks, dtype, atol):
    """A prompt fed in chunks whose boundaries lie inside it, each starting
    from the slot's state and tail and the K/V pages the last one left (keys
    rotated at their own positions, past a chunk's boundary too), then three
    decode steps off the same state, against the reference's one pass over the
    whole sequence. The other slots' state and the other pages stay as they
    were, and the first chunk alone resets the slot."""
    cfg = config_from_card(card(SHAPE), dtype)
    params = seeded_params(cfg)
    tokens, got, state, cache, sums = prefill_then_decode(cfg, params, chunks)
    np.testing.assert_allclose(got, reference_of(params, tokens), atol=atol)
    for leaf in jax.tree.leaves(state):  # slots 0, 1 and 3 of every layer: untouched
        assert float(leaf[(0, 1, 3), :].min()) == float(leaf[(0, 1, 3), :].max()) == 7.0
    # pages outside the lane's table (block 0: where a padding row's table points; 9 on)
    assert not np.asarray(cache["k"][:, 0]).any() and not np.asarray(cache["v"][:, 9:]).any()
    assert np.asarray(cache["k"][:, 1]).any()
    counted = [dict(zip(qn.COUNTERS, s.tolist())) for s in sums]
    assert [s["slot_state_resets"] for s in counted] == [1] + [0] * (len(chunks) - 1)
    # the kernel advances every DeltaNet layer's row by the chunk's valid tokens, in one pass a layer
    assert [s["gdn_chunk_tokens"] for s in counted] == [N_GDN * n for n in chunks]
    assert [s["gdn_state_passes"] for s in counted] == [N_GDN] * len(chunks)
    # every valid token routes 4 pairs in each of the 4 layers, a quarter of the experts held
    assert [s["moe_routed_pairs"] for s in counted] == [4 * N_LAYERS * n for n in chunks]
    assert all(0 < s["moe_held_rows"] < s["moe_routed_pairs"] for s in counted)
    assert [s["moe_layer_calls"] for s in counted] == [N_LAYERS] * len(chunks)


@pytest.mark.parametrize("what", ["the_routers_input", "a_plain_norm_weight", "a_rotation_of_the_whole_head",
                                  "a_softmax_after_the_choice"])
def test_a_coarser_or_wrong_program_fails_the_float32_tolerance(monkeypatch, what):
    """What ATOL is there to catch. The router's input rounded to bfloat16 (8
    bits of the normed hidden state: the probabilities move, and with them the
    weights of the chosen experts and now and then the choice); ``w`` in the
    place of ``1 + w`` in the zero-centred norms; the rotation over a head's
    whole 32 channels and not its first 8; the softmax over the chosen logits
    alone. The last is the same function as the softmax before the choice
    wherever the weights are renormalised (``norm_topk_prob``: ``p_e / sum p``
    over the chosen IS the softmax over their logits), so its case runs on a
    card that says false, where the published order gives weights that do not
    add up to one."""
    shape = {**SHAPE, "norm_topk_prob": False} if what == "a_softmax_after_the_choice" else SHAPE
    cfg = config_from_card(card(shape), jnp.float32)
    params = seeded_params(cfg)
    if what == "the_routers_input":
        route = moe.route_softmax_topk
        patched(monkeypatch, moe, "route_softmax_topk", lambda x, *a, **kw: route(
            x.astype(jnp.bfloat16).astype(jnp.float32), *a, **kw))
    elif what == "a_plain_norm_weight":
        patched(monkeypatch, qn, "_norm", qn.rms_norm)
    elif what == "a_rotation_of_the_whole_head":
        patched(monkeypatch, qn.Qwen3NextConfig, "rotary_dim", property(lambda c: c.head_dim))
    else:
        def after(x, router, top_k, renormalize):
            logits = jnp.dot(x, router, precision=jax.lax.Precision.HIGHEST)
            chosen, ids = jax.lax.top_k(logits, top_k)
            return ids.astype(jnp.int32), jax.nn.softmax(chosen, axis=-1)

        patched(monkeypatch, moe, "route_softmax_topk", after)
    tokens, got, *_ = prefill_then_decode(cfg, params, (16, 9))
    assert np.abs(got - reference_of(params, tokens, shape)).max() > 3 * ATOL


@pytest.mark.parametrize("layer", [0, 3], ids=["after_deltanet", "after_attention"])
def test_the_four_shares_add_up_to_the_uncut_references_whole_layer(layer):
    """Experts 0-3, 4-7, 8-11 and 12-15 as the four shares of a four-chip
    deployment, each chip computing the shared expert alike: the shares' parts
    of the result, the shared expert counted once, add up to what the uncut
    reference gives for the whole layer (the model-configs guide, section 4)."""
    whole = {**SHAPE, "num_experts": 16}
    cfg = config_from_card(card(whole), jnp.float32)
    lp = seeded_params(cfg)["layers"][layer]
    h = jax.random.normal(jax.random.PRNGKey(7), (2, 9, 64))
    valid = jnp.ones((2, 9), bool)
    x = (qn._norm(h, lp["ffn_norm"], cfg.rms_norm_eps)).reshape(18, 64)
    want = np.asarray(ref.expert_layer(lp, whole, x))
    shared = want - np.asarray(ref.expert_layer(lp, whole, x, shared=False))
    parts, held = [], 0
    for lo in (0, 4, 8, 12):
        mine = {**lp, **{k: lp[k][lo:lo + 4] for k in ("w_gate", "w_up", "w_down")}}
        out, stats = qn.feed_forward(mine, dataclasses.replace(cfg, num_experts=4, first_expert=lo), h, valid)
        parts.append(np.asarray(out - h).reshape(18, 64))
        held += int(stats[1])
        # the reference given the same share gives the same part
        np.testing.assert_allclose(parts[-1], np.asarray(ref.expert_layer(mine, whole, x, first_expert=lo)), atol=2e-5)
    assert held == 18 * 4  # every routed pair is some share's
    np.testing.assert_allclose(sum(parts) - 3 * shared, want, atol=5e-5)
    assert np.abs(shared).max() > 1e-2 and np.abs(parts[0] - parts[1]).max() > 1e-2


@pytest.mark.parametrize("where", ["a_chunk_boundary", "prefill_to_decode"])
def test_deltanets_state_and_tail_carry_across(cfg, params, where):
    """After a chunk of 7 tokens a DeltaNet layer's tail holds the
    convolution's inputs 4, 5 and 6 (oldest first) and its state the seven
    rank-one updates; a second chunk, or a decode step, that starts from a
    zeroed state and tail is wrong by far more than ATOL, and only from there
    on."""
    chunks = (7, 9) if where == "a_chunk_boundary" else (7,)
    tokens, got, *_ = prefill_then_decode(cfg, params, chunks)
    want = reference_of(params, tokens)
    np.testing.assert_allclose(got, want, atol=ATOL)

    seen = {}

    def zeroed(state):
        if "tail" in seen and where == "a_chunk_boundary":
            return state  # only after the first chunk
        seen.setdefault("tail", np.asarray(state["conv"][0][2]))
        seen.setdefault("s", np.asarray(state["s"][0][2]))
        return jax.tree.map(jnp.zeros_like, state)

    _, cut, *_ = prefill_then_decode(cfg, params, chunks, between=zeroed)
    assert np.abs(cut[7:] - want[7:]).max() > 100 * ATOL
    np.testing.assert_allclose(cut[:7], want[:7], atol=ATOL)
    # layer 0's convolution inputs: q | k | v of the in-projection of the normed embedding
    lp = params["layers"][0]
    u = qn._norm(params["embed"][jnp.asarray(tokens[:7])], lp["mixer_norm"], cfg.rms_norm_eps)
    pre = np.asarray(u @ lp["w_qkvz"])[:, :cfg.conv_dim]
    np.testing.assert_allclose(seen["tail"].reshape(3, cfg.conv_dim), pre[4:7], atol=1e-5)
    assert seen["s"].shape == (4, 16, 16) and np.abs(seen["s"]).max() > 1e-3


@pytest.mark.parametrize("carried", [False, True], ids=["from_zeros", "from_a_carried_state"])
def test_kda_scan_with_the_spread_decay_equals_the_token_by_token_step(carried):
    """Gated DeltaNet on Kimi's kernel: a head's ONE log-decay spread over its
    key channels, rows of 0, 1, 11 and 16 valid tokens in one call, against
    ``kda_step`` token by token with the decay ``[B, H, 1]`` (the decode
    program's form); zeros past a row's valid tokens, an empty row's state bit
    for bit."""
    b, t, h, d = 4, 16, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(11), 7)
    q, k, v = (jax.random.normal(ks[i], (b, t, h, d)) * 0.5 for i in range(3))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, t, h)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    s0 = jax.random.normal(ks[5], (b, h, d, d)) if carried else jnp.zeros((b, h, d, d))
    n = jnp.asarray([0, 1, 11, 16])
    o, s = kda_scan(q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta, s0, n, interpret=True)
    want_s, want_o = s0, []
    for i in range(t):
        new, out = kda_step(want_s, q[:, i], k[:, i], v[:, i], g[:, i, :, None], beta[:, i])
        live = (i < n)[:, None, None, None]
        want_s = jnp.where(live, new, want_s)
        want_o.append(jnp.where(live[..., 0], out, 0.0))
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(o), np.asarray(jnp.stack(want_o, axis=1)), rtol=1e-5, atol=1e-6)
    assert np.array_equal(np.asarray(s[0]), np.asarray(s0[0])) and not np.asarray(o[1, 1:]).any()


@pytest.mark.parametrize("where", ["a_chunk_row", "a_decode_lane"])
def test_an_empty_rows_state_comes_back_bit_for_bit(cfg, params, where):
    """A real slot whose row holds no valid token (a chunk row of padding
    positions; a lane that does not decode): every DeltaNet layer's state and
    tail of that slot come back as they went in, whatever the row's tokens
    are, and a slot beside it advances."""
    slots, bs, mb = 4, 8, 8
    cache = qn.make_kv_cache(cfg, 32, bs)
    state = jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(5), a.shape), qn.make_slot_state(cfg, slots))
    tables = np.zeros((slots, mb), np.int32)
    tables[1], tables[2] = np.arange(1, 9), np.arange(9, 17)
    if where == "a_chunk_row":
        toks = np.full((2, 16), 5, np.int32)
        pos = np.stack([np.full((16,), -1), np.arange(16, 32)]).astype(np.int32)
        out = chunk_program(qn, cfg)(params, jnp.asarray(toks), jnp.asarray(pos), cache,
                                     jnp.asarray(tables[1:3]), state, jnp.asarray([1, 2], jnp.int32))[2]
    else:
        pos = np.asarray([-1, -1, 20, -1], np.int32)
        out = decode_program(qn, cfg, 3, 95)(  # no forcing: a lane's own first choice is its next token
            params, jnp.full((slots,), 5, jnp.int32), jnp.asarray(pos), cache, jnp.asarray(tables), state, None)[5]
    for name in ("s", "conv"):
        for was, now in zip(state[name], out[name]):
            assert np.array_equal(np.asarray(was[(0, 1, 3), :]), np.asarray(now[(0, 1, 3), :]))
            assert not np.array_equal(np.asarray(was[2]), np.asarray(now[2]))


@pytest.mark.parametrize("rows", [8, 16], ids=["one_group", "two_groups"])
def test_a_chunk_of_more_rows_is_taken_in_groups_and_gives_each_row_what_it_gives_alone(cfg, params, rows):
    """``ROWS_AT_ONCE`` rows at once and 16 as two groups under one scan: every
    row's hidden states, pages, state and tail are what the row gives in a
    chunk of its own, and the counters are the groups' sums."""
    bs, mb, c = 8, 4, 16
    lengths = [(3 * r) % 16 + 1 for r in range(rows)]
    toks = np.zeros((rows, c), np.int32)
    pos = np.full((rows, c), -1, np.int32)
    for r, n in enumerate(lengths):
        toks[r, :n], pos[r, :n] = prompt_of(n, salt=r), np.arange(n)
    tables = 1 + np.arange(rows * mb, dtype=np.int32).reshape(rows, mb)
    cache, state = qn.make_kv_cache(cfg, 1 + rows * mb, bs), qn.make_slot_state(cfg, rows)
    h, cache, state, counted = chunk_program(qn, cfg)(
        params, jnp.asarray(toks), jnp.asarray(pos), cache, jnp.asarray(tables), state,
        jnp.arange(rows, dtype=jnp.int32))
    counted = dict(zip(qn.COUNTERS, np.asarray(counted).tolist()))
    groups = -(-rows // qn.ROWS_AT_ONCE)
    assert counted["moe_layer_calls"] == groups * N_LAYERS and counted["slot_state_resets"] == rows
    assert counted["gdn_chunk_tokens"] == N_GDN * sum(lengths) and counted["gdn_state_passes"] == N_GDN * rows
    assert counted["moe_routed_pairs"] == 4 * N_LAYERS * sum(lengths)
    for r in (0, rows - 1):
        one = chunk_program(qn, cfg)(
            params, jnp.asarray(toks[r:r + 1]), jnp.asarray(pos[r:r + 1]),
            qn.make_kv_cache(cfg, 1 + rows * mb, bs), jnp.asarray(tables[r:r + 1]),
            qn.make_slot_state(cfg, rows), jnp.asarray([r], jnp.int32))
        n = lengths[r]
        # the order of an expert's sorted rows differs with the rows beside them: float32 rounding
        np.testing.assert_allclose(np.asarray(h[r, :n]), np.asarray(one[0][0, :n]), atol=1e-4)
        for name in ("k", "v"):
            np.testing.assert_allclose(np.asarray(cache[name][:, tables[r]]),
                                       np.asarray(one[1][name][:, tables[r]]), atol=1e-4)
        for name in ("s", "conv"):
            for mine, alone in zip(state[name], one[2][name]):
                np.testing.assert_allclose(np.asarray(mine[r]), np.asarray(alone[r]), atol=1e-4)


@pytest.mark.parametrize("shape, what", [
    (SHAPE, "the_interval"),
    ({**SHAPE, "layer_types": ["linear_attention"] * 3 + ["full_attention"]}, "the_interval"),
    ({k: v for k, v in SHAPE.items() if k != "num_experts_published"}, "all_held"),
    ({**SHAPE, "mlp_only_layers": [1]}, "mlp_only_layers"),
    ({**SHAPE, "decoder_sparse_step": 2}, "decoder_sparse_step"),
    ({**SHAPE, "rope_scaling": {"rope_type": "yarn", "factor": 4.0}}, "rope_scaling"),
    ({**SHAPE, "layer_types": ["linear_attention"] * 3}, "short"),
    ({"model_type": "qwen2", "hidden_size": 64}, "llama"),
    ({"model_type": "some_moe", "num_experts": 64, "hidden_size": 64}, "impostor"),
], ids=["full_attention_interval", "layer_types", "no_published_count", "mlp_only_layers",
        "a_decoder_sparse_step", "a_rope_scaling", "too_few_layer_types", "a_qwen2_card", "another_expert_card"])
def test_config_from_card_picks_the_module_by_model_type(shape, what):
    """``model_type: qwen3_next`` is read before the refusal of an expert card
    no module runs; a non-empty ``mlp_only_layers``, a ``decoder_sparse_step``
    other than 1 and a ``rope_scaling`` are refused by name (the module runs an
    expert layer after every mixer and rotates by ``rope_theta`` alone); the
    other cards go where they went."""
    if what in ("the_interval", "all_held"):
        c = config_from_card(card(shape), jnp.float32)
        assert isinstance(c, qn.Qwen3NextConfig) and module_for(c) is qn
        assert c.layer_types == ("linear_attention",) * 3 + ("full_attention",)
        assert (c.head_dim, c.rotary_dim, c.rope_theta, c.tie_embeddings) == (32, 8, 1e7, False)
        assert (c.num_experts, c.num_experts_per_tok, c.norm_topk_prob) == (4, 4, True)
        assert c.num_experts_published == (4 if what == "all_held" else 16)
    elif what == "short":
        with pytest.raises(ValueError, match="layer_types names 3 layers"):
            config_from_card(card(shape))
    elif what == "llama":
        assert isinstance(config_from_card(card(shape)), llama.LlamaConfig)
    elif what == "impostor":
        with pytest.raises(ValueError, match="no module here runs it"):
            config_from_card(card(shape))
    else:
        with pytest.raises(ValueError, match=f"model_type 'qwen3_next' with {what}"):
            config_from_card(card(shape))


@pytest.mark.parametrize("model_type", ["qwen2", "kimi_linear", "jamba", "lfm2_moe"])
def test_serving_another_card_imports_no_qwen3_next(model_type):
    """A fifth module costs the other four's start-up nothing:
    ``config_from_card`` and ``module_for`` import a module in its own branch
    alone."""
    shape = {"model_type": "qwen2"} if model_type == "qwen2" else published_shape(model_type)
    code = (
        "import sys, types, json\n"
        "from dynamo_tpu.engine_jax.weights import config_from_card\n"
        "from dynamo_tpu.models import module_for\n"
        "import dynamo_tpu.engine_jax.engine\n"
        f"c = config_from_card(types.SimpleNamespace(model_config=json.loads({json.dumps(json.dumps(shape))})))\n"
        "print(module_for(c).__name__, 'dynamo_tpu.models.qwen3_next' in sys.modules)\n"
    )
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=110,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    want = {"qwen2": "llama", "lfm2_moe": "lfm2"}.get(model_type, model_type)
    assert done.returncode == 0, done.stdout + done.stderr[-800:]
    assert done.stdout.strip().endswith(f"dynamo_tpu.models.{want} False"), done.stdout


def test_the_engine_serves_the_reference_greedy_tokens_and_logprobs(engine, params):
    """Through ``JaxServingEngine``: admission, three chunk dispatches,
    pipelined decode dispatches of 4 steps, sampling and log-probabilities,
    the seal-time checksums over a slot model's K and V members."""
    prompt = prompt_of(37)
    toks, lps, finish = served(engine, prompt, 10, logprobs=5)
    seq = jnp.asarray(prompt + toks[:-1], jnp.int32)
    want = np.asarray(reference_program(ref, SHAPE)(params, seq, jnp.arange(len(prompt) - 1, len(seq))))
    assert toks == want.argmax(-1).tolist() and len(toks) == 10 and finish == "length"
    logp = want - np.log(np.exp(want).sum(-1, keepdims=True))
    np.testing.assert_allclose(lps, logp[np.arange(10), toks], atol=ATOL)
    snap = engine.metrics_snapshot()
    assert snap["moe_layer_calls"] > 0 and snap["gdn_chunk_tokens"] > 0 and snap["slot_state_resets"] >= 1
    assert set(qn.COUNTERS) <= set(snap) and len(qn.COUNTERS) == 10 and qn.COUNTERS[:6] == (
        "moe_layer_calls", "moe_held_rows", "moe_experts_hit", "moe_routed_pairs", "moe_rows_computed",
        "moe_expert_reads")
    assert not any(k.startswith(("ssm_", "kda_", "conv_")) for k in snap)
    # the module says what its programs read of the tables: the live part, not all
    assert 0 < snap["chunk_history_tiles_read"] <= snap["chunk_history_tiles_full"]
    # state per slot beside the pages, handed from row to row inside the kernel: a lane may fill several
    # rows of a dispatch (here the ladder is [1, 4]: no rung under the full width holds two)
    assert qn.LANE_TAKES_ROWS and qn.COUNTERS[-1] == "gdn_state_handovers"
    assert engine._lane_rows and engine._chunk_rungs == [1, 4]
    assert snap["chunk_rows_live"] == snap["chunk_lanes_fed"] > 0 and snap["gdn_state_handovers"] == 0
    assert 0 < snap["decode_history_tiles_read"] <= snap["decode_history_tiles_full"]
    assert set(engine.cache) == {"k", "v"} and engine.cache["k"].shape == (1, engine.num_blocks, 8, 2, 32)
    tiers = list(snap["attention_tiers"].values())
    assert tiers and all(t == {"tier": "dense", "interpret": False} for t in tiers)


def test_the_counters_count_what_a_served_prompt_did(engine):
    """A prompt of 40 tokens and 4 answered: three chunk dispatches of one
    group each (the ladder is [1, 4]: a row a dispatch), then the decode steps;
    every one of the four expert layers routes 4 pairs a valid token, some of
    them to the 4 experts held of 16, and every one of the three DeltaNet
    layers advances the prompt's 40 tokens in three passes of the slot's state,
    one a LANE of a dispatch, no row handing its state to the row under it. On
    the ladder [2, 4, 16] the three pieces share a dispatch of four rows: ONE
    pass a layer and two handovers."""
    before = engine.metrics_snapshot()
    served(engine, prompt_of(40, salt=11), 4)
    after = engine.metrics_snapshot()
    rise = {k: after[k] - before[k] for k in qn.COUNTERS}
    assert rise["slot_state_resets"] == 1
    assert rise["gdn_chunk_tokens"] == N_GDN * 40 and rise["gdn_state_passes"] == N_GDN * 3
    assert rise["gdn_state_handovers"] == 0
    wide = JaxServingEngine(engine.model_config, engine.params, dataclasses.replace(ENGINE_CFG, max_slots=16))
    try:
        served(wide, prompt_of(40, salt=11), 4)
        snap = wide.metrics_snapshot()
        assert (snap["gdn_chunk_tokens"], snap["gdn_state_passes"], snap["gdn_state_handovers"]) == (
            N_GDN * 40, N_GDN * 1, 2)
    finally:
        wide.close()
    # 3 chunk dispatches + the decode dispatches' 4 steps each (3 more tokens: 1 or 2 dispatches)
    steps = rise["moe_layer_calls"] // N_LAYERS - 3
    assert steps in (4, 8) and rise["moe_layer_calls"] == N_LAYERS * (3 + steps)
    # the prompt's 40 tokens, and a lane's every step until it stops (a step past its last counts too)
    assert 4 * N_LAYERS * (40 + 3) <= rise["moe_routed_pairs"] <= 4 * N_LAYERS * (40 + steps)
    assert 0 < rise["moe_held_rows"] < rise["moe_routed_pairs"]
    assert rise["moe_experts_hit"] == rise["moe_expert_reads"] <= rise["moe_held_rows"]
    assert rise["moe_rows_computed"] >= rise["moe_held_rows"]


def test_a_reused_slot_gives_what_the_request_gives_alone(engine, cfg, params):
    """Four requests fill every slot and leave their state behind; a fifth
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
    """The pages of a prompt served before are in the prefix cache; the state
    that goes with them is not, so the hit is declined, the prompt prefills
    from position 0, and the answer is the first one's."""
    prompt = prompt_of(40, salt=3)
    first = served(engine, prompt, 6)[0]
    declined, resets = engine.prefix_hits_declined, engine.model_counters["slot_state_resets"]
    seq = submit(engine, prompt, 6)
    step(engine)
    assert seq.alloc.cached_tokens == 0 and seq.alloc.declined_tokens == 32
    run_out(engine)
    assert answer(seq)[0] == first
    assert engine.prefix_hits_declined == declined + 1
    assert engine.model_counters["slot_state_resets"] == resets + 1


@pytest.mark.parametrize("what", [
    "export_migratable", "stage_migration", "set_remote_prefill_policy", "extract_blocks",
    "seed_external_prefix", "the host tier", "a mesh"])
def test_what_would_hand_pages_over_without_the_state_is_refused_by_name(engine, cfg, params, what):
    """Migration, disaggregated prefill, page transfer and the host tier each
    raise ``StateNotPortable`` (a ``MigrationRejected``) with the reason; a
    mesh is refused at construction."""
    assert issubclass(StateNotPortable, MigrationRejected)
    calls = {
        "export_migratable": engine.export_migratable,
        "stage_migration": lambda: engine.stage_migration({"token_ids": [1, 2, 3]}, {}),
        "set_remote_prefill_policy": lambda: engine.set_remote_prefill_policy(object()),
        "extract_blocks": lambda: engine.extract_blocks([0]),
        "seed_external_prefix": lambda: engine.seed_external_prefix([1] * 8, {}),
    }
    if what in calls:
        with pytest.raises(StateNotPortable, match="Qwen3NextConfig keeps state per slot"):
            calls[what]()
    elif what == "the host tier":
        with pytest.raises(StateNotPortable, match="the host tier"):
            JaxServingEngine(cfg, params, EngineConfig(
                max_slots=2, kv_block_size=8, max_model_len=64, host_cache_blocks=4))
    else:
        with pytest.raises(ValueError, match="one device"):
            JaxServingEngine(cfg, params, ENGINE_CFG, mesh=object())
        with pytest.raises(NotImplementedError, match="one device"):
            qn.param_shardings(cfg, object())


def test_the_step_programs_carry_the_three_scopes(engine):
    """The device trace finds the mechanisms by name: ``gdn``, ``attn`` and
    ``moe`` are scopes of both step programs, the shared expert under
    ``moe/shared``, a chunk's ``kda_scan`` under ``gdn`` and the expert layer's
    three grouped products under ``moe``."""
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
        text = program.as_text(debug_info=True)
        names = set(re.findall(r'loc\("(?:[^"]*/)?(gdn|attn|moe|mlp|conv|kda)/', text))
        assert names == {"gdn", "attn", "moe"}, names
        assert re.search(r'loc\("(?:[^"]*/)?moe/shared/', text)
    # an operation's name in the compiled program (what a trace's events carry) is its whole path
    compiled = chunk.compile().as_text()
    assert re.search(r'op_name="[^"]*/moe/jit\(dropless_experts\)/[^"]*grouped_product', compiled)
    assert re.search(r'op_name="[^"]*/gdn/jit\(kda_scan\)/[^"]*kda_scan', compiled)
