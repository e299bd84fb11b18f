"""LFM2 (``model_type: lfm2_moe``) on the served path, at a tiny size on the CPU
(hidden 64, six layers: conv + dense, conv + dense, attention + experts, conv +
experts, attention + experts, conv + experts; eight query heads over four
key/value heads of 64, so that two KV heads share a page row of 128 and the
pool has two rows; 8 experts, 2 a token).

The program (``models/lfm2.py``: chunked prefill through the convolutions'
per-slot tails and the K/V pages, then decode) is held against the benchmark's
plain reference (``benchmark/reference_lfm2.py``: one sequence, the whole prompt
at once, every expert for every token, no cache); the engine against both, and
against the refusals a model with per-slot state owes whatever would hand its
pages over without it.
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

from benchmark import reference_lfm2 as ref
from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
from dynamo_tpu.engine_jax.weights import config_from_card
from dynamo_tpu.kv.pages import MigrationRejected, StateNotPortable
from dynamo_tpu.models import lfm2, llama, module_for
from dynamo_tpu.ops import moe

from .step_programs import (  # noqa: F401  (highest_precision: autouse, for this file's tests)
    MIXED, answer, busy, card, chunk_program, decode_program, highest_precision, patched, prompt_of, published_shape,
    reference_program, run_out, served, step, submit,
)

# ATOL, the float32 build: float32 on the CPU at the highest matmul precision
# on both sides, so the program and the reference differ by the order of their
# sums alone (flash partials against one softmax, a row of two heads against a
# head, sorted rows of an expert against every expert for every token): 2e-4 on
# logits of magnitude 4 is what the other three modules are allowed for the same
# reason (measured here: 3e-6). A wrong tail, page or rotation moves a logit by
# 1e-1 and more, and a router whose input is rounded to bfloat16 by 9e-4 without
# a single choice swapped (a test below holds that it fails this tolerance).
ATOL = 2e-4
# ATOL_BF16, the served build (bfloat16 weights, float32 activations and pages,
# every product before a router in three bfloat16 parts, the head in one: what
# the cell's readings on the chip settled, PERF.md 6, PR 43) against the float32
# reference over the same weights: measured 0.0064 on logits of magnitude 3.7,
# all of it the head's rounding of its input, held at three times that. One
# swapped expert reads 0.5 here, and the one-part build 0.9. The benchmark's
# comparison (logprob_rms) is the tight one for this build.
ATOL_BF16 = 0.02

SHAPE = {
    "model_type": "lfm2_moe", "hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 6,
    "layer_types": ["conv", "conv", "full_attention", "conv", "full_attention", "conv"],
    "num_dense_layers": 2, "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 64,
    "conv_L_cache": 3, "conv_bias": False, "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": True, "use_expert_bias": True,
    "routed_scaling_factor": 1, "norm_eps": 1e-5, "vocab_size": 96,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "max_position_embeddings": 128000,
}
N_CONV, N_EXPERT_LAYERS = 4, 4
ENGINE_CFG = EngineConfig(max_slots=4, kv_block_size=8, max_model_len=96,
                          prefill_chunk=16, decode_steps=4, top_logprobs=5)
PUBLISHED = "benchmark/configs/lfm2-24b-a2b.json"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def cfg():
    return config_from_card(card(SHAPE), jnp.float32)


def seeded_params(cfg):
    """Seeded weights, the head norms' too (ones as published would hide a
    norm that is not applied)."""
    made = lfm2.init_params(jax.random.PRNGKey(3), cfg)
    layers = []
    for i, lp in enumerate(made["layers"]):
        if "q_norm" in lp:
            k = jax.random.split(jax.random.PRNGKey(100 + i))
            lp = {**lp, "q_norm": 1.0 + 0.3 * jax.random.normal(k[0], lp["q_norm"].shape),
                  "k_norm": 1.0 + 0.3 * jax.random.normal(k[1], lp["k_norm"].shape)}
        layers.append(lp)
    return {**made, "layers": tuple(layers)}


@pytest.fixture(scope="module")
def params(cfg):
    return seeded_params(cfg)


@pytest.fixture(scope="module")
def engine(cfg, params):
    eng = JaxServingEngine(cfg, params, ENGINE_CFG)
    yield eng
    eng.close()


@pytest.mark.parametrize("which", ["published", "tiny"])
def test_the_layer_kinds_are_the_published_pattern_and_the_first_feed_forwards_dense(which, cfg, params):
    """Attention at 2 and 6 of the 10 layers held (2, 6, 10, ... of 40), the
    other 8 gated short convolutions; layers 0 and 1 (``num_dense_layers``) a
    dense feed-forward of 11,776, the other 8 a router and 64 experts of 1,536;
    two KV heads of 64 a page row of 128. The tiny shape keeps the three kinds
    of pair and two page rows."""
    if which == "published":
        with open(os.path.join(ROOT, PUBLISHED)) as f:
            c = config_from_card(card(json.load(f)))
        made = jax.eval_shape(lambda: lfm2.init_params(jax.random.PRNGKey(0), c))
        assert [i for i, k in enumerate(c.layer_types) if k == "full_attention"] == [2, 6]
        assert c.layer_types.count("conv") == 8 and c.num_layers == 10
        assert (c.head_dim, c.num_heads, c.num_kv_heads, c.heads_a_row) == (64, 32, 8, 2)
        assert (c.num_experts, c.num_experts_per_tok, c.conv_kernel, c.rope_theta) == (64, 4, 3, 1e6)
        assert made["layers"][0]["w_gate"].shape == (2048, 11776)
        assert made["layers"][2]["w_gate"].shape == (64, 2048, 1536)
        assert jax.eval_shape(lambda: lfm2.make_kv_cache(c, 4, 16))["k"].shape == (2, 4, 16, 4, 128)
    else:
        c, made = cfg, params
        assert lfm2.make_kv_cache(c, 4, 8)["k"].shape == (2, 4, 8, 2, 128)
    for i, lp in enumerate(made["layers"]):
        assert ("router" in lp) == (i >= c.num_dense_layers) == lfm2.is_expert_layer(c, i)
        assert ("w_in" in lp) == (c.layer_types[i] == "conv") != ("wq" in lp)
    assert module_for(c) is lfm2 and module_for(llama.LLAMA_PRESETS["tiny"]) is llama


def dispatch_rows(cfg, params, dispatches, rows=2, slots=4, mb=8, n_decode=3, between=None, salt=None):
    """Chunk dispatches of ``rows`` rows over ``slots`` slots, then ``n_decode``
    teacher-forced decode steps of every slot fed, off the tails and pages the
    dispatches left. A dispatch is a list of its rows in order, ``(slot, n)``
    = the slot's next ``n`` prompt tokens (a lane's rows of one dispatch are
    its successive pieces) or ``None`` = a padding row; the rows left are
    padding. The k-th slot fed has blocks ``1 + k * mb`` onwards; every slot's
    tails start stale; its tokens are ``prompt_of(., salt or the slot)``.
    Returns ({slot: (its tokens, logits ``[prompt + n_decode, V]``)}, state,
    cache, the dispatches' counters)."""
    c, bs = 16, 8
    fed = list(dict.fromkeys(row[0] for d in dispatches for row in d if row))
    length = {slot: sum(row[1] for d in dispatches for row in d if row and row[0] == slot) for slot in fed}
    toks_of = {slot: np.asarray(prompt_of(length[slot] + n_decode, salt=salt or slot), np.int32) for slot in fed}
    table = {slot: 1 + k * mb + np.arange(mb, dtype=np.int32) for k, slot in enumerate(fed)}
    cache = lfm2.make_kv_cache(cfg, 1 + len(fed) * mb, bs)
    state = jax.tree.map(lambda a: a + 7.0, lfm2.make_slot_state(cfg, slots))  # stale, every slot
    at, got, sums = dict.fromkeys(fed, 0), {slot: [] for slot in fed}, []
    chunk = chunk_program(lfm2, cfg)
    for d in dispatches:
        toks, pos = np.zeros((rows, c), np.int32), np.full((rows, c), -1, np.int32)
        tables, lanes = np.zeros((rows, mb), np.int32), np.full((rows,), slots, np.int32)
        for r, row in enumerate(d):
            if row is None:
                continue
            slot, n = row
            toks[r, :n], pos[r, :n] = toks_of[slot][at[slot]:at[slot] + n], np.arange(at[slot], at[slot] + n)
            tables[r], lanes[r] = table[slot], slot
            at[slot] += n
        h, cache, state, counted = chunk(
            params, jnp.asarray(toks), jnp.asarray(pos), cache, jnp.asarray(tables), state, jnp.asarray(lanes))
        for r, row in enumerate(d):
            if row is not None:
                got[row[0]].append(np.asarray(lfm2.lm_head(params, cfg, h[r, :row[1]]), np.float32))
        sums.append(dict(zip(lfm2.COUNTERS, np.asarray(counted).tolist())))
        if between is not None:
            state = between(state)
    if not n_decode:
        return {slot: (toks_of[slot], np.concatenate(got[slot])) for slot in fed}, state, cache, sums
    lanes_tables = np.zeros((slots, mb), np.int32)
    toks, pos = np.zeros((slots,), np.int32), np.full((slots,), -1, np.int32)
    forcing = np.zeros((slots, bs * mb), np.int32)  # a table's positions wide: one program a geometry
    for slot in fed:
        lanes_tables[slot], toks[slot], pos[slot] = table[slot], toks_of[slot][length[slot]], length[slot]
        forcing[slot, :len(toks_of[slot])] = toks_of[slot]

    out = decode_program(lfm2, cfg, n_decode, 8 * mb - 1)(  # teacher forcing: each sequence's own next token
        params, jnp.asarray(toks), jnp.asarray(pos), cache, jnp.asarray(lanes_tables), state, jnp.asarray(forcing))
    counted = dict(zip(lfm2.COUNTERS, np.asarray(out[6]).tolist()))
    assert [int(out[1][slot]) for slot in fed] == [length[slot] + n_decode for slot in fed]
    assert counted["conv_layer_calls"] == n_decode * N_CONV
    assert counted["slot_state_resets"] == counted["conv_tail_handovers"] == 0
    # the lanes that decode route 2 pairs a layer and step each, every expert held
    assert counted["moe_layer_calls"] == n_decode * N_EXPERT_LAYERS
    assert counted["moe_held_rows"] == counted["moe_routed_pairs"] == 2 * len(fed) * n_decode * N_EXPERT_LAYERS
    assert counted["moe_experts_hit"] <= counted["moe_held_rows"]  # one lane: each pair its own expert
    assert len(fed) > 1 or counted["moe_experts_hit"] == counted["moe_held_rows"]
    decoded = np.asarray(out[3], np.float32)
    return ({slot: (toks_of[slot], np.concatenate(got[slot] + [decoded[:, slot]])) for slot in fed},
            out[5], out[4], sums)


def prefill_then_decode(cfg, params, chunks, n_decode=3, between=None):
    """A prompt fed a chunk a dispatch into slot 2 of 4 (the dispatch's second
    row is padding) and decoded: (tokens, logits ``[sum(chunks) + n_decode,
    V]``, state, cache, the chunks' counters)."""
    served, state, cache, sums = dispatch_rows(
        cfg, params, [[(2, n)] for n in chunks], n_decode=n_decode, between=between, salt=len(chunks))
    return (*served[2], state, cache, sums)


def reference_of(params, tokens):
    return np.asarray(reference_program(ref, SHAPE)(params, jnp.asarray(tokens), jnp.arange(len(tokens))))


def a_chunk_a_dispatch(*chunks):
    return dict(dispatches=[[(2, n)] for n in chunks], salt=len(chunks))


# (how `dispatch_rows` is called: a dispatch is its rows, (slot, tokens) each)
LAYOUTS = {
    "one_chunk": a_chunk_a_dispatch(16),
    "a_prompt_that_ends_mid_chunk": a_chunk_a_dispatch(16, 16, 5),
    "a_short_first_chunk": a_chunk_a_dispatch(7, 16, 14),
    # a lane's successive pieces in consecutive rows of ONE dispatch, beside another lane's one
    "two_pieces_in_one_dispatch": dict(rows=8, slots=10, dispatches=[[(2, 16), (2, 5), (5, 12)]]),
    "three_pieces_in_one_dispatch": dict(rows=8, slots=10, dispatches=[[(1, 9), (2, 16), (2, 16), (2, 7)]]),
    "eight_pieces_that_fill_a_group": dict(rows=8, slots=10, mb=20, dispatches=[[(2, 16)] * 7 + [(2, 10)]]),
    # two groups of 8 rows: lane 2's eight pieces are rows 4-11, lane 1's three end before the boundary
    "eight_pieces_astride_two_groups": dict(rows=16, slots=20, mb=20, dispatches=[
        [(0, 11), (1, 16), (1, 16), (1, 3)] + [(2, 16)] * 7 + [(2, 9)] + [(7, 16), (7, 2)]]),
    # pieces behind 32 positions of the lane's own pool history, lane 3's rows 6-9 astride the groups
    "pieces_behind_pool_history": dict(rows=16, slots=20, mb=20, dispatches=[
        [(3, 16), (3, 16), (5, 16)],
        [(0, 7), (1, 16), (1, 2), (2, 16), (2, 16), (2, 1), (3, 16), (3, 16), (3, 16), (3, 6), (5, 4)]]),
}


@pytest.mark.parametrize("dtype, atol", [(jnp.float32, ATOL), (jnp.bfloat16, ATOL_BF16)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_chunked_prefill_then_decode_agrees_with_the_plain_reference(layout, dtype, atol):
    """A prompt fed in chunks whose boundaries lie inside it, each starting
    from the slot's tails and the K/V pages the last one left (keys rotated at
    their own positions, past a chunk's boundary too), then three decode steps
    off the same state, against the reference's one pass over the whole
    sequence. The chunks a dispatch each, or several in consecutive rows of
    ONE dispatch (at 8 rows, and at 16 with a lane astride the two groups): a
    later row starts from what the row above it leaves and attends its fresh
    keys, and the lane's last row leaves the slot its tail. The other slots'
    tails and the other pages stay as they were, and a sequence's first chunk
    alone resets its slot."""
    cfg = config_from_card(card(SHAPE), dtype)
    params = seeded_params(cfg)
    how = {"rows": 2, "slots": 4, "mb": 8, **LAYOUTS[layout]}
    served, state, cache, sums = dispatch_rows(cfg, params, **how)
    for slot, (tokens, got) in served.items():
        np.testing.assert_allclose(got, reference_of(params, tokens), atol=atol, err_msg=f"slot {slot}")
    idle = tuple(i for i in range(how["slots"]) if i not in served)
    for leaf in jax.tree.leaves(state):  # the slots no row fed, of every layer: untouched
        assert float(leaf[idle, :].min()) == float(leaf[idle, :].max()) == 7.0
    # pages outside the lanes' tables (block 0: where a padding row's table points)
    assert not np.asarray(cache["k"][:, 0]).any() and not np.asarray(cache["v"][:, 0]).any()
    assert np.asarray(cache["k"][:, 1]).any() and cache["k"].shape[1] == 1 + len(served) * how["mb"]
    groups, begun = -(-how["rows"] // lfm2.ROWS_AT_ONCE), set()
    for d, counted in zip(how["dispatches"], sums):
        assert counted["slot_state_resets"] == len({slot for slot, _ in d} - begun)
        begun |= {slot for slot, _ in d}
        # the rows that went on from the row above them: the dispatch's rows less its lanes
        assert counted["conv_tail_handovers"] == len(d) - len({slot for slot, _ in d})
        assert counted["conv_layer_calls"] == groups * N_CONV
        # every valid token routes 2 pairs in each of the 4 expert layers, all 8 experts held
        assert counted["moe_held_rows"] == 2 * N_EXPERT_LAYERS * sum(n for _, n in d)
        assert counted["moe_layer_calls"] == groups * N_EXPERT_LAYERS


@pytest.mark.parametrize("what", ["the_routers_input", "the_rotation", "the_head_norms"])
def test_a_coarser_or_wrong_program_fails_the_float32_tolerance(cfg, params, monkeypatch, what):
    """What ATOL is there to catch. The router's input rounded to bfloat16
    (8 bits of the normed hidden state: the scores move, and with them the
    weights of the chosen experts and now and then the choice); the positions
    past the first chunk's boundary rotated as if the prompt began there (keys
    in the pages before it as they were); q and k without their head norms.
    The router's is off by 4.6 x ATOL here (no choice swapped in these 28
    tokens: the weights alone), the other two by hundreds."""
    if what == "the_routers_input":
        route = moe.route_sigmoid_topk
        patched(monkeypatch, moe, "route_sigmoid_topk", lambda x, *a, **kw: route(
            x.astype(jnp.bfloat16).astype(jnp.float32), *a, **kw))
    elif what == "the_rotation":
        # the tails and the history say 16 tokens came before; the rotation says none did
        real = lfm2.apply_rope
        patched(monkeypatch, lfm2, "apply_rope", lambda x, pos, theta: real(
            x, jnp.where(pos >= 16, pos - 16, pos), theta))
    else:
        norm = lfm2.rms_norm
        patched(monkeypatch, lfm2, "rms_norm", lambda x, w, eps: x if x.ndim == 4 else norm(x, w, eps))
    tokens, got, *_ = prefill_then_decode(cfg, params, (16, 9))
    assert np.abs(got - reference_of(params, tokens)).max() > (3 if what == "the_routers_input" else 100) * ATOL


@pytest.mark.parametrize("where", ["a_chunk_boundary", "prefill_to_decode", "a_row_of_the_same_dispatch"])
def test_the_convolutions_tail_carries_across(cfg, params, where, monkeypatch):
    """After a chunk of 7 tokens a conv layer's tail holds its gated inputs 5
    and 6 (oldest first); a second chunk, or a decode step, that starts from a
    zeroed tail is wrong by far more than ATOL, and only from there on. A
    prompt's second piece in the row under its first (16 tokens, then 9) starts
    from the first row's gated inputs 14 and 15: from the slot's stored tail,
    as a row alone in its lane does, it is as wrong, and the tail the slot
    is left is the second row's."""
    if where == "a_row_of_the_same_dispatch":
        how = dict(dispatches=[[(2, 16), (2, 9)]], salt=2)
        (tokens, got), = dispatch_rows(cfg, params, **how)[0].values()
        want = reference_of(params, tokens)
        np.testing.assert_allclose(got, want, atol=ATOL)
        mixer = lfm2.conv_mixer
        patched(monkeypatch, lfm2, "conv_mixer", lambda *a: mixer(*a[:5]))  # nothing from the row above
        served, state, *_ = dispatch_rows(cfg, params, **how)
        cut, first, last = served[2][1], 16, 25
        monkeypatch.undo()
        tail = np.asarray(dispatch_rows(cfg, params, n_decode=0, **how)[1]["conv"][0][2])
    else:
        chunks = (7, 9) if where == "a_chunk_boundary" else (7,)
        tokens, got, *_ = prefill_then_decode(cfg, params, chunks)
        want = reference_of(params, tokens)
        np.testing.assert_allclose(got, want, atol=ATOL)

        seen = {}

        def zeroed(state):
            if "tail" in seen and where == "a_chunk_boundary":
                return state  # only after the first chunk
            seen.setdefault("tail", np.asarray(state["conv"][0][2]))
            return jax.tree.map(jnp.zeros_like, state)

        _, cut, *_ = prefill_then_decode(cfg, params, chunks, between=zeroed)
        tail, first, last = seen["tail"], 7, 7
    assert np.abs(cut[first:] - want[first:]).max() > 100 * ATOL
    np.testing.assert_allclose(cut[:first], want[:first], atol=ATOL)
    # layer 0's gated inputs: B * x of the in-projection of the normed embedding
    lp, e = params["layers"][0], cfg.hidden_size
    u = llama.rms_norm(params["embed"][jnp.asarray(tokens[:last])], lp["operator_norm"], cfg.norm_eps)
    bcx = np.asarray(u @ lp["w_in"])
    np.testing.assert_allclose(tail.reshape(2, e), (bcx[:, :e] * bcx[:, 2 * e:])[last - 2:last], atol=1e-5)


def test_a_lanes_rows_write_the_slots_tail_once_and_a_padding_row_between_lanes_changes_nothing(cfg, params):
    """Three pieces of a prompt in rows 0-2 beside another lane's one: the
    slot is left the tail of the lane's LAST row, what three dispatches of a
    row each leave it (the rows above it write nowhere: three writes of one
    slot in one scatter would leave any of them), and a padding row between
    the two lanes moves nothing of either."""
    rows = [(2, 16), (2, 16), (2, 5), (5, 9)]
    one = dispatch_rows(cfg, params, [rows], rows=8, slots=10, n_decode=0)
    apart = dispatch_rows(cfg, params, [rows[:3] + [None] + rows[3:]], rows=8, slots=10, n_decode=0)
    piecewise = dispatch_rows(cfg, params, [[(2, 16)], [(2, 16)], [(2, 5), (5, 9)]], rows=8, slots=10, n_decode=0)
    assert one[3][0]["conv_tail_handovers"] == apart[3][0]["conv_tail_handovers"] == 2
    assert [s["conv_tail_handovers"] for s in piecewise[3]] == [0, 0, 0]
    for other in (apart, piecewise):
        # the order of an expert's sorted rows differs with the rows beside them: float32 rounding
        for slot in (2, 5):
            np.testing.assert_allclose(one[0][slot][1], other[0][slot][1], atol=1e-4)
        for mine, theirs in zip(one[1]["conv"], other[1]["conv"]):
            np.testing.assert_allclose(np.asarray(mine), np.asarray(theirs), atol=1e-5)
            assert float(mine[0].min()) == float(mine[9].max()) == 7.0
        for name in ("k", "v"):
            np.testing.assert_allclose(np.asarray(one[2][name]), np.asarray(other[2][name]), atol=1e-5)


@pytest.mark.parametrize("where", ["a_chunk_row", "a_decode_lane"])
def test_an_empty_rows_state_comes_back_bit_for_bit(cfg, params, where):
    """A real slot whose row holds no valid token (a chunk row of padding
    positions; a lane that does not decode): every conv layer's tail of that
    slot comes back as it went in, whatever the row's tokens are, and a slot
    beside it advances."""
    slots, bs, mb = 4, 8, 8
    cache = lfm2.make_kv_cache(cfg, 32, bs)
    state = jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(5), a.shape), lfm2.make_slot_state(cfg, slots))
    tables = np.zeros((slots, mb), np.int32)
    tables[1], tables[2] = np.arange(1, 9), np.arange(9, 17)
    if where == "a_chunk_row":
        toks = np.full((2, 16), 5, np.int32)
        pos = np.stack([np.full((16,), -1), np.arange(16)]).astype(np.int32)
        out = chunk_program(lfm2, cfg)(params, jnp.asarray(toks), jnp.asarray(pos), cache,
                                       jnp.asarray(tables[1:3]), state, jnp.asarray([1, 2], jnp.int32))[2]
    else:
        pos = np.asarray([-1, -1, 20, -1], np.int32)
        out = decode_program(lfm2, cfg, 3, 95)(  # no forcing: a lane's own first choice is its next token
            params, jnp.full((slots,), 5, jnp.int32), jnp.asarray(pos), cache, jnp.asarray(tables), state, None)[5]
    for was, now in zip(state["conv"], out["conv"]):
        assert np.array_equal(np.asarray(was[(0, 1, 3), :]), np.asarray(now[(0, 1, 3), :]))
        assert not np.array_equal(np.asarray(was[2]), np.asarray(now[2]))


@pytest.mark.parametrize("rows", [8, 16], ids=["one_group", "two_groups"])
def test_a_chunk_of_more_rows_is_taken_in_groups_and_gives_each_row_what_it_gives_alone(cfg, params, rows):
    """``ROWS_AT_ONCE`` rows at once and 16 as two groups under one scan: every
    row's hidden states, pages and tail are what the row gives in a chunk of its
    own, and the counters are the groups' sums."""
    bs, mb, c = 8, 4, 16
    lengths = [(3 * r) % 16 + 1 for r in range(rows)]
    toks = np.zeros((rows, c), np.int32)
    pos = np.full((rows, c), -1, np.int32)
    for r, n in enumerate(lengths):
        toks[r, :n], pos[r, :n] = prompt_of(n, salt=r), np.arange(n)
    tables = 1 + np.arange(rows * mb, dtype=np.int32).reshape(rows, mb)
    cache, state = lfm2.make_kv_cache(cfg, 1 + rows * mb, bs), lfm2.make_slot_state(cfg, rows)
    h, cache, state, counted = chunk_program(lfm2, cfg)(
        params, jnp.asarray(toks), jnp.asarray(pos), cache, jnp.asarray(tables), state,
        jnp.arange(rows, dtype=jnp.int32))
    counted = dict(zip(lfm2.COUNTERS, np.asarray(counted).tolist()))
    groups = -(-rows // lfm2.ROWS_AT_ONCE)
    assert counted["moe_layer_calls"] == groups * N_EXPERT_LAYERS
    assert counted["conv_layer_calls"] == groups * N_CONV and counted["slot_state_resets"] == rows
    assert counted["moe_held_rows"] == 2 * N_EXPERT_LAYERS * sum(lengths)
    for r in (0, rows - 1):
        one = chunk_program(lfm2, cfg)(
            params, jnp.asarray(toks[r:r + 1]), jnp.asarray(pos[r:r + 1]),
            lfm2.make_kv_cache(cfg, 1 + rows * mb, bs), jnp.asarray(tables[r:r + 1]),
            lfm2.make_slot_state(cfg, rows), jnp.asarray([r], jnp.int32))
        n = lengths[r]
        # the order of an expert's sorted rows differs with the rows beside them: float32 rounding
        np.testing.assert_allclose(np.asarray(h[r, :n]), np.asarray(one[0][0, :n]), atol=1e-4)
        for name in ("k", "v"):
            np.testing.assert_allclose(np.asarray(cache[name][:, tables[r]]),
                                       np.asarray(one[1][name][:, tables[r]]), atol=1e-4)
        for mine, alone in zip(state["conv"], one[2]["conv"]):
            np.testing.assert_allclose(np.asarray(mine[r]), np.asarray(alone[r]), atol=1e-4)


@pytest.mark.parametrize("eps", [1e-20, 1e-6, 0.5])
def test_the_renormalisation_adds_the_epsilon_it_is_given(eps):
    """``ops/moe.py:route_sigmoid_topk``: a chosen expert weighs ``scale *
    score / (sum of the chosen + eps)``; the default is Kimi's 1e-20, this
    family's published code adds 1e-6, and the reference's router does too."""
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 16))
    router = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    bias = 0.02 * jax.random.normal(jax.random.PRNGKey(2), (8,))
    ids, weights = moe.route_sigmoid_topk(x, router, bias, 2, 1.0, True, eps)
    scores = np.asarray(jax.nn.sigmoid(x @ router))
    chosen = np.take_along_axis(scores, np.asarray(ids), axis=-1)
    np.testing.assert_allclose(weights, chosen / (chosen.sum(-1, keepdims=True) + eps), rtol=1e-6)
    if eps == 1e-20:
        np.testing.assert_array_equal(weights, moe.route_sigmoid_topk(x, router, bias, 2, 1.0)[1])
    if eps == lfm2.ROUTER_EPS == ref.ROUTER_EPS:
        want = ref.route({"router": router, "router_bias": bias},
                         {"num_experts_per_tok": 2, "routed_scaling_factor": 1}, x)
        np.testing.assert_allclose(want[np.arange(5)[:, None], ids], weights, rtol=1e-6)


@pytest.mark.parametrize("shape, what", [
    (SHAPE, "nested"),
    ({**{k: v for k, v in SHAPE.items() if k != "rope_parameters"}, "rope_theta": 500000.0}, "flat"),
    ({**SHAPE, "rope_theta": 10000.0}, "nested_wins"),
    ({**SHAPE, "conv_bias": True}, "refused"),
    ({**SHAPE, "layer_types": SHAPE["layer_types"][:5]}, "short"),
    ({"model_type": "qwen2", "hidden_size": 64}, "llama"),
    ({"model_type": "some_moe", "num_experts": 64, "hidden_size": 64}, "impostor"),
], ids=["rope_parameters", "a_flat_rope_theta", "both_spellings", "a_conv_bias", "too_few_layer_types",
        "a_qwen_card", "another_expert_card"])
def test_config_from_card_picks_the_module_by_model_type(shape, what):
    """``model_type: lfm2_moe`` is read before the refusal of an expert card no
    module runs, with the rotation's base from the published ``rope_parameters``
    group or, where a harness wrote only scalar and list keys, from a flat
    ``rope_theta``; the other cards go where they went."""
    if what in ("nested", "flat", "nested_wins"):
        c = config_from_card(card(shape), jnp.float32)
        assert isinstance(c, lfm2.Lfm2Config) and module_for(c) is lfm2
        assert c.rope_theta == (500000.0 if what == "flat" else 1e6)
        assert (c.head_dim, c.heads_a_row, c.conv_kernel, c.num_dense_layers, c.tie_embeddings) == (64, 2, 3, 2, True)
        assert (c.num_experts, c.num_experts_per_tok, c.norm_topk_prob, c.use_expert_bias) == (8, 2, True, True)
    elif what == "refused":
        with pytest.raises(ValueError, match="conv_bias"):
            config_from_card(card(shape))
    elif what == "short":
        with pytest.raises(ValueError, match="layer_types names 5 layers"):
            config_from_card(card(shape))
    elif what == "llama":
        assert isinstance(config_from_card(card(shape)), llama.LlamaConfig)
    else:
        with pytest.raises(ValueError, match="no module here runs it"):
            config_from_card(card(shape))


@pytest.mark.parametrize("model_type", ["qwen2", "kimi_linear", "jamba"])
def test_serving_another_card_imports_no_lfm2(model_type):
    """A fourth module costs the other three's start-up nothing (its expert
    layer embeds a Pallas kernel, and Mosaic's import is seconds of
    ``setup_s``): ``config_from_card`` and ``module_for`` import a module in
    its own branch alone."""
    shape = {"model_type": "qwen2"} if model_type == "qwen2" else published_shape(model_type)
    code = (
        "import sys, types, json\n"
        "from dynamo_tpu.engine_jax.weights import config_from_card\n"
        "from dynamo_tpu.models import module_for\n"
        "import dynamo_tpu.engine_jax.engine\n"
        f"c = config_from_card(types.SimpleNamespace(model_config=json.loads({json.dumps(json.dumps(shape))})))\n"
        "print(module_for(c).__name__, 'dynamo_tpu.models.lfm2' in sys.modules)\n"
    )
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=110,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    want = {"qwen2": "llama"}.get(model_type, model_type)
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
    assert snap["moe_layer_calls"] > 0 and snap["conv_layer_calls"] > 0 and snap["slot_state_resets"] >= 1
    assert set(lfm2.COUNTERS) <= set(snap) and len(lfm2.COUNTERS) == 9
    assert not any(k.startswith(("ssm_", "kda_")) for k in snap)
    # the module says what its programs read of the tables: the live part, not all
    assert 0 < snap["chunk_history_tiles_read"] <= snap["chunk_history_tiles_full"]
    # the module says a lane may fill several rows; this ladder, [1, 4], has no rung that holds them
    assert lfm2.LANE_TAKES_ROWS and engine._lane_rows
    assert snap["chunk_rows_live"] == snap["chunk_lanes_fed"] > 0 == snap["conv_tail_handovers"]
    assert 0 < snap["decode_history_tiles_read"] <= snap["decode_history_tiles_full"]
    assert set(engine.cache) == {"k", "v"} and engine.cache["k"].shape == (2, engine.num_blocks, 8, 2, 128)
    tiers = list(snap["attention_tiers"].values())
    assert tiers and all(t == {"tier": "dense", "interpret": False} for t in tiers)


def test_the_counters_count_what_a_served_prompt_did(engine):
    """A prompt of 40 tokens and 4 answered: three chunk dispatches of one
    group each, then the decode steps; every one of the four expert layers
    routes 2 pairs a valid token, all to experts held here, and every one of
    the four conv layers runs once a group and step."""
    before = engine.metrics_snapshot()
    served(engine, prompt_of(40, salt=11), 4)
    after = engine.metrics_snapshot()
    rise = {k: after[k] - before[k] for k in lfm2.COUNTERS}
    assert rise["slot_state_resets"] == 1
    # 3 chunk dispatches + the decode dispatches' 4 steps each (3 more tokens: 1 or 2 dispatches)
    steps = rise["conv_layer_calls"] // N_CONV - 3
    assert steps in (4, 8) and rise["conv_layer_calls"] == N_CONV * (3 + steps)
    assert rise["moe_layer_calls"] == N_EXPERT_LAYERS * (3 + steps)
    # the prompt's 40 tokens, and a lane's every step until it stops (a step past its last counts too)
    assert rise["moe_routed_pairs"] == rise["moe_held_rows"]
    assert 2 * N_EXPERT_LAYERS * (40 + 3) <= rise["moe_held_rows"] <= 2 * N_EXPERT_LAYERS * (40 + steps)
    assert rise["moe_experts_hit"] == rise["moe_expert_reads"] <= rise["moe_held_rows"]
    assert rise["moe_rows_computed"] >= rise["moe_held_rows"]


def test_a_reused_slot_gives_what_the_request_gives_alone(engine, cfg, params):
    """Four requests fill every slot and leave their tails behind; a fifth
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
    """The pages of a prompt served before are in the prefix cache; the tails
    that go with them are not, so the hit is declined, the prompt prefills
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


# ladder [8, 16, 64]: a lane fills up to sixteen rows of a dispatch, in one group of 8 or two
WIDE_CFG = EngineConfig(max_slots=64, kv_block_size=8, max_model_len=192, prefill_chunk=16,
                        decode_steps=4)

def test_every_request_answers_as_alone_where_a_lane_fills_several_rows(cfg, params):
    """Mixed traffic on a ladder whose rungs under the full width hold 8 and 16
    rows: most prompts prefill in one dispatch, a later piece starting from
    the tails the row above it leaves and attending its fresh keys inside the
    program, and every answer is the one the request gets alone on an engine
    of four slots (ladder [1, 4]), prefilled a chunk a step."""
    wide = JaxServingEngine(cfg, params, WIDE_CFG)
    one = JaxServingEngine(cfg, params, dataclasses.replace(WIDE_CFG, max_slots=4))
    try:
        seqs, t = {}, 0
        while busy(wide) or len(seqs) < len(MIXED):
            for i, (at, n, m) in enumerate(MIXED):
                if at == t:
                    seqs[i] = submit(wide, prompt_of(n, salt=40 + i), m)
            step(wide)
            t += 1
            assert t < 400
        for i, (at, n, m) in enumerate(MIXED):
            toks, _, finish = answer(seqs[i])
            assert (toks, finish) == (served(one, prompt_of(n, salt=40 + i), m)[0], "length"), i
        assert one.metrics_snapshot()["chunk_rows_live"] == one.metrics_snapshot()["chunk_lanes_fed"]
        snap = wide.metrics_snapshot()
        # a row for every chunk of every prompt, whichever dispatch held it, and fewer dispatches a prompt
        assert snap["chunk_rows_live"] == sum(-(-n // 16) for _, n, _ in MIXED)
        assert snap["prompts_prefilled"] == len(MIXED) < snap["prompt_dispatches"] < snap["chunk_rows_live"]
        assert snap["chunk_rows_live"] > snap["chunk_lanes_fed"] == snap["prompt_dispatches"]
        # every row but a lane's first of a dispatch went on from the row above it
        assert snap["conv_tail_handovers"] == snap["chunk_rows_live"] - snap["chunk_lanes_fed"]
        assert snap["slot_state_resets"] == len(MIXED)
        assert {8, 16} <= {int(r) for r in snap["chunk_dispatches_by_rows"]}
        assert wide.allocator.active_blocks == 0 and not wide._zombie_allocs
    finally:
        wide.close()
        one.close()


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
        with pytest.raises(StateNotPortable, match="Lfm2Config keeps state per slot"):
            calls[what]()
    elif what == "the host tier":
        with pytest.raises(StateNotPortable, match="the host tier"):
            JaxServingEngine(cfg, params, EngineConfig(
                max_slots=2, kv_block_size=8, max_model_len=64, host_cache_blocks=4))
    else:
        with pytest.raises(ValueError, match="one device"):
            JaxServingEngine(cfg, params, ENGINE_CFG, mesh=object())
        with pytest.raises(NotImplementedError, match="one device"):
            lfm2.param_shardings(cfg, object())


def lowered_step_programs(engine, rows=None):
    """(the chunk program at ``rows`` rows, the decode program) of the engine's
    module, lowered from shapes as the engine calls them."""
    def sd(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    s, c, mb = ENGINE_CFG.max_slots, ENGINE_CFG.prefill_chunk, ENGINE_CFG.max_blocks_per_seq
    r = s if rows is None else rows
    pool = (jax.tree.map(sd, engine.params), jax.tree.map(sd, engine.cache),
            jax.tree.map(sd, engine.slot_state), sd(engine._dummy_counts))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    wd = (i32(),) if engine._watchdog else ()
    chunk = engine._build_chunk_fn(False, False, False).lower(
        *pool, i32(r, c), i32(r, c), i32(r, mb), i32(r), i32(r), i32(), i32(2, r), f32(4, r), *wd)
    decode = engine._build_decode_fn(False, False, False).lower(
        *pool, i32(s), i32(s), i32(s, mb), i32(), i32(2, s), f32(4, s), *wd)
    return chunk, decode


def test_the_step_programs_carry_the_four_scopes(engine):
    """The device trace finds the mechanisms by name: ``conv``, ``attn``,
    ``moe`` and ``mlp`` are scopes of both step programs, and the expert
    layer's three grouped products sit under ``moe``."""
    chunk, decode = lowered_step_programs(engine)
    for program in (chunk, decode):
        text = program.as_text(debug_info=True)
        names = set(re.findall(r'loc\("(?:[^"]*/)?(conv|attn|moe|mlp)/', text))
        assert names == {"conv", "attn", "moe", "mlp"}, names
    # an operation's name in the compiled program (what a trace's events carry) is its whole path
    compiled = chunk.compile().as_text()
    assert re.search(r'op_name="[^"]*/moe/jit\(dropless_experts\)/[^"]*grouped_product', compiled)


# sha256 of the full-width chunk program's lowered text (4 rows of 16 at SHAPE, float32, as the engine
# calls it). Compared with the text the tree before a lane could take several rows lowers (PR 48's), at
# this shape, at 16 and 64 slots and at the cell's: one constant and one broadcast more, the ninth
# counter's zero, and nothing else.
FULL_WIDTH_CHUNK_SHA256 = "bfd3714991d518f4073edfc9d985c6e5fea0b6cbe6b2d72bd651d3fbfe36ddb4"


def test_the_full_width_chunk_program_holds_nothing_of_the_hand_over(engine, monkeypatch):
    """At ``rows == slots`` a lane has one row by the engine's rule, and the
    admission wave's program is what it was: none of the functions that hand
    a row what lies above it is traced there (each raises here), the text is
    the same with them gone and its hash stands. The rung under it calls
    them."""
    import hashlib

    text = lowered_step_programs(engine)[0].as_text()

    def unreachable(*a, **kw):
        raise AssertionError("the hand-over, in a program that has one row a lane")

    for name in ("chunk_layout", "_Left", "chunk_rows_above_partial"):
        patched(monkeypatch, lfm2, name, unreachable)
    assert lowered_step_programs(engine)[0].as_text() == text
    assert hashlib.sha256(text.encode()).hexdigest() == FULL_WIDTH_CHUNK_SHA256
    with pytest.raises(AssertionError, match="the hand-over"):
        lowered_step_programs(engine, rows=1)
