"""openPangu-Ultra-MoE on the served path, at a tiny size on the CPU in float32
(hidden 64, 4 heads of 16 + 8, a compressed query of 24, a latent of 32; one
dense layer and two expert layers of 8 experts top-2 holding 4, the
prediction module behind them: the structure of ``openpangu-ultra-moe-718b``
whole, every width small).

The program (``models/openpangu.py``: chunked prefill through latent pages in
the absorbed form, then decode; the prediction module beside both) is held
against the benchmark's plain reference (``benchmark/reference_openpangu.py``:
one sequence, naive attention, no cache); the expert layer against the share
rule of the model-configs guide; the engine against both, for what a module
with its own programs and NO state beside the pages is now given: a prefix
hit, ``verify`` with the module's own drafts, the host tier.
"""

import asyncio
import dataclasses
import inspect
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_openpangu as ref
from dynamo_tpu.engine_jax import drafter
from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
from dynamo_tpu.engine_jax.weights import config_from_card
from dynamo_tpu.kv import pages as kv_pages
from dynamo_tpu.kv.pages import StateNotPortable
from dynamo_tpu.models import llama, module_for
from dynamo_tpu.models import openpangu as op

from .latent_harness import ATOL, BS, LANE_ROWS, MB, C, check_lane_rows, check_lanes_decode_as_each_does_alone, feed
from .latent_harness import OPENPANGU_SHAPE as SHAPE
from .step_programs import (  # noqa: F401  (highest_precision: autouse, for this file's tests)
    answer, card, collect, decode_program, highest_precision, patched, prompt_of, reference_program, run_out, step,
    submit,
)

ENGINE_CFG = EngineConfig(max_slots=4, kv_block_size=8, max_model_len=96,
                          prefill_chunk=16, decode_steps=4, top_logprobs=5)


@pytest.fixture(scope="module")
def cfg():
    return config_from_card(card(SHAPE), jnp.float32)


@pytest.fixture(scope="module")
def params(cfg):
    """Seeded weights, the norm weights moved off one (a norm whose weight is
    dropped, or applied twice, must show)."""
    tree = op.init_params(jax.random.PRNGKey(3), cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    moved = [
        leaf * (1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(i), leaf.shape))
        if "norm" in jax.tree_util.keystr(path) else leaf
        for i, (path, leaf) in enumerate(leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, moved)


@pytest.fixture(scope="module")
def engine(cfg, params):
    eng = JaxServingEngine(cfg, params, ENGINE_CFG)
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def drafting_engine(cfg, params):
    eng = JaxServingEngine(cfg, params, dataclasses.replace(ENGINE_CFG, spec_k=1))
    yield eng
    eng.close()


def test_the_module_is_found_by_its_config_and_keeps_nothing_per_slot(cfg):
    assert module_for(cfg) is op and module_for(llama.LLAMA_PRESETS["tiny"]) is llama
    assert [op.is_expert_layer(cfg, i) for i in range(3)] == [False, True, True]
    # nothing per slot, and a lane may fill several rows of a dispatch: the rows meet through the pool
    assert not hasattr(op, "make_slot_state") and op.LANE_TAKES_ROWS is True
    # the engine's call form where a lane may: the rows' lanes behind the table's width, taken and not read
    assert list(inspect.signature(op.chunk_history_tiles).parameters)[3:] == ["lanes"]
    reach = np.asarray([[40, 41, -1], [3, 4, 5], [-1, -1, -1]])
    assert op.chunk_history_tiles(reach, BS, 16, np.asarray([2, 2, 7])) == op.chunk_history_tiles(reach, BS, 16) == 1
    assert op.COUNTERS[:6] == ("moe_layer_calls", "moe_held_rows", "moe_experts_hit",
                               "moe_routed_pairs", "moe_rows_computed", "moe_expert_reads")
    pool = op.make_kv_cache(cfg, 16, BS)
    # the row is whole registers of 128 lanes wide, the 40 held values in front
    assert list(pool) == ["latent"] and pool["latent"].shape == (3, 16, BS, 128)
    assert op.make_kv_cache(cfg, 16, BS, drafting=True)["latent"].shape[0] == 4
    with pytest.raises(NotImplementedError, match="one device"):
        op.param_shardings(cfg, None)
    with pytest.raises(ValueError, match="int8"):
        op.make_kv_cache(cfg, 16, BS, quantized=True)


@pytest.mark.parametrize("chunks", [(16, 16, 5), (7, 16, 14), (16, 9), (16,)],
                         ids=["full_chunks", "a_short_first_chunk", "two_chunks", "one_chunk"])
def test_chunked_prefill_then_decode_agrees_with_the_plain_reference(cfg, params, chunks):
    """A prompt fed in chunks whose boundaries lie inside it, each attending
    what the last ones left in the latent pages (the absorbed form over pages,
    against the reference's naive form over the whole sequence), then three
    decode steps; the prediction module's logits at every position of both."""
    n_prompt, n_decode = sum(chunks), 3
    tokens = np.asarray(prompt_of(n_prompt + n_decode + 1, salt=len(chunks)), np.int32)
    want = np.asarray(reference_program(ref, SHAPE)(params, jnp.asarray(tokens), jnp.arange(len(tokens))))
    want_draft = np.asarray(reference_program(ref, SHAPE, "draft_logits")(params, jnp.asarray(tokens),
                                             jnp.arange(len(tokens) - 1)))
    cache = op.make_kv_cache(cfg, 32, BS, drafting=True)
    table = np.arange(1, 9)
    got, got_draft, at = [], [], 0
    for n in chunks:
        logits, drafts, cache, sums = feed(op, cfg, params, cache, tokens, at, n, table,
                                           drafting=True, following=tokens[1:])
        got.append(logits), got_draft.append(drafts)
        counts = dict(zip(op.COUNTERS, sums))
        assert counts["mla_layer_calls"] == 4 and counts["mtp_layer_calls"] == 1
        assert counts["moe_layer_calls"] == 3  # two expert layers and the module's
        # one row with tokens, four layers: each attends ONE tile (this table is one), (at + n) of it history
        assert counts["mla_history_positions_read"] == 4 * MB * BS
        assert counts["mla_history_positions_live"] == 4 * (at + n)
        at += n
    np.testing.assert_allclose(np.concatenate(got), want[:n_prompt], atol=ATOL)
    np.testing.assert_allclose(np.concatenate(got_draft), want_draft[:n_prompt], atol=ATOL)

    slots, slot = 4, 2
    lanes_tables = np.zeros((slots, MB), np.int32)
    lanes_tables[slot] = table
    toks, pos = np.zeros((slots,), np.int32), np.full((slots,), -1, np.int32)
    toks[slot], pos[slot] = tokens[n_prompt], n_prompt
    forcing = np.zeros((slots, BS * MB), np.int32)  # teacher forcing: the sequence's own next token
    forcing[slot, :len(tokens)] = tokens
    out = decode_program(op, cfg, n_decode, 95, draft=True)(
        params, jnp.asarray(toks), jnp.asarray(pos), cache, jnp.asarray(lanes_tables), None, jnp.asarray(forcing))
    np.testing.assert_allclose(np.asarray(out[3])[:, slot], want[n_prompt:n_prompt + n_decode], atol=ATOL)
    assert out[5] is None and int(out[1][slot]) == n_prompt + n_decode
    # the module's first choice behind the last step's token
    assert int(out[7][slot]) == int(want_draft[n_prompt + n_decode - 1].argmax())
    counts = dict(zip(op.COUNTERS, np.asarray(out[6])))
    assert counts["mla_layer_calls"] == 4 * n_decode and counts["mtp_layer_calls"] == n_decode
    assert counts["mla_history_positions_live"] == 4 * sum(n_prompt + k + 1 for k in range(n_decode))
    # the gather reads every slot's whole table once; a step's row is scored against its block's tiles (one
    # block of four lanes, this table's one tile: the host's count) and the dispatch's steps
    assert op.decode_history_tiles(pos, BS, MB) == slots
    assert counts["mla_history_positions_read"] == 4 * (slots * MB * BS + n_decode * (MB * BS + n_decode))
    # without the module nothing of it runs, and the pool needs no page of it
    plain = decode_program(op, cfg, n_decode, 95)(
        params, jnp.asarray(toks), jnp.asarray(pos), {"latent": cache["latent"][:3]}, jnp.asarray(lanes_tables),
        None, jnp.asarray(forcing))
    assert len(plain) == 7 and int(np.asarray(plain[6])[-1]) == 0
    np.testing.assert_allclose(np.asarray(plain[3])[:, slot], np.asarray(out[3])[:, slot], atol=ATOL)


@pytest.mark.parametrize("drafting", [False, True], ids=["decode", "decode_drafting"])
def test_two_lanes_of_a_decode_dispatch_answer_as_each_does_alone(cfg, params, drafting):
    check_lanes_decode_as_each_does_alone(op, cfg, params, drafting)


def test_a_lane_that_starts_past_position_zero_is_rotated_at_its_own_positions(cfg, params):
    """The rotation: a row whose first token stands at position 16 (a prefix
    hit: two blocks another request left in the pool) rotates its queries and
    keys at 16 on and attends the cached, rotated keys before it."""
    tokens = np.asarray(prompt_of(29, salt=5), np.int32)
    want = np.asarray(reference_program(ref, SHAPE)(params, jnp.asarray(tokens), jnp.arange(29)))
    cache = op.make_kv_cache(cfg, 32, BS)
    _, _, cache, _ = feed(op, cfg, params, cache, tokens, 0, 16, np.asarray([5, 6, 0, 0, 0, 0, 0, 0]))
    # another request's table: the two cached blocks, then its own
    logits, _, cache, _ = feed(op, cfg, params, cache, tokens, 16, 13, np.asarray([5, 6, 9, 10, 0, 0, 0, 0]))
    np.testing.assert_allclose(logits, want[16:], atol=ATOL)
    # the same tokens at the wrong positions (from 0, over an empty table) are another answer
    fresh, _, _, _ = feed(op, cfg, params, op.make_kv_cache(cfg, 32, BS), tokens[16:], 0, 13, np.arange(1, 9))
    assert np.abs(np.asarray(fresh) - want[16:]).max() > 100 * ATOL


@pytest.mark.parametrize("layout", list(LANE_ROWS))
def test_a_later_row_of_one_dispatch_attends_the_rows_before_it_through_the_pool(cfg, params, monkeypatch, layout):
    """Successive pieces of ONE prompt in successive rows of ONE chunk
    dispatch, every row with the lane's block table: a layer writes all the
    rows' latents of a group to the pool before any row attends, a row reads
    its table out of the pool, and the groups run in order over one pool, so a
    later row meets the earlier rows' fresh keys there, in its own group or an
    earlier one, beside another lane's rows, across a padding row and behind
    pages an earlier dispatch or another request wrote, and answers as the
    reference does over the whole prompt; the prediction module's own layer is
    written and read by rows in the same way. (What ``LANE_TAKES_ROWS``
    rests on.)"""
    check_lane_rows(op, ref, SHAPE, cfg, params, layout, monkeypatch)


def test_a_row_that_does_not_find_its_lanes_earlier_rows_in_the_pool_is_wrong(cfg, params, monkeypatch):
    """What ``_paged``'s write in front of the read is there for: with the
    rows' latents kept out of the pool, the second piece of a prompt does not
    find the first (nor a row its own keys) and the answer is wrong by far
    more than ATOL."""
    dropped = []
    patched(monkeypatch, op, "write_latent", lambda pool, *a: (dropped.append(a), pool)[1])
    with pytest.raises(AssertionError, match="Not equal to tolerance"):
        check_lane_rows(op, ref, SHAPE, cfg, params, "two_rows", monkeypatch)
    assert dropped


def test_a_chunk_dispatch_counts_the_tiles_it_reads_and_not_the_tables_width(cfg, params):
    """A 40-token prompt alone, prefilled by ONE chunk dispatch under a table of
    512 positions (two tiles of 256): the program's own sums rise by one fed
    row x the layers x ONE tile (and by the 40 positions that held history),
    and the host counts one trip of the two that cover a table."""
    eng = JaxServingEngine(cfg, params, dataclasses.replace(ENGINE_CFG, max_model_len=512, prefill_chunk=64))
    try:
        bs, mb = ENGINE_CFG.kv_block_size, eng.config.max_blocks_per_seq
        tile = llama.history_tile(bs, mb)
        assert (tile, llama.history_tiles_full(bs, mb)) == (256, 2)
        before = eng.metrics_snapshot()
        seq = submit(eng, prompt_of(40, salt=3), 1)
        run_out(eng)
        assert len(answer(seq)[0]) == 1
        after = eng.metrics_snapshot()
        rise = {k: after[k] - before[k] for k in (
            "mla_layer_calls", "mla_history_positions_read", "mla_history_positions_live",
            "chunk_history_tiles_read", "chunk_history_tiles_full", "decode_history_tiles_full")}
        assert rise == {"mla_layer_calls": cfg.num_layers, "mla_history_positions_read": cfg.num_layers * tile,
                        "mla_history_positions_live": cfg.num_layers * 40,
                        "chunk_history_tiles_read": 1, "chunk_history_tiles_full": 2,
                        "decode_history_tiles_full": 0}
    finally:
        eng.close()


def test_the_expert_shares_add_up_to_the_uncut_reference_layer(cfg, params):
    """Experts 0-3 and 4-7 as the two shares of a 2-chip deployment (the
    cell's is 32 shares of 8): the routed parts that the shares give, with the
    shared expert counted ONCE, add up to the reference's layer over all 8."""
    lp = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 9, 64))
    valid = jnp.ones((2, 9), bool)
    key = jax.random.PRNGKey(12)
    others = {name: (jax.random.normal(jax.random.fold_in(key, i), lp[name].shape) / 8).astype(lp[name].dtype)
              for i, name in enumerate(("w_gate", "w_up", "w_down"))}
    whole = {**lp, **{n: jnp.concatenate([lp[n], others[n]]) for n in others}}
    want = ref.expert_layer(whole, SHAPE, x.reshape(18, 64))
    shared = ref.swiglu(x.reshape(18, 64), lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    first, _ = op.feed_forward(lp, cfg, x, valid)
    second, stats = op.feed_forward({**lp, **others}, dataclasses.replace(cfg, first_expert=4), x, valid)
    total = first.reshape(18, 64) + second.reshape(18, 64) - shared
    np.testing.assert_allclose(total, want, atol=ATOL)
    assert int(stats[3]) == 18 * 2  # every pair routed, whichever share holds it
    # and a share alone is NOT the layer: the absent experts' part is left out, not made up
    assert np.abs(np.asarray(first.reshape(18, 64)) - np.asarray(want)).max() > 100 * ATOL


@pytest.mark.parametrize("dropped", ["post_attn_norm", "post_mlp_norm", "pre_mlp_norm", "q_norm"])
def test_a_dropped_norm_cannot_hide(cfg, params, monkeypatch, dropped):
    """The sandwich: the reference WITHOUT one of a layer's norms (either
    post-norm, the pre-norm of the feed-forward, the compressed query's) is
    another model, further from the program than any tolerance here."""
    tokens = np.asarray(prompt_of(14, salt=2), np.int32)
    got, _, _, _ = feed(op, cfg, params, op.make_kv_cache(cfg, 32, BS), tokens, 0, 14, np.arange(1, 9))
    skipped = {id(lp[dropped]) for lp in params["layers"]}
    norm = ref._norm
    monkeypatch.setattr(ref, "_norm", lambda x, w, eps: x if id(w) in skipped else norm(x, w, eps))
    without = np.asarray(ref.logits(params, SHAPE, jnp.asarray(tokens), jnp.arange(14)))  # patched: called directly
    assert np.abs(np.asarray(got) - without).max() > 100 * ATOL


def test_bfloat16_in_float32s_place_would_fail(cfg, params):
    """The tolerance is tight enough: the reference with its activations
    rounded to bfloat16 in front of every weight product misses it."""
    tokens = np.asarray(prompt_of(14, salt=2), np.int32)
    want = np.asarray(reference_program(ref, SHAPE)(params, jnp.asarray(tokens), jnp.arange(14)))

    def coarse(x, w):
        return jnp.dot(x.astype(jnp.bfloat16).astype(jnp.float32), w.astype(jnp.float32))

    low = np.asarray(ref.logits(params, SHAPE, jnp.asarray(tokens), jnp.arange(14), dot=coarse))
    assert np.abs(low - want).max() > 10 * ATOL


# -- the engine's contract: own programs, no state beside the pages ----------------

def test_a_prefix_hit_is_served_from_latent_pages(cfg, params, engine):
    """A second request that shares a 3-block prefix prefills from the first
    uncached block, reports the hit, and answers as an engine that never saw
    the first request does."""
    assert engine._own_programs and not engine._slot_model and engine.slot_state is None
    shared = prompt_of(27, salt=1)
    first = submit(engine, shared + [9, 8, 7], 6, logprobs=5)
    run_out(engine)
    assert answer(first)[2] == "length"
    before = engine.metrics_snapshot()
    second = submit(engine, shared + [4, 5, 6, 7], 6, logprobs=5)
    run_out(engine)
    toks, lps, _ = answer(second)
    after = engine.metrics_snapshot()
    assert after["prefix_hit_tokens"] - before["prefix_hit_tokens"] == 3 * BS
    assert after["prefix_probe_tokens"] > before["prefix_probe_tokens"]
    assert after["prefix_hits_declined"] == 0
    alone = JaxServingEngine(cfg, params, ENGINE_CFG)
    try:
        cold = submit(alone, shared + [4, 5, 6, 7], 6, logprobs=5)
        run_out(alone)
        want_toks, want_lps, _ = answer(cold)
        assert alone.metrics_snapshot()["prefix_hit_tokens"] == 0
    finally:
        alone.close()
    assert toks == want_toks
    np.testing.assert_allclose(lps, want_lps, atol=ATOL)
    # and against the reference, teacher-forced over what the engine emitted
    seq = np.asarray(shared + [4, 5, 6, 7] + toks, np.int32)
    at = np.arange(30, 30 + len(toks))
    logits = np.asarray(reference_program(ref, SHAPE)(params, jnp.asarray(seq), jnp.asarray(at)))
    want = jax.nn.log_softmax(logits)[np.arange(len(toks)), toks]
    np.testing.assert_allclose(lps, want, atol=ATOL)
    counters = engine.metrics_snapshot()
    assert counters["mla_layer_calls"] > 0 and counters["mtp_layer_calls"] == 0
    assert 0 < counters["mla_history_positions_live"] < counters["mla_history_positions_read"]


def test_speculation_off_traces_no_prediction_layer_and_allocates_none_of_its_pages(cfg, engine):
    assert engine._spec_k == 0 and not engine._device_drafts and not engine._verify_fns
    assert engine.cache["latent"].shape[0] == cfg.num_layers
    lowered = engine._chunk(False, False, False, True, 4).lower(
        *jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), (engine.params, engine.cache)),
        None, jax.ShapeDtypeStruct((4, 96), jnp.int32),
        *(jax.ShapeDtypeStruct(s, jnp.int32) for s in ((4, C), (4, C), (4, MB), (4,), (4,), ())),
        jax.ShapeDtypeStruct((2, 4), jnp.int32), jax.ShapeDtypeStruct((4, 4), jnp.float32),
        *((jax.ShapeDtypeStruct((), jnp.int32),) if engine._watchdog else ()))
    assert "mtp" not in lowered.as_text(debug_info=True)


def test_the_step_programs_carry_their_scopes(engine, drafting_engine):
    """The device trace finds the mechanisms by name: ``mla``, ``mlp`` (the
    leading dense layer), ``moe`` and ``moe/shared`` are scopes of every step
    program; ``mtp`` of the ``spec_k`` > 0 variants alone (chunk, decode and
    verify), with the module's own ``mtp/mla`` and ``mtp/moe`` under it."""
    import re

    def sd(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    s, c, mb = ENGINE_CFG.max_slots, ENGINE_CFG.prefill_chunk, ENGINE_CFG.max_blocks_per_seq
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    for eng, want in ((engine, {"mla", "mlp", "moe", "moe/shared"}),
                      (drafting_engine, {"mla", "mlp", "moe", "moe/shared", "mtp", "mtp/mla", "mtp/moe",
                                         "mtp/moe/shared"})):
        pool = (jax.tree.map(sd, eng.params), jax.tree.map(sd, eng.cache))
        counts, wd = sd(eng._dummy_counts), ((i32(),) if eng._watchdog else ())
        following = (i32(s, c),) if eng._device_drafts else ()
        programs = [
            eng._build_chunk_fn(False, False, False).lower(
                *pool, None, counts, i32(s, c), i32(s, c), i32(s, mb), i32(s), i32(s), *following,
                i32(), i32(2, s), f32(4, s), *wd),
            eng._build_decode_fn(False, False, False).lower(
                *pool, None, counts, i32(s), i32(s), i32(s, mb), i32(), i32(2, s), f32(4, s), *wd),
        ]
        if eng._device_drafts:
            programs.append(eng._build_verify_fn(False, False, False).lower(
                *pool, counts, i32(s, 2), i32(s, 2), i32(s, mb), i32(), i32(2, s), f32(4, s), *wd))
        for program in programs:
            names = set(re.findall(r'loc\("(?:jit\([^"/]*\)/)*((?:mtp/)?(?:mla|mlp|moe/shared|moe)|mtp)/',
                                   program.as_text(debug_info=True)))
            assert names == want, names


@pytest.mark.parametrize("proposer", ["the_modules_own_draft", "the_models_own_next_token"])
def test_with_one_draft_the_greedy_stream_is_the_undrafted_one(cfg, params, engine, drafting_engine,
                                                              run, monkeypatch, proposer):
    """``spec_k`` = 1: the draft comes from the prediction module on the
    device (``DeviceDrafter``), ``verify`` runs through the module's own
    program, and the stream is token for token ``spec_k`` = 0's, with the
    log-probabilities it had. With a drafter that proposes the model's own
    next token every draft is accepted; with the module's own, whatever it is."""
    eng = drafting_engine
    assert eng._device_drafts and eng.cache["latent"].shape[0] == cfg.num_layers + 1
    prompt = prompt_of(21, salt=3)
    golden, golden_lps, _ = run(collect(engine, prompt, max_tokens=14, with_lp=True))
    if proposer == "the_models_own_next_token":
        stream = prompt + golden

        def oracle(self):
            return [stream[len(self)]] if len(self) < len(stream) else None

        monkeypatch.setattr(drafter.DeviceDrafter, "draft", oracle)
        monkeypatch.setattr(drafter.DeviceDrafter, "would_draft", lambda self: True)
    before = eng.metrics_snapshot()
    toks, lps, _ = run(collect(eng, prompt, max_tokens=14, with_lp=True))
    after = eng.metrics_snapshot()
    drafted = after["spec_drafted_tokens"] - before["spec_drafted_tokens"]
    accepted = after["spec_accepted_tokens"] - before["spec_accepted_tokens"]
    assert toks == golden
    np.testing.assert_allclose(lps, golden_lps, atol=ATOL)
    assert drafted > 0 and eng._verify_fns
    assert after["mtp_layer_calls"] > before["mtp_layer_calls"]
    if proposer == "the_models_own_next_token":
        assert accepted == drafted
    else:
        assert 0 <= accepted <= drafted


def test_the_engines_drafts_are_the_references_first_choices(cfg, params, drafting_engine, run, monkeypatch):
    """What the device offers after the chunk, after a decode dispatch and
    after a verify dispatch is the first choice of the reference's
    ``draft_logits`` behind the stream's last token, every time."""
    offered = []
    offer = drafter.DeviceDrafter.offer
    monkeypatch.setattr(drafter.DeviceDrafter, "offer",
                        lambda self, token, at: (offered.append((token, at)), offer(self, token, at))[1])
    prompt = prompt_of(19, salt=4)
    toks, _, _ = run(collect(drafting_engine, prompt, max_tokens=10))
    stream = np.asarray(prompt + toks, np.int32)
    want = np.asarray(reference_program(ref, SHAPE, "draft_logits")(
        params, jnp.asarray(stream), jnp.arange(len(stream) - 1)))
    assert len(offered) >= 3
    checked = 0
    for token, at in offered:  # a guess for a stream of `at` tokens: position at - 2's module output
        if at <= len(stream):
            row = want[at - 2]
            assert row[token] >= row.max() - ATOL, (token, at)
            checked += 1
    assert checked >= 3


def test_preemption_recomputes_as_for_any_model_without_state(cfg, params, run):
    """Out of blocks, a lane is preempted and recomputed: greedy output as
    with room to spare, with and without drafts."""
    tight = dataclasses.replace(ENGINE_CFG, max_slots=2, max_model_len=48, num_kv_blocks=6)

    async def both(engine):
        return await asyncio.gather(collect(engine, prompt_of(8, 1), max_tokens=18),
                                    collect(engine, prompt_of(8, 2), max_tokens=18))

    def served(config):
        eng = JaxServingEngine(cfg, params, config)
        try:
            return [r[0] for r in run(both(eng))], eng.preemptions
        finally:
            eng.close()

    golden, none = served(dataclasses.replace(tight, num_kv_blocks=None))
    assert none == 0
    for config in (tight, dataclasses.replace(tight, spec_k=1)):
        got, preemptions = served(config)
        assert preemptions > 0 and got == golden


def test_the_host_tier_takes_latent_blocks_and_gives_them_back(cfg, params, run):
    """Eviction spills a block's one ``latent`` member to the host pool and a
    re-hit puts it back: the answer is the first run's."""
    eng = JaxServingEngine(cfg, params, dataclasses.replace(
        ENGINE_CFG, max_slots=2, max_model_len=64, num_kv_blocks=8, host_cache_blocks=32))
    try:
        t1, _, _ = run(collect(eng, prompt_of(32, 1), max_tokens=4))
        run(collect(eng, prompt_of(32, 2), max_tokens=4))
        assert eng.host_pool.offloaded > 0
        block, _ = next(iter(eng.host_pool._data.values()))
        assert list(block) == ["latent"] and block["latent"].shape == (3, BS, 128)
        hits = eng.host_pool.hits
        t2, _, _ = run(collect(eng, prompt_of(32, 1), max_tokens=4))
        assert eng.host_pool.hits > hits and t2 == t1
    finally:
        eng.close()


def test_latent_pages_travel_through_frames_and_the_device_plane(cfg, params):
    """``kv/pages.py`` carries this module's pool: blocks taken out, framed
    (``pack`` / ``unpack``, with their checksums), staged (``arrays`` /
    ``from_arrays``) and put into another pool are the blocks they were; the
    frame names its member and has no ``k_bytes``, so a peer that knows only
    k and v fails on it and never injects."""
    pool = op.make_kv_cache(cfg, 16, BS, drafting=True)
    pool = {"latent": jax.random.normal(jax.random.PRNGKey(5), pool["latent"].shape)}
    taken = kv_pages.to_host(kv_pages.take(pool, [3, 7, 2]))
    assert kv_pages.members(taken) == ["latent"] and not kv_pages.is_native(taken)
    assert taken["latent"].shape == (4, 3, BS, 128) and kv_pages.count(taken) == 3
    crcs = kv_pages.checksums(taken)
    header, body = kv_pages.pack(taken, crcs)
    assert header["members"] == ["latent"] and "k_bytes" not in header and header["crcs"] == crcs
    back = kv_pages.unpack(header, body)
    kv_pages.verify(back, header["crcs"])
    np.testing.assert_array_equal(back["latent"], taken["latent"])
    staged = kv_pages.from_arrays(kv_pages.arrays(taken))
    assert list(staged) == ["latent"]
    other = kv_pages.put(op.make_kv_cache(cfg, 16, BS, drafting=True), [1, 4, 9], staged)
    np.testing.assert_array_equal(np.asarray(other["latent"][:, [1, 4, 9]]), taken["latent"])
    with pytest.raises(kv_pages.KvDtypeMismatch, match="no wire form"):
        kv_pages.pack({"latent": taken["latent"], "other": taken["latent"]})


def test_blocks_extracted_from_one_engine_serve_a_prefix_in_another(cfg, params, engine):
    """A transfer of pages out of the pool and a prefix seeded from another
    worker, which a module with state is refused: here they work, and the
    seeded engine serves the prefix from the pages it was handed and answers
    as the engine that computed them."""
    prompt = prompt_of(27, salt=6) + [1, 2, 3]
    first = submit(engine, prompt, 5, logprobs=5)
    step(engine)
    ids = list(first.alloc.block_ids[:3])
    run_out(engine)
    want, want_lps, _ = answer(first)
    pages = engine.extract_blocks(ids)
    assert list(pages) == ["latent"] and pages["latent"].shape == (3, 3, BS, 128)
    other = JaxServingEngine(cfg, params, ENGINE_CFG)
    try:
        assert other.seed_external_prefix(prompt[:24], pages) == 3
        second = submit(other, prompt, 5, logprobs=5)
        run_out(other)
        got, lps, _ = answer(second)
        assert other.metrics_snapshot()["prefix_hit_tokens"] == 3 * BS
    finally:
        other.close()
    assert got == want
    np.testing.assert_allclose(lps, want_lps, atol=ATOL)


@pytest.mark.parametrize("name", ["kimi_linear", "jamba", "lfm2", "qwen3_next"])
def test_the_modules_with_state_are_refused_what_they_were(name):
    """The split contract leaves the four modules with state beside the pages
    where they were: own programs AND ``make_slot_state``, so no prefix reuse,
    no draft, and every hand-over of pages refused by name."""
    import importlib

    module = importlib.import_module(f"dynamo_tpu.models.{name}")
    assert hasattr(module, "COUNTERS") and hasattr(module, "make_slot_state")
    stand_in = types.SimpleNamespace(_slot_model=hasattr(module, "make_slot_state"),
                                     model_config=object())
    for what in ("the host tier", "a transfer of pages out of the pool", "export_migratable"):
        with pytest.raises(StateNotPortable, match="state per slot"):
            JaxServingEngine._refuse_for_state(stand_in, what)
    JaxServingEngine._refuse_for_state(
        types.SimpleNamespace(_slot_model=hasattr(op, "make_slot_state"), model_config=object()),
        "the host tier")


# -- the card ----------------------------------------------------------------------

def test_the_published_row_maps_onto_the_modules_config():
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs",
                        "openpangu-ultra-moe-718b.json")
    with open(path) as f:
        shape = json.load(f)
    c = config_from_card(card(shape))
    assert isinstance(c, op.OpenPanguConfig) and c.dtype == jnp.bfloat16
    assert (c.hidden_size, c.num_heads, c.q_lora_rank, c.kv_lora_rank) == (7680, 128, 1536, 512)
    assert (c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim) == (128, 64, 128)
    assert (c.intermediate_size, c.moe_intermediate_size) == (18432, 2048)
    assert (c.num_experts, c.num_experts_published, c.num_experts_per_tok) == (8, 256, 8)
    assert (c.num_layers, c.first_k_dense, c.num_mtp_layers, c.vocab_size) == (5, 1, 1, 38400)
    assert c.rope_theta == 25600000.0 and c.routed_scaling_factor == 2.5 and c.moe_renormalize
    assert (c.latent_dim, c.latent_width) == (576, 640)
    # the published row itself: all 256 experts, 61 layers
    published = {**shape, "n_routed_experts": 256, "num_hidden_layers": 61,
                 "first_k_dense_replace": 3, "vocab_size": 153600}
    published.pop("n_routed_experts_published")
    whole = config_from_card(card(published))
    assert (whole.num_experts, whole.num_experts_published, whole.num_layers) == (256, 256, 61)


@pytest.mark.parametrize("key, value", [
    ("rope_scaling", {"type": "yarn", "factor": 4.0}), ("attention_bias", True),
    ("sandwich_norm", False), ("n_shared_experts", 2), ("num_nextn_predict_layers", 2),
    ("num_key_value_heads", 2),
])
def test_what_the_module_does_not_run_is_refused_by_its_name(key, value):
    with pytest.raises(ValueError, match=f"pangu_ultra_moe.*{key}"):
        config_from_card(card({**SHAPE, key: value}))
