"""The two latent-attention modules that keep nothing per slot
(``models/openpangu.py``, ``models/xing4.py``) where a lane fills several rows
of one chunk dispatch (both set ``LANE_TAKES_ROWS``), at their own tests' tiny
sizes on the CPU in float32: the engine on ladders whose rungs under the full
width hold 8 and 16 rows (64 slots) or 2 and 4 (16 slots), every request held
against what it gets alone on an engine of one row a lane; and, because these
modules are open to what the modules with state are refused, a prefix hit in
front of a lane's rows, a preempted lane that resumes over several rows, and
the device drafter's ``following`` across a lane's rows. The flag moves the
host's rows and no program: ``lanes`` is read by no equation of either
module's chunk program, and serving compiles nothing after ``warmup``.

The model-level half (a lane's rows within and across the groups of
``_in_groups``, beside a padding row, behind a prefix hit, the prediction
module over the same rows; all against the plain references) is
``test_a_later_row_of_one_dispatch_attends_the_rows_before_it_through_the_pool``
in ``tests/test_openpangu.py`` and ``tests/test_xing4.py``."""

import dataclasses
import importlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine_jax import drafter
from dynamo_tpu.engine_jax.compile_cache import compile_count
from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
from dynamo_tpu.engine_jax.weights import config_from_card
from dynamo_tpu.models import openpangu

from .latent_harness import ATOL, OPENPANGU_SHAPE, XING4_SHAPE, C
from .step_programs import (  # noqa: F401  (highest_precision: autouse, for this file's tests)
    MIXED, answer, busy, card, highest_precision, patched, prompt_of, reference_program, run_out, served, step,
    submit,
)

SHAPES = {"openpangu": OPENPANGU_SHAPE, "xing4": XING4_SHAPE}
# ladder [8, 16, 64]: a lane fills up to sixteen rows of a dispatch
WIDE_CFG = EngineConfig(max_slots=64, kv_block_size=8, max_model_len=192, prefill_chunk=C,
                        decode_steps=4, top_logprobs=5)
# ladder [2, 4, 16]: a lane fills up to four (three programs a variant to compile, at a quarter of the rows)
MID_CFG = dataclasses.replace(WIDE_CFG, max_slots=16, max_model_len=96)


@pytest.fixture(scope="module", params=["openpangu", "xing4"])
def model(request):
    """(the module, its plain reference, the tiny shape, the config, seeded weights)."""
    shape = SHAPES[request.param]
    module = importlib.import_module(f"dynamo_tpu.models.{request.param}")
    reference = importlib.import_module(f"benchmark.reference_{request.param}")
    cfg = config_from_card(card(shape), jnp.float32)
    return types.SimpleNamespace(module=module, ref=reference, shape=shape, cfg=cfg,
                                 params=module.init_params(jax.random.PRNGKey(3), cfg))


@pytest.fixture(scope="module")
def wide(model):
    eng = JaxServingEngine(model.cfg, model.params, WIDE_CFG)
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def one(model):
    """Four slots, ladder [1, 4]: the module says a lane may take rows and no
    rung under the full width holds two, so a prompt prefills a chunk a step."""
    eng = JaxServingEngine(model.cfg, model.params, dataclasses.replace(WIDE_CFG, max_slots=4))
    yield eng
    eng.close()


def serve_schedule(engine, schedule, salt):
    """Submits ``schedule``'s requests on their steps and steps the engine
    until it rests: the sequences, in the schedule's order."""
    seqs, t = {}, 0
    while busy(engine) or len(seqs) < len(schedule):
        for i, (at, n, m) in enumerate(schedule):
            if at == t:
                seqs[i] = submit(engine, prompt_of(n, salt=salt + i), m)
        step(engine)
        t += 1
        assert t < 400
    return [seqs[i] for i in range(len(schedule))]


def rise(engine, before, *names):
    after = engine.metrics_snapshot()
    return [after[n] - before[n] for n in names]


def test_every_request_answers_as_alone_where_a_lane_fills_several_rows(model, wide, one):
    """Mixed traffic on a ladder whose rungs under the full width hold 8 and 16
    rows: most prompts prefill in one dispatch, a later piece attending the
    rows above it through the pool, and every answer (tokens and finish
    reason) is the one the request gets alone on an engine of four slots
    (ladder [1, 4]), prefilled a chunk a step."""
    assert model.module.LANE_TAKES_ROWS and wide._lane_rows and wide._chunk_rungs == [8, 16, 64]
    assert wide._own_programs and not wide._slot_model and wide.slot_state is None
    before = wide.metrics_snapshot()
    seqs = serve_schedule(wide, MIXED, salt=40)
    for i, (_, n, m) in enumerate(MIXED):
        toks, _, finish = answer(seqs[i])
        assert (toks, finish) == (served(one, prompt_of(n, salt=40 + i), m)[0], "length"), i
    alone = one.metrics_snapshot()
    assert one._lane_rows and one._chunk_rungs == [1, 4] and alone["chunk_rows_live"] == alone["chunk_lanes_fed"]
    rows, lanes, dispatches, prompts = rise(wide, before, "chunk_rows_live", "chunk_lanes_fed",
                                            "prompt_dispatches", "prompts_prefilled")
    # a row for every chunk of every prompt, whichever dispatch held it, and fewer dispatches a prompt
    assert rows == sum(-(-n // C) for _, n, _ in MIXED)
    assert prompts == len(MIXED) < dispatches < rows and rows > lanes == dispatches
    assert {8, 16} <= {int(r) for r in wide.metrics_snapshot()["chunk_dispatches_by_rows"]}
    assert wide.allocator.active_blocks == 0 and not wide._zombie_allocs


def test_a_prefix_hit_stands_in_front_of_a_lanes_rows(model, wide, one):
    """A request that shares four blocks with one served before starts its
    lane's FIRST row behind them, on pages the earlier request's dispatch
    wrote, and fills five rows of ONE dispatch from there: tokens and
    log-probabilities are those of an engine that never saw the first request
    and prefills a chunk a step, and the reference's, teacher-forced."""
    shared = prompt_of(35, salt=71)
    served(wide, shared + prompt_of(30, salt=72), 4)
    prompt = shared + prompt_of(72, salt=73)  # 32 positions hit, 75 to prefill: five rows
    before = wide.metrics_snapshot()
    toks, lps, finish = served(wide, prompt, 7, logprobs=5)
    assert rise(wide, before, "prefix_hit_tokens", "chunk_rows_live", "chunk_lanes_fed", "prompt_dispatches") == [
        32, 5, 1, 1]
    want_toks, want_lps, _ = served(one, prompt, 7, logprobs=5)
    assert (toks, finish) == (want_toks, "length")
    np.testing.assert_allclose(lps, want_lps, atol=ATOL)
    stream = np.asarray(prompt + toks, np.int32)
    logits = np.asarray(reference_program(model.ref, model.shape)(
        model.params, jnp.asarray(stream), jnp.arange(len(prompt) - 1, len(stream) - 1)))
    np.testing.assert_allclose(lps, jax.nn.log_softmax(logits)[np.arange(len(toks)), toks], atol=ATOL)


def test_a_preempted_lane_resumes_over_several_rows(model, one):
    """Out of blocks, a lane is preempted; readmitted, it recomputes its prompt
    and what it had generated in several rows of one dispatch, and both
    answers are those of an engine with room to spare and one row a lane."""
    tight = JaxServingEngine(model.cfg, model.params, dataclasses.replace(MID_CFG, num_kv_blocks=14))
    try:
        assert tight._lane_rows and tight._chunk_rungs == [2, 4, 16]
        prompts = [prompt_of(40, salt=81), prompt_of(40, salt=82)]
        seqs = [submit(tight, p, 30) for p in prompts]
        for _ in range(100):
            if tight.preemptions:
                break
            step(tight)
        before = tight.metrics_snapshot()
        assert tight.preemptions == 1 and before["prompts_prefilled"] == 2
        run_out(tight)
        # the readmitted lane alone prefills from here on: its prompt and what it had generated, 3 rows or more
        rows, lanes, prompts_done = rise(tight, before, "chunk_rows_live", "chunk_lanes_fed", "prompts_prefilled")
        assert tight.preemptions == 1 and prompts_done == 1 and rows >= 3 and rows - lanes >= 2
        for seq, prompt in zip(seqs, prompts):
            assert answer(seq)[::2] == served(one, prompt, 30)[::2]
        assert tight.allocator.active_blocks == 0 and not tight._zombie_allocs
    finally:
        tight.close()


def test_the_device_drafter_follows_a_lanes_rows(model, one, monkeypatch):
    """``spec_k`` = 1 on a ladder with 2- and 4-row rungs: the prediction
    module runs over a lane's rows with the token that FOLLOWS each position
    (``following``: a row's last position takes the next row's first token),
    so what the device offers behind the prompt, and after every later
    dispatch, is the first choice of the reference's ``draft_logits`` over the
    whole stream; the streams are those of the undrafted engine of one row a
    lane."""
    eng = JaxServingEngine(model.cfg, model.params, dataclasses.replace(MID_CFG, spec_k=1))
    try:
        assert eng._device_drafts and eng._lane_rows and eng._chunk_rungs == [2, 4, 16]
        offered = {}
        offer = drafter.DeviceDrafter.offer
        monkeypatch.setattr(drafter.DeviceDrafter, "offer", lambda self, token, at: (
            offered.setdefault(id(self), []).append((token, at)), offer(self, token, at))[1])
        prompts = [prompt_of(59, salt=91), prompt_of(23, salt=92), prompt_of(41, salt=93)]

        def submit_drafting(prompt):  # `submit` stands in for `generate`, which gives a request its drafter
            seq = submit(eng, prompt, 14)
            seq.drafter = drafter.DeviceDrafter(seq.prompt, eng._spec_k)
            return seq

        seqs = [submit_drafting(prompts[0])]
        step(eng)  # the first prompt's four rows in ONE dispatch
        snap = eng.metrics_snapshot()
        assert (snap["chunk_rows_live"], snap["chunk_lanes_fed"], snap["chunk_dispatches_by_rows"]) == (4, 1, {"4": 1})
        seqs += [submit_drafting(p) for p in prompts[1:]]  # two more beside a lane that decodes: 2 + 2 rows, then 1
        run_out(eng)
        snap = eng.metrics_snapshot()
        assert snap["spec_drafted_tokens"] > 0 and snap["mtp_layer_calls"] > 0 and eng._verify_fns
        checked = 0
        for seq, prompt in zip(seqs, prompts):
            toks, _, finish = answer(seq)
            assert (toks, finish) == (served(one, prompt, 14)[0], "length")
            stream = np.asarray(prompt + toks, np.int32)
            want = np.asarray(reference_program(model.ref, model.shape, "draft_logits")(
                model.params, jnp.asarray(stream), jnp.arange(len(stream) - 1)))
            mine = offered[id(seq.drafter)]
            assert mine[0][1] == len(prompt) + 1  # the first offer: behind the prompt's chunk rows
            for token, at in mine:  # a guess for a stream of `at` tokens: position at - 2's module output
                if at <= len(stream):
                    assert want[at - 2][token] >= want[at - 2].max() - ATOL, (token, at)
                    checked += 1
        assert checked >= 9
    finally:
        eng.close()


def test_warmup_compiles_every_rung_and_serving_compiles_nothing_more(model):
    """The ladder's chunk programs are the ones ``warmup`` compiled before the
    flag (one a rung: a module with its own programs has no history-free
    variant), and traffic whose lanes fill several rows compiles nothing."""
    eng = JaxServingEngine(model.cfg, model.params, MID_CFG)
    try:
        eng.warmup("greedy")
        assert sorted(k[4] for k in eng._chunk_fns) == [2, 4, 16] and all(k[3] for k in eng._chunk_fns)
        compiled = compile_count()
        serve_schedule(eng, [(at, min(n, 80), m) for at, n, m in MIXED[:6]], salt=60)  # 96 positions a table
        assert compile_count() == compiled
        snap = eng.metrics_snapshot()
        assert snap["chunk_rows_live"] > snap["chunk_lanes_fed"] == snap["prompt_dispatches"]
        assert {2, 4} <= {int(r) for r in snap["chunk_dispatches_by_rows"]}
    finally:
        eng.close()


@pytest.mark.parametrize("program", ["forward_chunk", "forward_chunk_then_draft_chunk"])
def test_the_rows_lanes_are_read_by_no_equation_of_the_chunk_program(model, monkeypatch, program):
    """The flag moves the host's rows and NO program: traced at a rung of one
    group and at rungs of two and four (``_in_groups``' scan), ``lanes`` is an
    operand no equation of the module's chunk program reads and no output
    returns, so the ladder's programs are the parent's to the character and
    ``setup_s`` and the compile cache are untouched. (A change that made a row
    look for its lane would read it, and this test would say so.)"""
    # groups of four rows, as the served chunk of 128 has; the programs are traced here, under the patch, and
    # kept nowhere
    patched(monkeypatch, openpangu, "TOKENS_AT_ONCE", 4 * C)
    mb = 8
    drafting = program != "forward_chunk"
    cache = jax.eval_shape(lambda: model.module.make_kv_cache(model.cfg, 1 + 2 * mb, 8, drafting=drafting))

    def chunk(lanes, tokens, positions, tables, cache):
        x, cache, state, sums = model.module.forward_chunk(
            model.params, model.cfg, tokens, positions, cache, tables, None, lanes, raw=drafting)
        if drafting:
            x, cache, more = model.module.draft_chunk(model.params, model.cfg, x, tokens, positions, cache, tables)
        return x, cache, sums

    for rows in (4, 8, 16):
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
        traced = jax.make_jaxpr(chunk)(i32(rows), i32(rows, C), i32(rows, C), i32(rows, mb), cache).jaxpr
        lanes, tokens = traced.invars[0], traced.invars[1]
        read = {id(v) for eqn in traced.eqns for v in eqn.invars} | {id(v) for v in traced.outvars}
        assert id(tokens) in read and id(lanes) not in read, rows
        assert (rows > 4) == any(eqn.primitive.name == "scan" and eqn.params["length"] == rows // 4
                                 for eqn in traced.eqns), rows
