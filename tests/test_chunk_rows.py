"""A chunk dispatch holds the rows of the lanes that prefill, a lane as many as
its prompt needs while a rung under the full width holds them
(engine_jax/engine.py ``chunk_rows_of``); a lane that decodes is never a row of
it (``_prefill_step``).

The engine is driven one host step at a time on the test's own thread
(``_admit`` + ``_dispatch_step``, what ``_step_loop`` runs; no engine thread is
started), so that each hazard is met on the step it belongs to and not by
luck: a decode dispatch in flight while a later chunk finishes a lane's
prefill, a lane joining the decode set, a lane finishing with its blocks still
written. Tiny model, float32, CPU: greedy output of every request is held
against the same request served alone."""

import dataclasses

import jax
import numpy as np
import pytest

from dynamo_tpu.engine_jax.compile_cache import compile_count
from dynamo_tpu.engine_jax.engine import JaxServingEngine, chunk_row_ladder, chunk_rows_of
from dynamo_tpu.models.llama import init_params

from .dense_harness import CFG, MIXED, mesh_engine, prompt_of, serve_schedule
from .dense_harness import CHUNK_ROWS_CFG as ENGINE_CFG
from .step_programs import answer, busy, run_out, step, submit

CHUNK = ENGINE_CFG.prefill_chunk


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def alone(params):
    """The same request served with nothing beside it, on an engine of its own
    (one for the module: its programs compile once)."""
    eng = JaxServingEngine(CFG, params, ENGINE_CFG)
    known = {}

    def serve(prompt, max_tokens, **sampling):
        key = (tuple(prompt), max_tokens, tuple(sorted(sampling.items())))
        if key not in known:
            seq = submit(eng, prompt, max_tokens, **sampling)
            run_out(eng)
            known[key] = answer(seq)
        return known[key]

    yield serve
    eng.close()


@pytest.fixture(scope="module")
def shared(params):
    eng = JaxServingEngine(CFG, params, ENGINE_CFG)
    yield eng
    eng.close()


@pytest.fixture()
def eng(shared):
    assert not busy(shared)
    yield shared
    run_out(shared)
    assert shared.allocator.active_blocks == 0 and not shared._zombie_allocs


# -- the ladder ----------------------------------------------------------------


@pytest.mark.parametrize("slots, want", [
    (1, [1]), (2, [2]), (4, [1, 4]), (16, [2, 4, 16]), (32, [4, 8, 32]),
])
def test_the_ladder_has_max_slots_and_at_most_two_rungs_under_it(slots, want):
    ladder = chunk_row_ladder(slots)
    assert ladder == want
    assert ladder == sorted(set(ladder)) and ladder[0] >= 1 and ladder[-1] == slots
    assert len(ladder) <= 3


@pytest.mark.parametrize("axes", [dict(pp=2), dict(sp=2), dict(tp=2)], ids=["pp", "sp", "tp"])
def test_an_engine_on_a_mesh_has_the_one_rung_ladder(params, axes):
    """A pipeline's stages microbatch the row axis and the sp forward has run
    at one width: on those the decode lanes still ride the chunk dispatch
    (``_rides``), so every lane can be a row: ``[max_slots]`` through the same
    host code, and nothing read ahead. A one-process tp mesh fixes no shape of
    its own and takes the ladder, the rows a lane and the read-ahead that one
    device has."""
    eng = mesh_engine(params, **axes)
    try:
        if "tp" in axes:
            assert not eng._rides
            assert eng._chunk_rungs == chunk_row_ladder(ENGINE_CFG.max_slots)
            assert eng._sealing_sizes and eng._lane_rows
        else:
            assert eng._rides and eng._chunk_rungs == [ENGINE_CFG.max_slots]
            assert eng._sealing_sizes == [] and not eng._lane_rows
    finally:
        eng.close()


def test_a_process_spanning_mesh_keeps_the_one_rung_ladder(params, monkeypatch):
    """The leader broadcasts one chunk shape and one decode shape to its
    followers: a tp mesh over two processes rides as pp and sp do."""
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    eng = mesh_engine(params, tp=2)
    try:
        assert eng._multihost and eng._rides
        assert eng._chunk_rungs == [ENGINE_CFG.max_slots]
        assert eng._sealing_sizes == [] and not eng._lane_rows
    finally:
        eng.close()


# -- the rule: which rung, and whose pieces fill it ---------------------------------


@pytest.mark.parametrize("need, waited, rungs, want", [
    # one lane: the rows its prompt needs, as far as the second rung holds them
    ([1], [0], [4, 8, 32], (4, [1])),
    ([3], [0], [4, 8, 32], (4, [3])),
    ([5], [0], [4, 8, 32], (8, [5])),
    ([8], [0], [4, 8, 32], (8, [8])),
    ([12], [0], [4, 8, 32], (8, [8])),  # the rest goes on in the next step
    # every lane its first row, the rows left to the one that waited longest
    ([3, 2], [5, 1], [4, 8, 32], (8, [3, 2])),
    ([3, 3], [5, 1], [4, 8, 32], (8, [3, 3])),
    ([8, 8, 2], [3, 1, 2], [4, 8, 32], (8, [1, 6, 1])),
    ([2, 8, 2], [3, 9, 2], [4, 8, 32], (8, [2, 4, 2])),
    ([1, 1, 1, 1], [0, 1, 2, 3], [4, 8, 32], (4, [1, 1, 1, 1])),
    ([2, 1, 1, 1], [9, 1, 2, 3], [4, 8, 32], (8, [2, 1, 1, 1])),
    ([3] * 8, list(range(8)), [4, 8, 32], (8, [1] * 8)),
    # more lanes than the second rung: the full width, and one row a lane there
    ([3] * 9, list(range(9)), [4, 8, 32], (32, [1] * 9)),
    ([8] * 32, list(range(32)), [4, 8, 32], (32, [1] * 32)),
    # the pieces never raise the rung to the full width
    ([8, 8], [0, 1], [1, 2, 8], (2, [1, 1])),
    ([5], [0], [1, 2, 8], (2, [2])),
    ([5], [0], [1, 4], (1, [1])),
    # one rung (a mesh engine's ladder, were it asked): one row a lane
    ([5, 2], [0, 1], [16], (16, [1, 1])),
], ids=lambda v: "-".join(map(str, v)) if isinstance(v, list) else None)
def test_the_rung_holds_the_lanes_and_as_many_pieces_as_the_second_rung_takes(need, waited, rungs, want):
    takes = chunk_rows_of(need, waited, rungs)
    rows = next(r for r in rungs if r >= sum(takes))  # as `_chunk_build` picks it
    assert (rows, takes) == want
    # the rung of the rule: the lanes, and of their pieces what the second rung holds
    under = max((r for r in rungs if r < rungs[-1]), default=0)
    assert rows == next(r for r in rungs if r >= max(len(need), min(sum(need), under)))
    assert all(1 <= t <= n for t, n in zip(takes, need))


@pytest.mark.parametrize("need, waited, rungs, most, want", [
    # more lanes than the second rung: the full width, and its spare rows dealt as under it
    ([3] * 5, list(range(5)), [1, 2, 8], None, (8, [3, 2, 1, 1, 1])),
    ([3] * 5, [4, 3, 2, 1, 0], [1, 2, 8], None, (8, [1, 1, 1, 2, 3])),  # oldest first, wherever it sits
    ([40] * 3, [0, 1, 2], [1, 2, 8], None, (8, [6, 1, 1])),
    ([1] * 6, list(range(6)), [1, 2, 8], None, (8, [1] * 6)),  # nothing to deal: two rows stay padding
    ([8] * 8, list(range(8)), [1, 2, 8], None, (8, [1] * 8)),  # an admission wave: no row spare
    ([2, 1, 1, 1, 2], [3, 0, 1, 2, 4], [1, 2, 8], None, (8, [2, 1, 1, 1, 2])),  # all asked for, one row left
    ([3] * 9, list(range(9)), [4, 8, 32], None, (32, [3] * 9)),
    ([40] * 9, list(range(9)), [4, 8, 32], None, (32, [24] + [1] * 8)),
    # a lane held to what its module keeps apart of one dispatch, at the full width and under it
    ([40] * 3, [0, 1, 2], [1, 2, 8], 3, (8, [3, 3, 2])),
    ([40] * 9, list(range(9)), [4, 8, 32], 16, (32, [16, 9] + [1] * 7)),
    ([12], [0], [4, 8, 32], 3, (4, [3])),
    ([8, 8, 2], [3, 1, 2], [4, 8, 32], 4, (8, [2, 4, 2])),
    ([5, 5], [0, 1], [1, 2, 8], 1, (2, [1, 1])),
    # pieces alone still never raise a dispatch to the full width
    ([8, 8], [0, 1], [1, 2, 8], None, (2, [1, 1])),
    ([40], [0], [1, 2, 8], None, (2, [2])),
    ([5], [0], [1, 4], None, (1, [1])),
    # under the full width nothing moves
    ([3, 2], [5, 1], [4, 8, 32], None, (8, [3, 2])),
    ([8, 8, 2], [3, 1, 2], [4, 8, 32], None, (8, [1, 6, 1])),
], ids=lambda v: "-".join(map(str, v)) if isinstance(v, list) else None)
def test_the_full_width_deals_its_spare_rows_where_its_program_takes_them(need, waited, rungs, most, want):
    """``top_takes`` (the module's ``FULL_WIDTH_TAKES_ROWS``): the rule of the
    rungs under the full width, at it; ``most`` (its ``lane_rows_most``) caps a
    lane at every rung. The rung is the one the rule picks without either."""
    takes = chunk_rows_of(need, waited, rungs, True, most)
    rows = next(r for r in rungs if r >= sum(takes))  # as `_chunk_build` picks it
    assert (rows, takes) == want
    capped = [min(k, most or k) for k in need]
    assert all(1 <= t <= k for t, k in zip(takes, capped)) and sum(takes) <= rows
    # the rung never raised by pieces: what the lanes alone, or the second rung's worth of pieces, ask for
    under = max((r for r in rungs if r < rungs[-1]), default=0)
    assert rows == next(r for r in rungs if r >= max(len(need), min(sum(capped), under)))
    # no spare row is left while a lane still asks for one
    assert sum(takes) == min(rows, sum(capped))
    plain = chunk_rows_of(need, waited, rungs)
    if most is None and rows < rungs[-1]:
        assert takes == plain  # the statement moves the full width alone
    if rows == rungs[-1]:
        assert plain == [1] * len(need)  # what stands without it


# -- token for token -------------------------------------------------------------

# five lanes start their prefill in one step beside one that decodes: more
# than the largest small rung (2 of 8) holds
WAVE = [(0, 9, 24, {})] + [(3, 20 + 9 * i, 8, {}) for i in range(5)]


def with_sampling(schedule, which, **sampling):
    return [(at, n, m, dict(s, **sampling) if i in which else s)
            for i, (at, n, m, s) in enumerate(schedule)]


@pytest.mark.parametrize("salt, schedule", [
    (0, MIXED),
    (10, with_sampling(MIXED, {1, 3, 4}, logprobs=0)),
    (20, with_sampling(MIXED, {0, 2}, frequency_penalty=1.5, presence_penalty=0.5)),
    (30, WAVE),
], ids=["plain", "logprobs", "a_penalised_lane", "more_lanes_prefill_than_the_small_rungs_hold"])
def test_every_request_under_mixed_traffic_answers_as_it_does_alone(eng, alone, salt, schedule):
    """``salt`` gives each case prompts of its own: no case finds another's
    prefix in the cache, so each prefills all its chunks."""
    by_rows, rows_live = dict(eng.chunk_dispatches_by_rows), eng.chunk_rows_live
    got = serve_schedule(eng, schedule, salt=salt)
    for i, (at, n, m, sampling) in enumerate(schedule):
        toks, lps, finish = got[i]
        want_toks, want_lps, _ = alone(prompt_of(n, salt + i), m, **sampling)
        assert (toks, finish) == (want_toks, "length"), i
        assert len(toks) == m
        if "logprobs" in sampling:
            assert len(lps) == m
            np.testing.assert_allclose(lps, want_lps, atol=2e-5)
    # a row for every chunk of every prompt, and none for a lane that decodes
    assert eng.chunk_rows_live - rows_live == sum(-(-n // CHUNK) for _, n, _, _ in schedule)
    if schedule is WAVE:  # five lanes at once took the top rung
        assert eng.chunk_dispatches_by_rows[ENGINE_CFG.max_slots] > by_rows.get(ENGINE_CFG.max_slots, 0)


def watch_decodes(eng):
    """Per decode dispatch from here on: did a lane prefill in that host step?"""
    seen, dispatch = [], eng._decode_dispatch
    eng._decode_dispatch = lambda **kw: seen.append(
        any(s is not None and s.prefill_pos is not None for s in eng._slots)
    ) or dispatch(**kw)
    return seen


def watch_chunks(eng):
    """(rows, lanes fed) of every chunk dispatch from here on."""
    seen, build = [], eng._chunk_build

    def watched(paced):
        rows, lanes = eng.chunk_rows_dispatched, eng.chunk_lanes_fed
        built = build(paced)
        if built is not None:
            seen.append((eng.chunk_rows_dispatched - rows, eng.chunk_lanes_fed - lanes))
        return built

    eng._chunk_build = watched
    return seen


def held_against_one_at_a_time(one, schedule, salt, got):
    """``got`` against the same requests served alone on ``one``, an engine on
    a mesh like the served one's (a sharded sum rounds as a sharded sum)."""
    for i, (at, n, m, sampling) in enumerate(schedule):
        seq = submit(one, prompt_of(n, salt + i), m, **sampling)
        run_out(one)
        want_toks, want_lps, _ = answer(seq)
        toks, lps, finish = got[i]
        assert (toks, finish) == (want_toks, "length"), i
        if "logprobs" in sampling:
            np.testing.assert_allclose(lps, want_lps, atol=2e-5)


@pytest.mark.parametrize("salt, schedule", [
    (50, MIXED),
    (60, with_sampling(MIXED, {1, 3, 4}, logprobs=0)),
    (70, with_sampling(MIXED, {0, 2}, frequency_penalty=1.5, presence_penalty=0.5)),
], ids=["plain", "logprobs", "a_penalised_lane"])
def test_on_a_mesh_the_decode_lanes_ride_the_chunk_and_answer_as_alone(params, salt, schedule):
    """``_rides`` (here sp=2; pp and a process-spanning mesh alike): a lane
    that decodes is a row of every chunk dispatch, one token forward, and the
    decode program runs in no step in which a lane prefills. Held against the
    same requests served one at a time on an sp engine of their own."""
    eng, one = mesh_engine(params, sp=2), mesh_engine(params, sp=2)
    try:
        assert eng._rides
        decodes = watch_decodes(eng)
        got = serve_schedule(eng, schedule, salt=salt)
        held_against_one_at_a_time(one, schedule, salt, got)
        assert decodes and not any(decodes)
        # and its decode program keeps every table's full width
        assert eng.decode_history_tiles_read == eng.decode_history_tiles_full > 0
        assert set(eng.chunk_dispatches_by_rows) == {ENGINE_CFG.max_slots}
        # more rows than chunks of prompt: the riders, each lane one row
        assert eng.chunk_rows_live > sum(-(-n // CHUNK) for _, n, _, _ in schedule)
        assert not eng._lane_rows and eng.chunk_rows_live == eng.chunk_lanes_fed
        assert eng.allocator.active_blocks == 0 and not eng._zombie_allocs
    finally:
        eng.close()
        one.close()


# ladder [2, 4, 16]: a lane fills up to four rows of a dispatch
WIDE_CFG = dataclasses.replace(ENGINE_CFG, max_slots=16)
# a prompt of seven chunks beside lanes that decode and a short prompt: more
# rows than the second rung holds, so its pieces go on in the next step
SPILLS = [(0, 9, 30, {}), (2, 100, 8, {}), (2, 12, 10, {}), (3, 60, 6, {})]


@pytest.fixture(scope="module")
def wide(params):
    eng = JaxServingEngine(CFG, params, WIDE_CFG)
    yield eng
    eng.close()


@pytest.mark.parametrize("salt, schedule", [
    (100, MIXED),
    (110, with_sampling(MIXED, {1, 3, 4}, logprobs=0)),
    (120, with_sampling(MIXED, {0, 2}, frequency_penalty=1.5, presence_penalty=0.5)),
    (130, WAVE),
    (140, SPILLS),
], ids=["plain", "logprobs", "a_penalised_lane", "five_lanes_at_once",
        "a_prompt_longer_than_the_second_rung_holds"])
def test_every_request_answers_as_alone_where_a_lane_fills_several_rows(wide, alone, salt, schedule):
    """The same traffic on a ladder whose second rung holds four rows: most
    prompts prefill in one dispatch, a later piece attending the earlier
    rows' fresh keys in the program, and every answer is the one the request
    gets alone, prefilled a chunk a step."""
    assert not busy(wide)
    before = wide.metrics_snapshot()
    got = serve_schedule(wide, schedule, salt=salt)
    for i, (at, n, m, sampling) in enumerate(schedule):
        toks, lps, finish = got[i]
        want_toks, want_lps, _ = alone(prompt_of(n, salt + i), m, **sampling)
        assert (toks, finish) == (want_toks, "length"), i
        if "logprobs" in sampling:
            np.testing.assert_allclose(lps, want_lps, atol=2e-5)
    rise = {k: v - before[k] for k, v in wide.metrics_snapshot().items()
            if k.startswith(("chunk_", "prompt")) and isinstance(v, int)}
    # a row for every chunk of every prompt, whichever dispatch held it
    assert rise["chunk_rows_live"] == sum(-(-n // CHUNK) for _, n, _, _ in schedule)
    assert rise["prompts_prefilled"] == len(schedule)
    assert rise["chunk_lanes_fed"] == rise["prompt_dispatches"]
    # lanes took several rows: fewer dispatches a prompt than chunks a prompt
    assert rise["chunk_rows_live"] > rise["chunk_lanes_fed"]
    if schedule is SPILLS:
        # the 100 tokens: three rows beside the 12's one, three beside the
        # 60's first, the last beside the 60's other three
        assert rise["prompt_dispatches"] == 1 + 3 + 1 + 2
        assert rise["chunk_lanes_fed"] == 7 and rise["chunk_rows_live"] == 13
    assert wide.allocator.active_blocks == 0 and not wide._zombie_allocs


@pytest.mark.parametrize("engine_cfg, salt, schedule", [
    (ENGINE_CFG, 200, MIXED),
    (ENGINE_CFG, 210, with_sampling(MIXED, {1, 3, 4}, logprobs=0)),
    (ENGINE_CFG, 220, with_sampling(MIXED, {0, 2}, frequency_penalty=1.5, presence_penalty=0.5)),
    (WIDE_CFG, 230, SPILLS),
], ids=["plain", "logprobs", "a_penalised_lane", "a_prompt_longer_than_the_second_rung_holds"])
def test_a_tp_mesh_takes_the_one_device_step_and_answers_as_alone(params, engine_cfg, salt, schedule):
    """An engine on a one-process tp mesh takes the host step one device
    takes, through the same functions and sharded: two programs in flight in
    one host step, a lane's rows attending their siblings, and the blocks a
    dispatch fills read right behind it, every member's shards assembled to
    whole blocks for the checksum. (Its decode program alone keeps the form of
    every mesh: every table's full width.) Held against the same requests
    served one at a time on a tp engine of their own."""
    from dynamo_tpu.kv import pages as kv_pages

    eng, one = mesh_engine(params, engine_cfg, tp=2), mesh_engine(params, engine_cfg, tp=2)
    try:
        assert not eng._rides and eng._seal_checksums
        decodes, chunks = watch_decodes(eng), watch_chunks(eng)
        ahead, take_sealing = [], eng._take_sealing

        def taken(filled):
            ahead.append(take_sealing(filled))
            return ahead[-1]

        eng._take_sealing = taken
        got = serve_schedule(eng, schedule, salt=salt)
        held_against_one_at_a_time(one, schedule, salt, got)
        # the decode program ran beside the chunk program (a mesh's: full width)
        assert any(decodes)
        assert eng.decode_history_tiles_read == eng.decode_history_tiles_full > 0
        # a row for every chunk of every prompt and none for a lane that
        # decodes; the full width only where more lanes prefill at once than
        # the rung under it holds (an admission wave)
        assert eng.chunk_rows_live == sum(-(-n // CHUNK) for _, n, _, _ in schedule)
        under = eng._chunk_rungs[-2]
        assert chunks and all(
            rows < engine_cfg.max_slots or lanes > under for rows, lanes in chunks
        )
        assert eng.chunk_rows_live > eng.chunk_lanes_fed
        if schedule is SPILLS:  # as on one device: the long prompt goes on in the next step
            assert eng.prompt_dispatches == 1 + 3 + 1 + 2
            assert eng.chunk_lanes_fed == 7 and eng.chunk_rows_live == 13
        # every sealed block's checksum, read ahead, is the plain read's
        # (`extract_blocks`: take, to_host)
        assert any(a is not None for a in ahead)
        sealed = dict(eng.allocator._crc_of)
        assert len(sealed) >= sum(n // engine_cfg.kv_block_size for _, n, _, _ in schedule)
        for bid, crc in sealed.items():
            assert crc == kv_pages.checksums(eng.extract_blocks([bid]))[0], bid
        assert eng.allocator.active_blocks == 0 and not eng._zombie_allocs
    finally:
        eng.close()
        one.close()


def test_a_request_cancelled_between_its_lanes_two_dispatches_moves_no_other(wide, alone):
    """Request 1's first three rows went through one dispatch; it is cancelled
    before the step that would take the next three."""
    def cancel(t, seqs):
        if t == 3:
            assert seqs[1].prefill_pos == 3 * CHUNK
            seqs[1].ctx.context.stop_generating()

    got = serve_schedule(wide, SPILLS, on_step=cancel, salt=150)
    for i, (at, n, m, sampling) in enumerate(SPILLS):
        if i == 1:
            assert got[i] == ([], [], "cancelled")
        else:
            assert got[i][0] == alone(prompt_of(n, 150 + i), m)[0], i
    assert wide.allocator.active_blocks == 0 and not wide._zombie_allocs


@pytest.mark.parametrize("which", ["a_module_that_says_nothing", "rides"])
def test_an_engine_whose_module_or_mesh_does_not_allow_it_gives_a_lane_one_row(
        params, alone, monkeypatch, which):
    """Whether a lane's rows may share a dispatch is the module's to say
    (``LANE_TAKES_ROWS``; a module that keeps state per slot says nothing),
    and an engine whose decode lanes ride the chunk keeps one row a lane."""
    from dynamo_tpu.models import llama

    if which == "rides":
        eng = mesh_engine(params, WIDE_CFG, sp=2)
    else:
        monkeypatch.delattr(llama, "LANE_TAKES_ROWS")
        eng = JaxServingEngine(CFG, params, WIDE_CFG)
    try:
        assert not eng._lane_rows
        got = serve_schedule(eng, SPILLS, salt=160)
        for i, (at, n, m, _) in enumerate(SPILLS):
            if which != "rides":  # a sharded sum rounds as a sharded sum
                assert got[i][0] == alone(prompt_of(n, 160 + i), m)[0], i
            assert len(got[i][0]) == m
        assert eng.chunk_rows_live == eng.chunk_lanes_fed
        assert eng.prompt_dispatches == sum(-(-n // CHUNK) for _, n, _, _ in SPILLS)
        assert eng.prompts_prefilled == len(SPILLS)
    finally:
        eng.close()


def test_a_request_cancelled_mid_prefill_frees_its_lane_and_moves_no_other(eng, alone):
    def cancel(t, seqs):
        # request 2 (70 tokens, five chunks) has fed two chunks by now
        if t == 4:
            assert 0 < seqs[2].prefill_pos < 70
            seqs[2].ctx.context.stop_generating()

    got = serve_schedule(eng, MIXED, on_step=cancel, salt=40)
    for i, (at, n, m, sampling) in enumerate(MIXED):
        if i == 2:
            assert got[i] == ([], [], "cancelled")
        else:
            assert got[i][0] == alone(prompt_of(n, 40 + i), m)[0], i


def test_a_preempted_lane_answers_as_it_does_alone(params, alone):
    """A pool too small for its lanes: a decode lane's growth fails, the
    victim gives its blocks back and prefills again beside the lanes that
    decode, prompt and answer so far as its prompt."""
    cfg = dataclasses.replace(ENGINE_CFG, max_slots=4, num_kv_blocks=14)
    eng = JaxServingEngine(CFG, params, cfg)
    try:
        schedule = [(0, 30, 40, {}), (0, 28, 40, {}), (1, 26, 30, {})]
        got = serve_schedule(eng, schedule)
        assert eng.preemptions >= 1
        for i, (at, n, m, _) in enumerate(schedule):
            assert got[i][0] == alone(prompt_of(n, i), m)[0], i
        assert eng.allocator.active_blocks == 0 and not eng._zombie_allocs
    finally:
        eng.close()


# -- the hazards, each on the step it belongs to -----------------------------------


def test_a_decode_dispatch_in_flight_emits_nothing_for_a_lane_that_prefilled_then(eng, alone):
    """Lane B's last chunk is dispatched while the decode dispatch of the step
    before, in which B was inert, is still in flight: by the time that one is
    processed B reads as a decode lane, and its row there is garbage."""
    a = submit(eng, prompt_of(9, 50), 40)
    step(eng)  # A prefills
    step(eng)  # A decodes: one dispatch in flight
    assert eng._inflight is not None and eng._inflight.lanes[a.slot] is a
    b = submit(eng, prompt_of(3 * CHUNK, 51), 6)
    step(eng)  # B's first two chunks (the rung of 2), beside A's decode dispatch
    assert b.prefill_pos == 2 * CHUNK
    before = eng._inflight
    assert before.lanes[b.slot] is None and before.lanes[a.slot] is a
    step(eng)  # B's last chunk; `before` is processed after it
    assert b.prefill_pos is None and eng._inflight is not before
    toks, _, _ = answer(b)
    assert toks == alone(prompt_of(3 * CHUNK, 51), 6)[0][:1]  # the first token, no garbage run
    assert eng._inflight.lanes[b.slot] is None  # still inert in the newest dispatch
    run_out(eng)
    assert answer(b)[0] == alone(prompt_of(3 * CHUNK, 51), 6)[0][1:]
    assert answer(a)[0] == alone(prompt_of(9, 50), 40)[0]


def test_a_lane_that_finishes_prefill_joins_the_decode_set_from_host_built_inputs(eng, alone):
    """The lane's ``_Seq`` is the one the in-flight dispatch saw prefilling, so
    identity says nothing changed; its carry there is (0, -1). The next
    decode dispatch drains the pipeline and builds its inputs on the host."""
    a = submit(eng, prompt_of(9, 60), 40)
    b = submit(eng, prompt_of(CHUNK + 3, 61), 9)
    step(eng)  # both prefill (A done)
    step(eng)  # B's last chunk beside A's first decode dispatch
    assert b.prefill_pos is None
    inert = eng._inflight
    assert inert.lanes[a.slot] is a and inert.lanes[b.slot] is None
    assert int(np.asarray(inert.positions)[b.slot]) == -1
    step(eng)  # B joins: host-built inputs, not that carry
    assert eng._inflight is not inert and eng._inflight.lanes[b.slot] is b
    run_out(eng)
    assert answer(b)[0] == alone(prompt_of(CHUNK + 3, 61), 9)[0]
    assert answer(a)[0] == alone(prompt_of(9, 60), 40)[0]


def test_penalty_counts_are_read_and_written_at_the_lane_not_the_row(eng, alone):
    """B prefills as row 0 of its dispatch from lane 1: its first token counts
    in row 1 of the [S, V] buffer, and lane 0's row stays as it was."""
    a = submit(eng, prompt_of(9, 70), 30)
    step(eng)
    step(eng)
    b = submit(eng, prompt_of(12, 71), 12, frequency_penalty=1.5)
    step(eng)  # B's only chunk: one row, lane 1
    assert (a.slot, b.slot) == (0, 1) and b.prefill_pos is None
    first = b.generated[0]
    counts = np.asarray(eng._counts)
    assert counts[1, first] == 1 and counts[1].sum() == 1 and counts[0].sum() == 0
    run_out(eng)
    assert answer(b)[0] == alone(prompt_of(12, 71), 12, frequency_penalty=1.5)[0]
    assert answer(a)[0] == alone(prompt_of(9, 70), 30)[0]


def test_a_finished_lanes_blocks_wait_for_the_dispatch_in_flight_when_a_chunk_goes_next(eng):
    """A reaches its length while a decode dispatch that still writes its
    blocks is in flight: they are parked. The next step admits C into the
    lane and dispatches a chunk first; the blocks stay parked (and out of C's
    allocation) until the decode dispatch has been fetched."""
    a = submit(eng, prompt_of(9, 80), 1 + ENGINE_CFG.decode_steps)
    keep = submit(eng, prompt_of(11, 82), 40)  # keeps the pipeline going
    step(eng)  # both prefill: the first token each
    step(eng)  # decode dispatch 1
    step(eng)  # decode dispatch 2, then 1 processed: A is done, parked
    assert a.slot is None and len(eng._zombie_allocs) == 1
    parked = set(eng._zombie_allocs[0].block_ids)
    assert eng._inflight is not None and eng._inflight.lanes[0] is a
    c = submit(eng, prompt_of(20, 83), 3)
    seen = {}
    dispatch = eng._decode_dispatch

    def watched():
        # the chunk has been dispatched by now, the decode dispatch not yet drained
        seen["parked"] = [set(z.block_ids) for z in eng._zombie_allocs]
        seen["chunks"] = sum(eng.chunk_dispatches_by_rows.values())
        return dispatch()

    eng._decode_dispatch = watched
    chunks = sum(eng.chunk_dispatches_by_rows.values())
    try:
        step(eng)
    finally:
        del eng._decode_dispatch
    assert seen == {"parked": [parked], "chunks": chunks + 1}
    assert c.slot == 0 and not parked & set(c.alloc.block_ids)
    assert not eng._zombie_allocs  # freed once the dispatch was fetched
    run_out(eng)
    assert len(answer(keep)[0]) == 40


def test_a_request_that_arrives_while_a_stale_carry_is_read_prefills_behind_that_read(eng, alone):
    """A lane leaves the decode set and none prefills: the next decode
    dispatch is built on the host after what is in flight has been read. A
    request that arrives during that read (the caller whose stream just ended
    sends its next) is admitted behind it and its chunk goes in that host
    step, beside the decode dispatch, not a dispatch later."""
    a = submit(eng, prompt_of(9, 84), 1 + ENGINE_CFG.decode_steps)
    keep = submit(eng, prompt_of(11, 85), 40)
    step(eng)  # both prefill
    step(eng)  # decode dispatch 1
    step(eng)  # decode dispatch 2, then 1 processed: A is done
    assert a.slot is None and eng._inflight is not None and eng._carry_is_stale()
    arrived, drain = [], eng._drain_inflight

    def read():
        drain()
        if not arrived:  # the request lands while the engine thread waits
            arrived.append(submit(eng, prompt_of(12, 86), 6))

    eng._drain_inflight = read
    chunks = sum(eng.chunk_dispatches_by_rows.values())
    try:
        step(eng)
    finally:
        del eng._drain_inflight
    (c,) = arrived
    assert c.slot == 0 and c.prefill_pos is None and len(c.generated) == 1
    assert sum(eng.chunk_dispatches_by_rows.values()) == chunks + 1
    assert eng._inflight is not None and eng._inflight.lanes[keep.slot] is keep
    run_out(eng)
    assert answer(c)[0] == alone(prompt_of(12, 86), 6)[0]
    assert answer(keep)[0] == alone(prompt_of(11, 85), 40)[0]


def test_a_host_step_reads_its_results_in_the_devices_order(eng):
    """The decode dispatch a prefill step displaces ran before that step's
    chunk: its tokens go out, and a lane that ends in it frees its slot,
    before the chunk is waited for."""
    keep = submit(eng, prompt_of(9, 87), 40)
    step(eng)
    step(eng)  # a decode dispatch is in flight
    before = eng._inflight
    submit(eng, prompt_of(20, 88), 3)
    order, process, finish = [], eng._process_chunk, eng._chunk_finish
    eng._process_chunk = lambda chunk, **kw: (order.append(chunk), process(chunk, **kw))[1]
    eng._chunk_finish = lambda chunk: (order.append("chunk"), finish(chunk))[1]
    try:
        step(eng)
    finally:
        del eng._process_chunk, eng._chunk_finish
    assert order == [before, "chunk"]
    run_out(eng)
    assert len(answer(keep)[0]) == 40


# -- the counters and the programs ---------------------------------------------


def test_the_counters_say_how_full_the_chunk_dispatches_are(params):
    eng = JaxServingEngine(CFG, params, ENGINE_CFG)
    try:
        snap = eng.metrics_snapshot()
        assert [snap[k] for k in (
            "chunk_positions_dispatched", "chunk_tokens_fed", "chunk_rows_dispatched",
            "chunk_rows_live", "chunk_dispatches_by_rows", "chunk_lanes_fed",
            "prompt_dispatches", "prompts_prefilled")] == [0, 0, 0, 0, {}, 0, 0, 0]
        a = submit(eng, prompt_of(9, 0), 30)
        step(eng)  # one row of 16 positions, 9 tokens
        submit(eng, prompt_of(40, 1), 2)  # 16 + 16 in two rows of one dispatch, then 8, beside A
        for _ in range(3):
            step(eng)
        submit(eng, prompt_of(5, 2), 2)
        submit(eng, prompt_of(20, 3), 2)  # two lanes fill the rung of 2, then the 4 tokens left
        run_out(eng)
        snap = eng.metrics_snapshot()
        assert snap["chunk_dispatches_by_rows"] == {"1": 3, "2": 2}
        assert snap["chunk_rows_dispatched"] == 7 and snap["chunk_rows_live"] == 7
        assert snap["chunk_positions_dispatched"] == 7 * CHUNK
        assert snap["chunk_tokens_fed"] == 9 + 40 + 5 + 20
        # seven rows over the six lanes the five dispatches fed: one lane took two
        assert snap["chunk_lanes_fed"] == snap["prompt_dispatches"] == 6
        assert snap["prompts_prefilled"] == 4  # 1 + 2 + 1 + 2 dispatches
        assert len(answer(a)[0]) == 30
    finally:
        eng.close()


def test_warmup_compiles_every_rung_and_serving_compiles_nothing_more(params):
    eng = JaxServingEngine(CFG, params, ENGINE_CFG)
    try:
        eng.warmup("greedy")
        rows = sorted(k[4] for k in eng._chunk_fns)
        # both history variants at max_slots, the history-bearing one under it
        assert rows == [1, 2, 8, 8]
        assert all(k[3] for k in eng._chunk_fns if k[4] < 8)
        compiled = compile_count()
        serve_schedule(eng, WAVE)
        serve_schedule(eng, MIXED)
        assert compile_count() == compiled
        assert set(eng.chunk_dispatches_by_rows) == {1, 2, 8}
    finally:
        eng.close()


def test_seal_time_checksums_come_from_the_pages_taken_as_the_program_was_dispatched(params):
    """The blocks a chunk or decode dispatch fills are read off the pool by a
    program enqueued right behind it, so the seal-time checksum never waits
    behind a later dispatch: no seal goes through the plain read, and every
    checksum is the one the pool's bytes give."""
    from dynamo_tpu.kv import pages as kv_pages

    eng = JaxServingEngine(CFG, params, ENGINE_CFG)
    try:
        assert eng._seal_checksums
        plain, extract = [], eng.extract_blocks
        eng.extract_blocks = lambda ids, **kw: (plain.append(list(ids)), extract(ids, **kw))[1]
        serve_schedule(eng, MIXED, salt=90)
        assert plain == []
        del eng.extract_blocks
        sealed = dict(eng.allocator._crc_of)
        assert len(sealed) >= sum(n // ENGINE_CFG.kv_block_size for _, n, _, _ in MIXED)
        for bid, crc in sealed.items():
            assert crc == kv_pages.checksums(eng.extract_blocks([bid]))[0], bid
    finally:
        eng.close()
