"""The two delta-rule modules (``models/kimi_linear.py``,
``models/qwen3_next.py``) where a lane fills several rows of one chunk dispatch
(both set ``LANE_TAKES_ROWS``), at their own tests' tiny sizes on the CPU in
float32: the chunk kernel's rows handed over on the chip (``kda_scan(...,
continues)``, interpreted here), each module's chunk program against its plain
reference and against a row a dispatch, the engine on ladders whose rungs under
the full width hold 8 and 16 rows (64 slots) or 2 and 4 (16 slots). A file of
its own, as ``tests/test_jamba_lane_rows.py`` is: the driver's workers take the
tests a file deals them, and the two modules' own files are long already."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine_jax.compile_cache import compile_count
from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
from dynamo_tpu.ops.pallas.kda_scan import kda_scan

from .delta_harness import MODELS, dispatch_rows, model_of, recurrence_inputs
from .step_programs import (  # noqa: F401  (highest_precision: autouse, for this file's tests)
    MIXED, answer, busy, highest_precision, patched, prompt_of, reference_program, served, step, submit,
)


@pytest.fixture(scope="module", params=list(MODELS))
def model(request):
    return model_of(request.param)


# (tokens a row, the rows of a call in order: a lane is a list of its rows' valid tokens, each full but
# the last; None = a padding row, which belongs to no lane): tests/test_jamba_lane_rows.py's
HANDOVERS = {
    "lanes_of_one_row": (16, [[16], [5], [16]]),
    "a_lane_of_two_rows": (16, [[9], [16, 16], [3]]),
    "a_lane_of_three_rows_whose_last_is_ragged": (16, [[16, 16, 11], [16]]),
    "a_lane_of_eight_rows": (16, [[16] * 7 + [6]]),
    "a_padding_row_between_two_lanes": (16, [[16, 7], None, [16, 16]]),
    "a_last_row_of_one_valid_token": (16, [[16, 1], [16, 16, 1]]),
    "a_chunk_that_is_no_multiple_of_the_tile": (172, [[172, 172, 130], [40]]),
}


@pytest.mark.parametrize("decay", ["a_channel", "a_head"])
@pytest.mark.parametrize("layout", list(HANDOVERS))
def test_a_lanes_rows_handed_over_are_one_long_row_and_successive_calls(layout, decay):
    """``kda_scan`` with rows that continue the row above them, bit for bit
    against the same kernel with nothing handed over: (1) ONE row that holds
    the lane's tokens end to end, (2) a call a row, each from the state the
    call before it returns; the lane's state comes back at its first row. A
    padding row takes nothing and hands nothing on (its inputs are NaNs here,
    its state comes back as it went in), and (3) told that no row continues, or
    told nothing, every row is a call of its own. With a decay a key channel (Kimi-Linear) and with one a head
    spread over its channels (Qwen3-Next)."""
    t, lanes = HANDOVERS[layout]
    n_valid = [n for lane in lanes for n in (lane or [0])]
    continues = [k > 0 for lane in lanes for k in range(len(lane or [0]))]
    firsts = np.cumsum([0] + [len(lane or [0]) for lane in lanes])[:-1]
    xs, s0 = recurrence_inputs(len(n_valid), t, decay, seed=len(layout))
    pad = jnp.asarray([i for i, lane in zip(firsts, lanes) if lane is None], jnp.int32)
    xs = tuple(a.at[pad].set(jnp.nan) for a in xs)
    o, s = kda_scan(*xs, s0, jnp.asarray(n_valid), jnp.asarray(continues), interpret=True)
    o, s = np.asarray(o), np.asarray(s)
    assert np.isfinite(o).all() and np.isfinite(s[firsts]).all()
    for first, lane in zip(firsts, lanes):
        if lane is None:
            assert np.array_equal(s[first], np.asarray(s0[first])) and not o[first].any()
            continue
        m, mine = len(lane), slice(first, first + len(lane))
        # (1) one long row
        long_o, long_s = kda_scan(
            *(a[mine].reshape(1, m * t, *a.shape[2:]) for a in xs), s0[first:first + 1],
            jnp.asarray([(m - 1) * t + lane[-1]]), interpret=True)
        assert np.array_equal(np.asarray(long_o).reshape(m, t, *o.shape[2:]), o[mine]), "one long row: o"
        assert np.array_equal(np.asarray(long_s[0]), s[first]), "one long row: state"
        # (2) a call a row
        state = s0[first:first + 1]
        for r in range(first, first + m):
            own_o, state = kda_scan(*(a[r:r + 1] for a in xs), state, jnp.asarray(n_valid[r:r + 1]), interpret=True)
            assert np.array_equal(np.asarray(own_o[0]), o[r]), "successive calls: o"
        assert np.array_equal(np.asarray(state[0]), s[first]), "successive calls: state"
        assert not o[first + m - 1, lane[-1]:].any() and o[first + m - 1, :lane[-1]].any()
    # (3) nothing handed over: every row from its own state, as a call of its own
    xs = tuple(jnp.nan_to_num(a) for a in xs)
    for told in (None, jnp.zeros((len(n_valid),), bool)):
        o, s = kda_scan(*xs, s0, jnp.asarray(n_valid), told, interpret=True)
        for r in range(len(n_valid)):
            own_o, own_s = kda_scan(*(a[r:r + 1] for a in xs), s0[r:r + 1], jnp.asarray(n_valid[r:r + 1]),
                                    interpret=True)
            assert np.array_equal(np.asarray(own_o[0]), np.asarray(o[r]))
            assert np.array_equal(np.asarray(own_s[0]), np.asarray(s[r]))


def test_told_nothing_the_kernel_is_the_same_one_call():
    """One kernel, one grid: told nothing, or which rows continue, the call
    walks ``(head group, row, token tile)`` with the rows of a head group in
    order and two prefetched scalars (the rows' valid tokens, the first row of
    each row's sequence). ``continues=None`` is no path of its own: it is the
    kernel with no row continuing (the test above holds its results, bit for
    bit, to a call a row)."""
    xs, s0 = recurrence_inputs(3, 16, "a_channel")
    n = jnp.asarray([16, 16, 4])

    def call_of(*told):
        jaxpr = jax.make_jaxpr(lambda *a: kda_scan(*a, *told, interpret=True))(*xs, s0, n)
        eqn, = (e for e in jaxpr.jaxpr.eqns[0].params["jaxpr"].eqns if e.primitive.name == "pallas_call")
        grid, how = eqn.params["grid_mapping"], eqn.params["compiler_params"]["mosaic_tpu"]
        return grid.grid, grid.num_index_operands, tuple(str(s) for s in how.dimension_semantics)

    assert call_of() == call_of(jnp.asarray([False, True, False])) == (
        (1, 3, 1), 2, ("parallel", "arbitrary", "arbitrary"))


# a lane's successive pieces in consecutive rows of ONE dispatch (how `dispatch_rows` is called)
LAYOUTS = {
    "two_pieces_in_one_dispatch": dict(dispatches=[[(2, 16), (2, 5), (5, 12)]]),
    "three_pieces_in_one_dispatch": dict(dispatches=[[(1, 9), (2, 16), (2, 16), (2, 7)]]),
    "eight_pieces_that_fill_the_rung": dict(mb=20, dispatches=[[(2, 16)] * 7 + [(2, 10)]]),
    # 16 rows: Qwen3-Next takes them as two groups of 8, and lane 2's eight rows (4-11) straddle them
    "sixteen_rows": dict(rows=16, slots=20, mb=20, dispatches=[
        [(0, 11), (1, 16), (1, 16), (1, 3)] + [(2, 16)] * 7 + [(2, 9)] + [(7, 16), (7, 1)]]),
    # pieces behind 32 positions of the lane's own pool history and the state that went with them;
    # lane 3's four rows (6-9) straddle Qwen3-Next's two groups
    "pieces_behind_pool_history": dict(rows=16, slots=20, mb=20, dispatches=[
        [(3, 16), (3, 16), (5, 16)],
        [(0, 7), (1, 16), (1, 2), (2, 16), (2, 16), (2, 1), (3, 16), (3, 16), (3, 16), (3, 6), (5, 4)]]),
}


def check_against_the_reference(model, served_rows):
    for slot, (tokens, got) in served_rows.items():
        want = np.asarray(reference_program(model.ref, model.shape)(
            model.params, jnp.asarray(tokens), jnp.arange(len(tokens))))
        np.testing.assert_allclose(got, want, atol=model.atol, err_msg=f"slot {slot}")


def a_row_a_dispatch(dispatches):
    """The same pieces with one row a lane and dispatch, as the engine fed them
    before a lane could take several: a dispatch's k-th rows of its lanes
    become a dispatch of their own."""
    out = []
    for d in dispatches:
        rounds, seen = [], {}
        for slot, n in d:
            k = seen[slot] = seen.get(slot, -1) + 1
            rounds += [[]] * (k == len(rounds))
            rounds[k] = rounds[k] + [(slot, n)]
        out += rounds
    return out


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_a_prompt_whose_pieces_fill_rows_of_one_dispatch_agrees_with_the_plain_reference(model, layout):
    """Several chunks of a prompt in consecutive rows of ONE dispatch under the
    full width (8 rows, and 16), beside other lanes: a later row starts its
    recurrence from the state the row above it ends with and its convolutions
    from that row's last inputs, and attends the rows above it (Kimi-Linear
    through the pool, Qwen3-Next as fresh keys); then three decode steps off
    the state the lane's rows left the slot, against the reference's one pass
    over the whole sequence and against the same pieces a row a dispatch. The
    other slots' state stays as it was, a sequence's first chunk alone resets
    its slot, and the counters count a pass a LANE (Qwen3-Next: and group)."""
    how = {"rows": 8, "slots": 10, "mb": 8, **LAYOUTS[layout]}
    got, state, cache, sums = dispatch_rows(model, **how)
    check_against_the_reference(model, got)
    apart = dispatch_rows(model, **{**how, "dispatches": a_row_a_dispatch(how["dispatches"])})
    assert all(not s[f"{model.prefix}_state_handovers"] for s in apart[3])
    for slot in got:
        np.testing.assert_allclose(got[slot][1], apart[0][slot][1], atol=model.atol, err_msg=f"slot {slot}")
    idle = [i for i in range(how["slots"]) if i not in got]
    for leaf in jax.tree.leaves(state):  # the slots no row fed, of every layer: untouched
        assert float(np.asarray(leaf)[idle].min()) == float(np.asarray(leaf)[idle].max()) == 7.0
    begun = set()
    for d, counted in zip(how["dispatches"], sums):
        fed = {slot for slot, _ in d}
        assert counted["slot_state_resets"] == len(fed - begun)
        begun |= fed
        # the rows that went on from the row above them: the dispatch's rows less its lanes
        assert counted[f"{model.prefix}_state_handovers"] == len(d) - len(fed)
        assert counted[f"{model.prefix}_chunk_tokens"] == model.layers * sum(n for _, n in d)
        # the state went to the chip and back once a lane and layer; where a lane's rows straddle
        # two of Qwen3-Next's groups of 8, once more (the loop over the groups carries it)
        straddles = sum(
            1 for at in range(model.module.ROWS_AT_ONCE, len(d), model.module.ROWS_AT_ONCE)
            if d[at][0] == d[at - 1][0])
        assert straddles == (model.name == "qwen3_next" and len(d) > 8)
        assert counted[f"{model.prefix}_state_passes"] == model.layers * (len(fed) + straddles)


def test_kimi_linears_lane_whose_rows_straddle_two_groups_goes_on_through_the_slot(monkeypatch):
    """Kimi-Linear's groups are 16 rows and its ladder's rungs under the full
    width 8 and 16, so none of its lanes straddles two; one that did (here
    groups of 4 of 8 rows) is served by the loop's own carry: the later
    group's first row reads the slot's state and tail, which the group before
    wrote."""
    model = model_of("kimi_linear")
    patched(monkeypatch, model.module, "ROWS_AT_ONCE", 4)
    rows = [(1, 16), (2, 16), (2, 16), (2, 16), (2, 16), (2, 3), (5, 16), (5, 2)]
    got, _, _, (counted,) = dispatch_rows(model, [rows], mb=20)
    check_against_the_reference(model, got)
    assert counted["kda_state_handovers"] == 4 and counted["kda_state_passes"] == model.layers * 4


def test_a_lanes_rows_write_the_slots_state_once_and_a_padding_row_between_lanes_changes_nothing(model):
    """Three pieces of a prompt in rows 0-2 beside another lane's one: the
    slot is left the state after the lane's LAST row and that row's tail (the
    lane's other rows write nowhere: three writes of one slot in one scatter
    would leave any of them), and a padding row between the two lanes moves
    nothing of either, to the bit. A row a dispatch leaves the first layer's
    state and tail THE SAME BITS (the kernel's rows handed over are the kernel
    called a row at a time; the convolution's products are the same)."""
    rows = [(2, 16), (2, 16), (2, 5), (5, 9)]
    one = dispatch_rows(model, [rows], n_decode=0)
    apart = dispatch_rows(model, [rows[:3] + [None] + rows[3:]], n_decode=0)
    piecewise = dispatch_rows(model, [[(2, 16)], [(2, 16)], [(2, 5), (5, 9)]], n_decode=0)
    handovers = f"{model.prefix}_state_handovers"
    assert one[3][0][handovers] == apart[3][0][handovers] == 2
    assert [s[handovers] for s in piecewise[3]] == [0, 0, 0]
    for slot in (2, 5):
        assert np.array_equal(one[0][slot][1], apart[0][slot][1])
        np.testing.assert_allclose(one[0][slot][1], piecewise[0][slot][1], atol=1e-4)
    for mine, theirs in zip(jax.tree.leaves((one[1], one[2])), jax.tree.leaves((apart[1], apart[2]))):
        assert np.array_equal(np.asarray(mine), np.asarray(theirs))
    for name in ("s", "conv"):
        assert np.array_equal(np.asarray(one[1][name][0]), np.asarray(piecewise[1][name][0]))
        for mine, theirs in zip(one[1][name], piecewise[1][name]):
            np.testing.assert_allclose(np.asarray(mine), np.asarray(theirs), atol=1e-4)
            assert float(mine[0].min()) == float(mine[9].max()) == 7.0
            assert not np.array_equal(np.asarray(mine[2]), np.asarray(mine[5]))
    for mine, theirs in zip(jax.tree.leaves(one[2]), jax.tree.leaves(piecewise[2])):
        np.testing.assert_allclose(np.asarray(mine), np.asarray(theirs), atol=1e-4)


@pytest.mark.parametrize("part", ["the_state", "the_tail"])
def test_a_row_that_starts_from_its_slots_stored_state_is_wrong_where_it_should_go_on(model, part, monkeypatch):
    """What the hand-over is there for: a prompt's second piece in the row under
    its first (16 tokens, then 9), each part of the hand-over taken away in
    turn. From the slot's stored state, or its stored tail, as a row alone in
    its lane starts, the second piece is wrong by far more than the tolerance,
    and only from there on."""
    how = dict(dispatches=[[(2, 16), (2, 9)]], n_decode=0)
    (tokens, got), = dispatch_rows(model, **how)[0].values()
    want = np.asarray(reference_program(model.ref, model.shape)(
        model.params, jnp.asarray(tokens), jnp.arange(len(tokens))))
    np.testing.assert_allclose(got, want, atol=model.atol)
    mod = model.module
    if part == "the_state":  # the kernel is told nothing: every row from ``s0``
        scan = mod.kda_scan
        patched(monkeypatch, mod, "kda_scan", lambda *a, **kw: scan(*a[:7], **kw))
    elif model.name == "kimi_linear":
        inputs = mod._kda_inputs
        patched(monkeypatch, mod, "_kda_inputs", lambda *a: inputs(*a[:5]))
    else:  # no row takes the row above's tail; the kernel still hands the state on
        mixer = mod.gdn_mixer
        patched(monkeypatch, mod, "gdn_mixer", lambda *a: mixer(
            *a[:6], a[6] and (jnp.zeros_like(a[6][0]), *a[6][1:])))
    (_, off), = dispatch_rows(model, **how)[0].values()
    assert np.abs(off[16:] - want[16:]).max() > 100 * model.atol
    np.testing.assert_allclose(off[:16], want[:16], atol=model.atol)


# ladder [8, 16, 64]: a lane fills up to sixteen rows of a dispatch
WIDE_CFG = EngineConfig(max_slots=64, kv_block_size=8, max_model_len=192, prefill_chunk=16, decode_steps=4)
# ladder [2, 4, 16]: a lane fills up to four (at a quarter of the rows)
MID_CFG = dataclasses.replace(WIDE_CFG, max_slots=16)


def serve_schedule(engine, schedule, salt):
    seqs, t = {}, 0
    while busy(engine) or len(seqs) < len(schedule):
        for i, (at, n, m) in enumerate(schedule):
            if at == t:
                seqs[i] = submit(engine, prompt_of(n, salt=salt + i), m)
        step(engine)
        t += 1
        assert t < 400
    return [seqs[i] for i in range(len(schedule))]


@pytest.fixture(scope="module")
def one(model):
    """Four slots, ladder [1, 4]: no rung under the full width holds two rows,
    so a prompt prefills a chunk a step."""
    eng = JaxServingEngine(model.cfg, model.params, dataclasses.replace(WIDE_CFG, max_slots=4))
    yield eng
    eng.close()


@pytest.mark.parametrize("ladder", ["8_16_64", "2_4_16"])
def test_every_request_answers_as_alone_where_a_lane_fills_several_rows(model, one, ladder):
    """Mixed traffic on a ladder whose rungs under the full width hold 8 and 16
    rows (or 2 and 4): most prompts prefill in one dispatch, a later piece
    starting from the state and the tail the row above it leaves, and every
    answer is the one the request gets alone on an engine of four slots
    (ladder [1, 4]), prefilled a chunk a step. The module's two cross-check
    counters agree with the host's: a handover every row but a lane's first of
    a dispatch, a pass a lane, dispatch and layer (and one more where a lane's
    rows straddle two groups of a 16-row dispatch: Qwen3-Next's groups are 8)."""
    wide = JaxServingEngine(model.cfg, model.params, WIDE_CFG if ladder == "8_16_64" else MID_CFG)
    try:
        assert model.module.LANE_TAKES_ROWS and wide._lane_rows
        assert wide._chunk_rungs == [int(r) for r in ladder.split("_")]
        seqs = serve_schedule(wide, MIXED, salt=40)
        for i, (_, n, m) in enumerate(MIXED):
            toks, _, finish = answer(seqs[i])
            assert (toks, finish) == (served(one, prompt_of(n, salt=40 + i), m)[0], "length"), i
        alone = one.metrics_snapshot()
        assert alone["chunk_rows_live"] == alone["chunk_lanes_fed"]
        assert alone[f"{model.prefix}_state_handovers"] == 0
        snap = wide.metrics_snapshot()
        # a row for every chunk of every prompt, whichever dispatch held it, and fewer dispatches a prompt
        assert snap["chunk_rows_live"] == sum(-(-n // 16) for _, n, _ in MIXED)
        assert snap["prompts_prefilled"] == len(MIXED) < snap["prompt_dispatches"] < snap["chunk_rows_live"]
        assert snap["chunk_rows_live"] > snap["chunk_lanes_fed"] == snap["prompt_dispatches"]
        assert snap[f"{model.prefix}_state_handovers"] == snap["chunk_rows_live"] - snap["chunk_lanes_fed"]
        passes, lanes_fed = snap[f"{model.prefix}_state_passes"], model.layers * snap["chunk_lanes_fed"]
        sixteens = snap["chunk_dispatches_by_rows"].get("16", 0)  # keyed as JSON sends it
        if model.name == "qwen3_next" and ladder == "8_16_64":
            assert lanes_fed <= passes <= lanes_fed + model.layers * sixteens
        else:
            assert passes == lanes_fed
        assert snap["slot_state_resets"] == len(MIXED)
        assert set(wide._chunk_rungs[:2]) <= {int(r) for r in snap["chunk_dispatches_by_rows"]}
        assert wide.allocator.active_blocks == 0 and not wide._zombie_allocs
    finally:
        wide.close()


def test_warmup_compiles_every_rung_and_serving_compiles_nothing_more(model):
    """The ladder's chunk programs are the ones ``warmup`` compiled before the
    flag (one a rung: a module with its own programs has no history-free
    variant), and traffic whose lanes fill several rows compiles nothing."""
    eng = JaxServingEngine(model.cfg, model.params, dataclasses.replace(MID_CFG, max_model_len=96))
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


def lowered_chunk_program(engine, rows):
    """The engine's chunk program at ``rows`` rows, lowered from shapes as the
    engine calls it."""
    def sd(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    c, mb = engine.config.prefill_chunk, engine.config.max_blocks_per_seq
    pool = (jax.tree.map(sd, engine.params), jax.tree.map(sd, engine.cache),
            jax.tree.map(sd, engine.slot_state), sd(engine._dummy_counts))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    wd = (i32(),) if engine._watchdog else ()
    return engine._build_chunk_fn(False, False, False).lower(
        *pool, i32(rows, c), i32(rows, c), i32(rows, mb), i32(rows), i32(rows), i32(), i32(2, rows),
        f32(4, rows), *wd)


def test_the_full_width_chunk_program_holds_nothing_of_the_hand_over(model, one, monkeypatch):
    """At ``rows == slots`` a lane has one row by the engine's rule: none of the
    functions that hand a row what lies above it is traced there (each raises
    here, and the text is the same with them gone), and the kernel is told
    that no row continues. The rung under it calls them."""
    mod = model.module
    text = lowered_chunk_program(one, 4).as_text()
    told = []
    scan = mod.kda_scan
    patched(monkeypatch, mod, "kda_scan", lambda *a, **kw: told.append(a[7:]) or scan(*a, **kw))

    def unreachable(*a, **kw):
        raise AssertionError("the hand-over, in a program that has one row a lane")

    names = {"kimi_linear": (), "qwen3_next": ("chunk_layout", "_Left", "chunk_rows_above_partial")}[model.name]
    for name in names:
        patched(monkeypatch, mod, name, unreachable)
    assert lowered_chunk_program(one, 4).as_text() == text
    assert len(told) == model.layers and all(above in ((), (None,)) for above in told)
    del told[:]
    if names:
        with pytest.raises(AssertionError, match="the hand-over"):
            lowered_chunk_program(one, 1)
    else:
        lowered_chunk_program(one, 1)
        assert len(told) == model.layers and all(len(above) == 1 and above[0] is not None for above in told)
