"""Jamba where a lane fills several rows of one chunk dispatch (``models/jamba.py``
sets ``LANE_TAKES_ROWS``), at ``tests/test_jamba.py``'s tiny size on the CPU:
the chunk kernel's rows handed over on the chip (interpreted here), the
module's chunk program against the plain reference and against a row a
dispatch, the engine on a ladder whose rungs hold 8 and 16 rows. A file of its
own beside ``tests/test_jamba.py`` because the driver's workers take a file
each: together the two ran eleven minutes on one of them."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_jamba as ref
from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
from dynamo_tpu.engine_jax.weights import config_from_card
from dynamo_tpu.models import jamba
from dynamo_tpu.ops.pallas.selective_scan import selective_scan

from .jamba_harness import (  # noqa: F401  (the fixtures are this file's too)
    ATOL, ATOL_BF16, N_MAMBA, SHAPE, SPARE_BLOCKS, cfg, dispatch_rows, engine, lowered_step_programs, params,
    recurrence_inputs,
)
from .step_programs import (  # noqa: F401  (highest_precision: autouse, for this file's tests)
    MIXED, answer, busy, card, highest_precision, patched, prompt_of, reference_program, served, step, submit,
)

# (tokens a row, the rows of a call in order: a lane is a list of its rows' valid tokens, each full but
# the last; None = a padding row, which belongs to no lane)
HANDOVERS = {
    "lanes_of_one_row": (16, [[16], [5], [16]]),
    "a_lane_of_two_rows": (16, [[9], [16, 16], [3]]),
    "a_lane_of_three_rows_whose_last_is_ragged": (16, [[16, 16, 11], [16]]),
    "a_lane_of_eight_rows": (16, [[16] * 7 + [6]]),
    "a_padding_row_between_two_lanes": (16, [[16, 7], None, [16, 16]]),
    "a_last_row_of_one_valid_token": (16, [[16, 1], [16, 16, 1]]),
    "a_chunk_that_is_no_multiple_of_the_tile": (172, [[172, 172, 130], [40]]),
}


@pytest.mark.parametrize("layout", list(HANDOVERS))
def test_a_lanes_rows_handed_over_are_one_long_row_and_successive_calls(cfg, params, layout):
    """``selective_scan`` with rows that continue the row above them, bit for
    bit against the same kernel with nothing handed over: (1) ONE row that
    holds the lane's tokens end to end, (2) a call a row, each from the state
    the call before it returns; the lane's state comes back at its first row.
    A padding row takes nothing and hands nothing on (its inputs are NaNs here,
    its state comes back as it went in), and (3) with no row continuing, the
    call is the call a row that it was before rows could be handed over: the
    grid then walks rows that have nothing to do with each other."""
    t, lanes = HANDOVERS[layout]
    a = -jnp.exp(params["mamba"][0]["a_log"][0])
    n_valid = [n for lane in lanes for n in (lane or [0])]
    continues = [k > 0 for lane in lanes for k in range(len(lane or [0]))]
    firsts = np.cumsum([0] + [len(lane or [0]) for lane in lanes])[:-1]
    (delta, x, b, c), s0 = recurrence_inputs(cfg, len(n_valid), t, seed=len(layout), step=-1.0)
    pad = [i for i, lane in zip(firsts, lanes) if lane is None]
    x = x.at[jnp.asarray(pad, jnp.int32)].set(jnp.nan)
    y, s = selective_scan(delta, x, b, c, a, s0, jnp.asarray(n_valid), jnp.asarray(continues), interpret=True)
    y, s = np.asarray(y), np.asarray(s)
    assert np.isfinite(y).all() and np.isfinite(s[firsts]).all()
    for first, lane in zip(firsts, lanes):
        if lane is None:
            assert np.array_equal(s[first], np.asarray(s0[first])) and not y[first].any()
            continue
        m, mine = len(lane), slice(first, first + len(lane))
        # (1) one long row
        long_y, long_s = selective_scan(
            *(v[mine].reshape(1, m * t, -1) for v in (delta, x, b, c)), a, s0[first:first + 1],
            jnp.asarray([(m - 1) * t + lane[-1]]), interpret=True)
        assert np.array_equal(np.asarray(long_y).reshape(m, t, -1), y[mine]), "one long row: y"
        assert np.array_equal(np.asarray(long_s[0]), s[first]), "one long row: state"
        # (2) a call a row
        state = s0[first:first + 1]
        for r in range(first, first + m):
            own_y, state = selective_scan(delta[r:r + 1], x[r:r + 1], b[r:r + 1], c[r:r + 1], a, state,
                                          jnp.asarray(n_valid[r:r + 1]), interpret=True)
            assert np.array_equal(np.asarray(own_y[0]), y[r]), "successive calls: y"
        assert np.array_equal(np.asarray(state[0]), s[first]), "successive calls: state"
        assert not y[first + m - 1, lane[-1]:].any() and y[first + m - 1, :lane[-1]].all()
    # (3) nothing handed over: every row from its own state, as a call of its own
    x = jnp.nan_to_num(x)
    for told in (None, jnp.zeros((len(n_valid),), bool)):
        y, s = selective_scan(delta, x, b, c, a, s0, jnp.asarray(n_valid), told, interpret=True)
        for r in range(len(n_valid)):
            own_y, own_s = selective_scan(delta[r:r + 1], x[r:r + 1], b[r:r + 1], c[r:r + 1], a,
                                          s0[r:r + 1], jnp.asarray(n_valid[r:r + 1]), interpret=True)
            assert np.array_equal(np.asarray(own_y[0]), np.asarray(y[r]))
            assert np.array_equal(np.asarray(own_s[0]), np.asarray(s[r]))


# a lane's successive pieces in consecutive rows of ONE dispatch (how `dispatch_rows` is called)
LAYOUTS = {
    "two_pieces_in_one_dispatch": dict(dispatches=[[(2, 16), (2, 5), (5, 12)]]),
    "three_pieces_in_one_dispatch": dict(dispatches=[[(1, 9), (2, 16), (2, 16), (2, 7)]]),
    "eight_pieces_that_fill_the_rung": dict(mb=20, dispatches=[[(2, 16)] * 7 + [(2, 10)]]),
    "sixteen_rows": dict(rows=16, slots=20, mb=20, dispatches=[
        [(0, 11), (1, 16), (1, 16), (1, 3)] + [(2, 16)] * 7 + [(2, 9)] + [(7, 16), (7, 1)]]),
    # pieces behind 32 positions of the lane's own pool history and the state that went with them
    "pieces_behind_pool_history": dict(rows=16, slots=20, mb=20, dispatches=[
        [(3, 16), (3, 16), (5, 16)],
        [(0, 7), (1, 16), (1, 2), (2, 16), (2, 16), (2, 1), (3, 16), (3, 16), (3, 16), (3, 6), (5, 4)]]),
    # a lane whose rows straddle two GROUPS of the dispatch (``jamba.ROWS_AT_ONCE`` = 4 rows each): rows
    # 2-6 of 8 with a padding row above it and one after; rows 3-11 of 16 (three groups), a padding row,
    # a second lane that starts in the last group, a padding row after it
    "a_lane_that_straddles_two_groups": dict(mb=12, dispatches=[[(5, 9), None] + [(2, 16)] * 4 + [(2, 7)]]),
    "a_lane_over_three_groups_and_one_that_starts_in_the_last": dict(rows=16, slots=20, mb=20, dispatches=[
        [(0, 11), (1, 16), (1, 3)] + [(2, 16)] * 8 + [(2, 9), None, (7, 16), (7, 1)]]),
    # the same behind pool history and stored state: the lane's first piece went in a dispatch before
    "a_straddling_lane_behind_pool_history": dict(mb=14, dispatches=[
        [(2, 16), (5, 16), (5, 3)], [None, (3, 4), (2, 16), (2, 16), (2, 16), (2, 16), (2, 2)]]),
    # the full width: one row a lane, sixteen groups of which the last holds padding rows alone
    "the_full_width": dict(rows=64, slots=64, mb=3, dispatches=[[(s, (16, 5, 9, 12)[s % 4]) for s in range(58)]]),
}


def counted_in_groups(d, rows):
    """What a dispatch ``d`` of ``rows`` rows owes the counters: (the (lane,
    group) pairs: a lane's state goes to the chip and back once a group it has
    a row in; the rows that hold a piece; the rows of the groups as far as the
    last of them)."""
    n = jamba.ROWS_AT_ONCE if rows % jamba.ROWS_AT_ONCE == 0 else rows
    held = [r for r, row in enumerate(d) if row]
    return len({(d[r][0], r // n) for r in held}), len(held), -(-(held[-1] + 1) // n) * n


BF16_LAYOUTS = ("three_pieces_in_one_dispatch", "sixteen_rows",
                "a_lane_over_three_groups_and_one_that_starts_in_the_last")


@pytest.mark.parametrize("layout, dtype, atol", [
    *((layout, jnp.float32, ATOL) for layout in LAYOUTS),
    *((layout, jnp.bfloat16, ATOL_BF16) for layout in BF16_LAYOUTS),
], ids=[*(f"{layout}-float32" for layout in LAYOUTS), *(f"{layout}-bfloat16" for layout in BF16_LAYOUTS)])
def test_a_prompt_whose_pieces_fill_rows_of_one_dispatch_agrees_with_the_plain_reference(layout, dtype, atol):
    """Several chunks of a prompt in consecutive rows of ONE dispatch under the
    full width (8 rows, and 16), beside other lanes: a later row starts each
    recurrence from the state the row above it ends with and each convolution
    from that row's last inputs, and attends its fresh keys; then three decode
    steps off the state the lane's rows left the slot, against the reference's
    one pass over the whole sequence. The other slots' state and the other
    pages stay as they were, and a sequence's first chunk alone resets its
    slot."""
    cfg = config_from_card(card(SHAPE), dtype)
    params = jamba.init_params(jax.random.PRNGKey(3), cfg)
    how = {"rows": 8, "slots": 10, "mb": 8, **LAYOUTS[layout]}
    served, state, cache, sums = dispatch_rows(cfg, params, **how)
    for slot, (tokens, _, got) in served.items():
        want = np.asarray(reference_program(ref, SHAPE)(params, jnp.asarray(tokens), jnp.arange(len(tokens))))
        np.testing.assert_allclose(got, want, atol=atol, err_msg=f"slot {slot}")
    idle = tuple(i for i in range(how["slots"]) if i not in served)
    assert idle
    for leaf in jax.tree.leaves(state):  # the slots no row fed, of every layer: untouched
        assert float(leaf[:, idle].min()) == float(leaf[:, idle].max()) == 7.0
    # pages outside every fed slot's table: block 0 (a padding row's table points there) and the spare
    # ones behind the last table (``dispatch_rows`` holds the same of a table's blocks no token reaches)
    tabled = 1 + len(served) * how["mb"]
    assert cache["k"].shape[1] == tabled + SPARE_BLOCKS
    for pool in (np.asarray(cache["k"]), np.asarray(cache["v"])):
        assert not pool[:, 0].any() and not pool[:, tabled:].any() and pool[:, 1].any()
    begun = set()
    for d, counted in zip(how["dispatches"], sums):
        fed = {row[0] for row in d if row}
        assert counted["slot_state_resets"] == len(fed - begun)
        begun |= fed
        # the rows that went on from the row above them: the dispatch's rows less its lanes, a lane
        # counted once a GROUP it has a row in, and the state went to the chip and back once a lane,
        # group and layer; the groups run as far as the last row that holds a piece
        passes, held, computed = counted_in_groups(d, how["rows"])
        assert counted["ssm_state_handovers"] == held - passes
        assert counted["ssm_state_passes"] == N_MAMBA * passes
        assert counted["ssm_chunk_tokens"] == N_MAMBA * sum(row[1] for row in d if row)
        assert counted["chunk_rows_computed"] == computed <= how["rows"]


# (the rows of ONE dispatch; the same rows with padding rows put in, which moves where the lanes stand
# in the groups; the same pieces a dispatch a lane's piece; the rows and slots of the dispatches)
WRITES = {
    "three_rows_beside_a_lane_of_one": dict(
        one=[(2, 16), (2, 16), (2, 5), (5, 9)], apart=[(2, 16), (2, 16), (2, 5), None, (5, 9)],
        piecewise=[[(2, 16)], [(2, 16)], [(2, 5), (5, 9)]]),
    # rows 2-6 of 8: the lane's state and tail pass through its slot's entries between rows 3 and 4; moved
    # up a row it straddles between its second row and its third
    "rows_two_to_six_of_eight": dict(
        one=[(5, 9), None] + [(2, 16)] * 4 + [(2, 7)], apart=[None, (5, 9), None] + [(2, 16)] * 4 + [(2, 7)],
        piecewise=[[(5, 9), (2, 16)]] + [[(2, 16)]] * 3 + [[(2, 7)]], mb=12),
    # rows 3-11 of 16 over three groups, a second lane that starts in the last group; moved down two rows
    # the first lane ends in the last group and the second starts on its first row
    "rows_three_to_eleven_of_sixteen": dict(
        one=[(0, 11), (1, 16), (1, 3)] + [(2, 16)] * 8 + [(2, 9), None, (7, 16), (7, 1)],
        apart=[(0, 11), None, (1, 16), (1, 3), None] + [(2, 16)] * 8 + [(2, 9), (7, 16), (7, 1)],
        piecewise=[[(0, 11), (1, 16), (2, 16), (7, 16)], [(1, 3), (2, 16), (7, 1)]] + [[(2, 16)]] * 6 + [[(2, 9)]],
        rows=16, slots=20, mb=20),
}


@pytest.mark.parametrize("layout", list(WRITES))
def test_a_lanes_rows_write_the_slots_state_once_and_a_padding_row_between_lanes_changes_nothing(cfg, params, layout):
    """Several pieces of a prompt in consecutive rows beside other lanes: the
    slot is left the state after the lane's LAST row and that row's tail (the
    lane's other rows of a group write nowhere: three writes of one slot in one
    scatter would leave any of them; a lane whose rows straddle two groups
    writes its slot once a group, the later over the earlier), and padding rows
    between the lanes, which move where a lane's rows stand in the groups, move
    nothing of any, to the bit. A row a dispatch leaves the first run of
    Mamba layers' state and tails THE SAME BITS (the kernel's rows handed over
    are the kernel called a row at a time; the convolution's products are the
    same; from group to group the state is float32 there and back), and what
    lies behind the first attention layer within float32's rounding: a piece's
    keys are attended as fresh keys here and out of the pool there, two orders
    of one sum."""
    how = dict(WRITES[layout])
    rows, apart_rows, pieces = how.pop("one"), how.pop("apart"), how.pop("piecewise")
    one = dispatch_rows(cfg, params, [rows], n_decode=0, **how)
    apart = dispatch_rows(cfg, params, [apart_rows], n_decode=0, **how)
    piecewise = dispatch_rows(cfg, params, pieces, n_decode=0, **how)
    n_rows = how.get("rows", 8)
    for d, got in ((rows, one), (apart_rows, apart)):
        passes, held, _ = counted_in_groups(d, n_rows)
        assert got[3][0]["ssm_state_handovers"] == held - passes > 0
    assert [s["ssm_state_handovers"] for s in piecewise[3]] == [0] * len(pieces)
    fed = sorted(one[0])
    for slot in fed:
        assert np.array_equal(one[0][slot][1], apart[0][slot][1])
        np.testing.assert_allclose(one[0][slot][1], piecewise[0][slot][1], atol=1e-4)
    for mine, theirs in zip(jax.tree.leaves((one[1], one[2])), jax.tree.leaves((apart[1], apart[2]))):
        assert np.array_equal(np.asarray(mine), np.asarray(theirs))
    idle = [i for i in range(how.get("slots", 10)) if i not in fed]
    for name in ("s", "conv"):
        assert np.array_equal(np.asarray(one[1][name][0]), np.asarray(piecewise[1][name][0]))
        for mine, theirs in zip(one[1][name], piecewise[1][name]):
            np.testing.assert_allclose(np.asarray(mine), np.asarray(theirs), atol=1e-5)
            assert float(mine[:, idle].min()) == float(mine[:, idle].max()) == 7.0
            assert not np.array_equal(np.asarray(mine[:, fed[0]]), np.asarray(mine[:, fed[1]]))
    for name in ("k", "v"):
        np.testing.assert_allclose(np.asarray(one[2][name]), np.asarray(piecewise[2][name]), atol=1e-5)


def test_what_the_rows_past_the_last_piece_hold_changes_nothing(cfg, params):
    """Rows 0-4 of 8 hold pieces (a lane of four rows and one of one: two
    groups), the rows after them and every ragged tail hold a token that is no
    padding under position -1: the state, the pool and the live rows' hidden
    states are what they are with zeros there, to the bit; the group the rows
    reach is computed whole (row 5-7's hidden states are some numbers) and at
    sixteen rows the groups past it are not: their hidden states stay zeros."""
    d = [(2, 16), (2, 16), (2, 16), (2, 4), (5, 9)]
    for rows, slots in ((8, 10), (16, 20)):
        clean, garbage = [], []
        zeros = dispatch_rows(cfg, params, [d], rows=rows, slots=slots, n_decode=0, hidden_rows=clean)
        other = dispatch_rows(cfg, params, [d], rows=rows, slots=slots, n_decode=0, hidden_rows=garbage,
                              padding_token=SHAPE["vocab_size"] - 1)
        for mine, theirs in zip(jax.tree.leaves(zeros[:3]), jax.tree.leaves(other[:3])):
            assert np.array_equal(np.asarray(mine), np.asarray(theirs))
        assert zeros[3] == other[3] and zeros[3][0]["chunk_rows_computed"] == 8
        (h,), (g,) = clean, garbage
        assert all(np.array_equal(h[r, :n], g[r, :n]) for r, (_, n) in enumerate(d))
        assert h[5:8].any() and g[5:8].any() and not np.array_equal(h[5:8], g[5:8])
        assert not h[8:].any() and not g[8:].any()


def test_a_row_that_starts_from_its_slots_stored_state_is_wrong_where_it_should_go_on(cfg, params, monkeypatch):
    """What the hand-over is there for: a prompt's second piece in the row under
    its first (16 tokens, then 9), each part of the hand-over taken away in
    turn. From the slot's stored state, or its stored tail, as a row alone in
    its lane starts, the second piece is wrong by far more than ATOL, and only
    from there on."""
    how = dict(dispatches=[[(2, 16), (2, 9)]], n_decode=0)
    (tokens, _, got), = dispatch_rows(cfg, params, **how)[0].values()
    want = np.asarray(reference_program(ref, SHAPE)(params, jnp.asarray(tokens), jnp.arange(len(tokens))))
    np.testing.assert_allclose(got, want, atol=ATOL)
    scan, convolve = jamba._scan_tokens, jamba._convolve
    for name, cut in (("_scan_tokens", lambda *a: scan(*a[:7])), ("_convolve", lambda *a: convolve(*a[:5]))):
        patched(monkeypatch, jamba, name, cut)
        (_, _, off), = dispatch_rows(cfg, params, **how)[0].values()
        monkeypatch.undo()
        assert np.abs(off[16:] - want[16:]).max() > 100 * ATOL, name
        np.testing.assert_allclose(off[:16], want[:16], atol=ATOL)


@pytest.mark.parametrize("rows, slots", [(8, 10), (16, 16)], ids=["under_the_full_width", "the_full_width"])
def test_the_chunk_kernel_is_traced_before_the_loops_with_the_loops_own_arguments(cfg, params, monkeypatch, rows, slots):
    """``selective_scan`` is jitted: JAX keeps its trace by its arguments'
    shapes, dtypes and weak types, and the trace made inside the loop over the
    groups and a run's layer loop costs three times the Python it costs outside
    them (PERF.md 6, PR 67). ``_trace_the_chunk_kernel`` makes it before the
    loops: it calls the kernel ONCE, with exactly what each of the runs' calls
    inside the loops then hands it (so those find the trace made), and the
    program is the same text with it taken away."""
    def sd(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    def lowered():
        args = (jax.tree.map(sd, params), jnp.zeros((rows, 16), jnp.int32), jnp.zeros((rows, 16), jnp.int32),
                jax.tree.map(sd, jamba.make_kv_cache(cfg, 24, 8)), jnp.zeros((rows, 8), jnp.int32),
                jax.tree.map(sd, jamba.make_slot_state(cfg, slots)), jnp.zeros((rows,), jnp.int32))
        return jax.jit(lambda p, *operands: jamba.forward_chunk(p, cfg, *operands)).lower(*args).as_text()

    seen, scan = [], jamba.selective_scan

    def spy(*args, **kw):
        seen.append(([None if a is None else (a.shape, a.dtype, getattr(a, "weak_type", False)) for a in args], kw))
        return scan(*args, **kw)

    patched(monkeypatch, jamba, "selective_scan", spy)
    text = lowered()
    first, *inside = seen
    traced = len(set(jamba._runs(cfg)))  # a run's layer loop is traced once a length of run
    assert len(inside) == traced and all(call == first for call in inside)
    assert first[0][0][0] == (jamba.ROWS_AT_ONCE, 16, cfg.d_inner)  # a group's rows, not the dispatch's
    assert (first[0][7] is None) == (rows == slots)  # no row continues another at the full width
    patched(monkeypatch, jamba, "_trace_the_chunk_kernel", lambda *a: seen.clear())
    assert lowered() == text and len(seen) == traced


# ladder [8, 16, 64]: a lane fills up to sixteen rows of a dispatch
WIDE_CFG = EngineConfig(max_slots=64, kv_block_size=8, max_model_len=192, prefill_chunk=16,
                        decode_steps=4)


def test_every_request_answers_as_alone_where_a_lane_fills_several_rows(cfg, params):
    """Mixed traffic on a ladder whose rungs under the full width hold 8 and 16
    rows: most prompts prefill in one dispatch, a later piece starting from
    the state and the tail the row above it leaves and attending its fresh keys
    inside the program, and every answer is the one the request gets alone on
    an engine of four slots (ladder [1, 4]), prefilled a chunk a step."""
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
        assert one.metrics_snapshot()["ssm_state_handovers"] == 0
        snap = wide.metrics_snapshot()
        # a row for every chunk of every prompt, whichever dispatch held it, and fewer dispatches a prompt
        assert snap["chunk_rows_live"] == sum(-(-n // 16) for _, n, _ in MIXED)
        assert snap["prompts_prefilled"] == len(MIXED) < snap["prompt_dispatches"] < snap["chunk_rows_live"]
        assert snap["chunk_rows_live"] > snap["chunk_lanes_fed"] == snap["prompt_dispatches"]
        # every row but a lane's first of a GROUP went on from the row above it, and the state went to
        # the chip and back once a lane, group and layer: a lane whose rows straddle two groups once more
        assert 0 < snap["ssm_state_handovers"] <= snap["chunk_rows_live"] - snap["chunk_lanes_fed"]
        assert snap["ssm_state_passes"] == N_MAMBA * (snap["chunk_rows_live"] - snap["ssm_state_handovers"])
        assert snap["slot_state_resets"] == len(MIXED)
        assert {8, 16} <= {int(r) for r in snap["chunk_dispatches_by_rows"]}
        # the groups run as far as the last row that holds a piece: whole groups, under the rungs' rows
        assert snap["chunk_rows_live"] <= snap["chunk_rows_computed"] < snap["chunk_rows_dispatched"]
        assert snap["chunk_rows_computed"] % jamba.ROWS_AT_ONCE == 0
        assert wide.allocator.active_blocks == 0 and not wide._zombie_allocs
    finally:
        wide.close()
        one.close()


def test_the_full_width_chunk_program_holds_nothing_of_the_hand_over(engine, monkeypatch):
    """At ``rows == slots`` a lane has one row by the engine's rule: none of the
    functions that hand a row what lies above it is traced there (each raises
    here, and the text is the same with them gone), no row's tail is chosen
    between two, and the kernel is told that no row continues. The rung under
    it calls them."""
    text = lowered_step_programs(engine)[0].as_text()
    told = []
    scan = jamba.selective_scan
    patched(monkeypatch, jamba, "selective_scan", lambda *a, **kw: told.append(a[7]) or scan(*a, **kw))

    def unreachable(*a, **kw):
        raise AssertionError("the hand-over, in a program that has one row a lane")

    for name in ("lane_first_positions", "sibling_rows_back", "chunk_rows_above_partial"):
        patched(monkeypatch, jamba, name, unreachable)
    assert lowered_step_programs(engine)[0].as_text() == text
    assert told and all(above is None for above in told)
    with pytest.raises(AssertionError, match="the hand-over"):
        lowered_step_programs(engine, rows=1)
