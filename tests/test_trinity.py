"""Trinity (``model_type: afmoe``) on the served path, at a tiny size on the CPU
(hidden 64, five layers: window, window, window, full, window; the first
feed-forward dense, the rest 4 held of 16 experts, 2 a token, beside a shared
expert; six query heads over two key/value heads of 16; ``sliding_window`` 32,
so a window layer's ring holds 48 positions a slot and EVERY test crosses the
window and wraps the ring).

The program (``models/trinity.py``: chunked prefill through the full layer's
pages and the window layers' rings, then decode) is held against the
benchmark's plain reference (``benchmark/reference_trinity.py``: one sequence,
the whole prompt at once, naive masked attention, every held expert for every
token, no cache); the engine against both, and against the refusals a model
with per-slot state owes whatever would hand its pages over without it.
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_trinity as ref
from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine, chunk_row_ladder
from dynamo_tpu.engine_jax.weights import config_from_card
from dynamo_tpu.kv.pages import StateNotPortable
from dynamo_tpu.models import module_for, trinity
from dynamo_tpu.ops import ring

from .step_programs import (  # noqa: F401  (highest_precision: autouse, for this file's tests)
    answer, busy, card, chunk_program, collect, decode_program, highest_precision, patched, prompt_of,
    published_shape, reference_program, run_out, served, step, submit,
)

# float32 on the CPU at the highest matmul precision on both sides, so the
# program and the reference differ by the order of their sums alone (flash
# partials over tiles of a ring against one softmax, sorted rows of an expert
# against every expert for every token): 2e-4 on logits of magnitude 3 is what
# the other modules are allowed for the same reason (measured here: 4e-6). Each
# fault a test below puts in moves a logit by 30 x that and more.
ATOL = 2e-4
WINDOW = 32
SHAPE = {
    "model_type": "afmoe", "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 5,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention", "sliding_attention"],
    "global_attn_every_n_layers": 4, "sliding_window": WINDOW, "num_dense_layers": 1,
    "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 16,
    "moe_intermediate_size": 32, "num_experts": 4, "num_experts_published": 16, "first_expert": 0,
    "num_experts_per_tok": 2, "num_shared_experts": 1, "score_func": "sigmoid", "route_norm": True,
    "route_scale": 2.448, "n_group": 1, "topk_group": 1, "num_expert_groups": 1, "num_limited_groups": 1,
    "mup_enabled": True, "rms_norm_eps": 1e-5, "rope_theta": 10000, "rope_scaling": None,
    "tie_word_embeddings": False, "vocab_size": 96, "max_position_embeddings": 262144,
}
N_WINDOW, N_FULL, N_EXPERT_LAYERS = 4, 1, 4
RING = WINDOW + trinity.RING_BLOCK
# ladder [1, 2, 8]: a lane fills two rows of a dispatch under the full width
ENGINE_CFG = EngineConfig(max_slots=8, kv_block_size=8, max_model_len=128,
                          prefill_chunk=16, decode_steps=4, top_logprobs=5)


@pytest.fixture(scope="module")
def cfg():
    return config_from_card(card(SHAPE), jnp.float32)


def seeded_params(cfg, seed=3):
    """Seeded weights, the head norms' and the four layer norms' too (ones as
    published would hide a norm that is not applied)."""
    made = trinity.init_params(jax.random.PRNGKey(seed), cfg)
    layers = []
    for i, lp in enumerate(made["layers"]):
        names = ("q_norm", "k_norm", "in_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm")
        keys = jax.random.split(jax.random.PRNGKey(100 + i), len(names))
        layers.append({**lp, **{n: 1.0 + 0.3 * jax.random.normal(k, lp[n].shape)
                                for n, k in zip(names, keys)}})
    return {**made, "layers": tuple(layers)}


@pytest.fixture(scope="module")
def params(cfg):
    return seeded_params(cfg)


@pytest.fixture(scope="module")
def engine(cfg, params):
    eng = JaxServingEngine(cfg, params, ENGINE_CFG)
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def run():
    loop = asyncio.new_event_loop()
    yield loop.run_until_complete
    loop.close()


def test_the_published_card_is_the_cut_the_issue_states():
    shape = published_shape("afmoe")
    c = config_from_card(card(shape))
    assert c.layer_types == ("sliding_attention",) * 3 + ("full_attention", "sliding_attention")
    assert (c.num_layers, c.num_dense_layers, c.num_experts, c.num_experts_published) == (5, 1, 32, 256)
    assert (c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim) == (3072, 48, 8, 128)
    assert (c.intermediate_size, c.moe_intermediate_size, c.num_experts_per_tok) == (12288, 3072, 4)
    assert (c.sliding_window, c.ring_positions, c.vocab_size) == (4096, 4112, 25024)
    assert c.moe_renormalize and c.routed_scaling_factor == 2.448 and c.mup_enabled
    assert module_for(c) is trinity


# -- the step programs against the reference ----------------------------------

def dispatch_rows(cfg, params, dispatches, rows=2, slots=4, mb=16, n_decode=3, salt=None, c=16,
                  state=None):
    """Chunk dispatches of ``rows`` rows of ``c`` positions over ``slots``
    slots, then ``n_decode`` teacher-forced decode steps of every slot fed, off
    the pages and rings the dispatches left. A dispatch is a list of its rows
    in order, ``(slot, n)`` = the slot's next ``n`` prompt tokens (a lane's
    rows of one dispatch are its successive pieces) or ``None`` = a padding
    row; the rows left are padding. The k-th slot fed has blocks ``1 + k * mb``
    onwards; every ring starts stale (7.0 everywhere: what a slot's last
    request left). Returns ({slot: (its tokens, logits ``[prompt + n_decode,
    V]``)}, state, cache, the dispatches' counters, the decode steps')."""
    bs = 8
    fed = list(dict.fromkeys(row[0] for d in dispatches for row in d if row))
    length = {slot: sum(row[1] for d in dispatches for row in d if row and row[0] == slot) for slot in fed}
    toks_of = {slot: np.asarray(prompt_of(length[slot] + n_decode, salt=salt or slot), np.int32) for slot in fed}
    table = {slot: 1 + k * mb + np.arange(mb, dtype=np.int32) for k, slot in enumerate(fed)}
    cache = trinity.make_kv_cache(cfg, 1 + len(fed) * mb, bs)
    if state is None:
        state = jax.tree.map(lambda a: a + 7.0, trinity.make_slot_state(cfg, slots))
    at, got, sums = dict.fromkeys(fed, 0), {slot: [] for slot in fed}, []
    chunk = chunk_program(trinity, cfg)
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
                got[row[0]].append(np.asarray(trinity.lm_head(params, cfg, h[r, :row[1]]), np.float32))
        sums.append(dict(zip(trinity.COUNTERS, np.asarray(counted).tolist())))
    if not n_decode:
        return {slot: (toks_of[slot], np.concatenate(got[slot])) for slot in fed}, state, cache, sums, None
    lanes_tables = np.zeros((slots, mb), np.int32)
    toks, pos = np.zeros((slots,), np.int32), np.full((slots,), -1, np.int32)
    forcing = np.zeros((slots, bs * mb), np.int32)  # a table's positions wide: one program a geometry
    for slot in fed:
        lanes_tables[slot], toks[slot], pos[slot] = table[slot], toks_of[slot][length[slot]], length[slot]
        forcing[slot, :len(toks_of[slot])] = toks_of[slot]
    out = decode_program(trinity, cfg, n_decode, bs * mb - 1)(  # teacher forcing: each sequence's own next token
        params, jnp.asarray(toks), jnp.asarray(pos), cache, jnp.asarray(lanes_tables), state, jnp.asarray(forcing))
    counted = dict(zip(trinity.COUNTERS, np.asarray(out[6]).tolist()))
    assert [int(out[1][slot]) for slot in fed] == [length[slot] + n_decode for slot in fed]
    decoded = np.asarray(out[3], np.float32)
    return ({slot: (toks_of[slot], np.concatenate(got[slot] + [decoded[:, slot]])) for slot in fed},
            out[5], out[4], sums, counted)


def prefill_then_decode(cfg, params, chunks=(16, 16, 16, 9), **how):
    """A prompt fed a chunk a dispatch into slot 2 of 4 and (``n_decode``)
    decoded: (tokens, logits ``[sum(chunks) + n_decode, V]``)."""
    got, *_ = dispatch_rows(cfg, params, [[(2, n)] for n in chunks], salt=len(chunks), **how)
    return got[2]


def i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def reference_of(params, tokens, shape=SHAPE):
    return np.asarray(reference_program(ref, shape)(params, jnp.asarray(tokens), jnp.arange(len(tokens))))


def a_chunk_a_dispatch(*chunks):
    return dict(dispatches=[[(2, n)] for n in chunks], salt=len(chunks))


# (how `dispatch_rows` is called: a dispatch is its rows, (slot, tokens) each). Every prompt is
# longer than a ring's 48 positions, so every window layer wraps; every one is longer than the
# window's 32, so the mask binds in the ring, and at the third piece of a dispatch in the fresh keys
LAYOUTS = {
    "a_chunk_a_dispatch": a_chunk_a_dispatch(16, 16, 16, 16, 9),
    "a_short_first_chunk": a_chunk_a_dispatch(7, 16, 16, 16, 14),
    # a lane's successive pieces in consecutive rows of ONE dispatch, beside another lane's
    "two_pieces_a_dispatch": dict(dispatches=[
        [(2, 16), (2, 16)], [(2, 16), (2, 16)], [(0, 16), (2, 5)], [(0, 16), (0, 16)], [(0, 16), (0, 3)]]),
    # three pieces fill a ring to the entry: the third row's queries see the first row's keys
    # through the window's mask alone
    "three_pieces_a_dispatch": dict(rows=3, dispatches=[
        [(1, 16), (1, 16), (1, 16)], [(3, 9), (1, 16), (1, 12)], [(3, 16), (3, 16), (3, 16)], [(3, 2)]]),
    # the full width (4 rows over 4 slots: `FULL_WIDTH_TAKES_ROWS`): a lane's three pieces, a whole
    # ring of positions, beside other lanes' single rows; slots 1 and 0 pass their rings' ends inside
    # the second and the fourth dispatch
    "pieces_at_the_full_width": dict(rows=4, dispatches=[
        [(1, 16), (1, 16), (1, 16), (3, 9)], [(3, 16), (1, 16), (1, 12), (0, 16)],
        [(3, 16), (3, 16), (0, 16), (0, 16)], [(3, 2), (0, 16), (0, 16), (0, 3)]]),
}


@pytest.mark.parametrize("layouts", [
    ("a_chunk_a_dispatch", "a_short_first_chunk", "two_pieces_a_dispatch"), ("three_pieces_a_dispatch",),
    ("pieces_at_the_full_width",)],
    ids=["dispatches_of_two_rows", "dispatches_of_three_rows", "dispatches_of_the_full_width"])
def test_chunked_prefill_then_decode_agrees_with_the_plain_reference(cfg, params, layouts):
    """A prompt fed in chunks whose boundaries lie inside it, each reading the
    pages and the rings the last one left (keys rotated at their own positions,
    past the ring's end too), then three decode steps off the same caches,
    against the reference's one pass over the whole sequence: logits, at every
    position. The other slots' rings and the other pages stay as they were. Five
    chunkings (``LAYOUTS``), those of one geometry in one case: their step
    programs compile once."""
    for layout in layouts:
        check_a_layout(cfg, params, layout)


def check_a_layout(cfg, params, layout):
    how = {"rows": 2, "slots": 4, **LAYOUTS[layout]}
    got, state, cache, sums, decoded = dispatch_rows(cfg, params, **how)
    for slot, (tokens, logits) in got.items():
        assert len(tokens) > RING
        np.testing.assert_allclose(logits, reference_of(params, tokens), atol=ATOL, err_msg=f"slot {slot}")
    idle = tuple(i for i in range(how["slots"]) if i not in got)
    for leaf in jax.tree.leaves(state):  # the slots no row fed, of every window layer: untouched
        assert leaf.shape == (how["slots"], 2, RING, 16)
        assert float(leaf[idle, :].min()) == float(leaf[idle, :].max()) == 7.0
    assert not np.asarray(cache["k"][:, 0]).any() and cache["k"].shape[0] == N_FULL
    for d, counted in zip(how["dispatches"], sums):
        tokens = sum(n for _, n in d)
        assert counted["swa_layer_calls"] == N_WINDOW and counted["full_layer_calls"] == N_FULL
        assert counted["moe_layer_calls"] == N_EXPERT_LAYERS
        assert counted["moe_routed_pairs"] == 2 * N_EXPERT_LAYERS * tokens >= counted["moe_held_rows"]
        assert (counted["swa_history_positions_live"] <= counted["swa_history_positions_whole"]
                and counted["swa_history_positions_live"] <= counted["swa_history_positions_read"])
    # a decode lane reads its ring, 48 entries a window layer and step, whatever lies behind it
    assert decoded["swa_history_positions_read"] == 3 * N_WINDOW * RING * len(got)
    assert decoded["swa_history_positions_read"] < decoded["swa_history_positions_whole"]
    # ... of which the window's, less the steps' own keys in hand
    assert decoded["swa_history_positions_live"] == N_WINDOW * len(got) * (31 + 30 + 29)


def test_a_chunk_of_more_rows_is_taken_in_groups_as_far_as_its_last_row(cfg, params):
    """Sixteen rows at the full width of sixteen slots: two groups of eight. A
    dispatch whose rows end in the first group computes that group alone, and
    every row gets what the reference gives its sequence, in either group."""
    few = [[(s, n) for s in (2, 5)] for n in (16, 16, 16, 9)]
    got, _, _, sums, _ = dispatch_rows(cfg, params, few, rows=16, slots=16, n_decode=0)
    np.testing.assert_allclose(got[5][1], reference_of(params, got[5][0]), atol=ATOL)
    assert all(counted["swa_layer_calls"] == N_WINDOW for counted in sums)  # one group, not two
    many = [[(s, n) for s in range(12)] for n in (16, 16, 16, 9)]
    got, _, _, sums, _ = dispatch_rows(cfg, params, many, rows=16, slots=16, n_decode=0)
    for slot in (2, 11):  # a row of the first group, and one of the second
        np.testing.assert_allclose(got[slot][1], reference_of(params, got[slot][0]), atol=ATOL)
    assert all(counted["swa_layer_calls"] == 2 * N_WINDOW for counted in sums)


@pytest.mark.parametrize("what", ["rotation_in_the_full_layer", "no_rotation_in_a_window_layer", "no_gate",
                                  "no_head_norms", "bfloat16_rings"])
def test_a_wrong_or_coarser_program_fails_the_tolerance(cfg, params, monkeypatch, what):
    """What ATOL is there to catch: the served path (a prompt of 57 tokens in
    four chunks, through the ring's wrap) with a fault put into the PROGRAM is
    off the reference by 30 x ATOL and more: a full layer that rotates or a
    window layer that does not, attended values without their gate, q and k
    without their head norms, rings in bfloat16."""
    how = {}
    if what == "rotation_in_the_full_layer":
        project = trinity._project
        patched(monkeypatch, trinity, "_project", lambda lp, c, kind, a, pos: project(lp, c, trinity.WINDOW, a, pos))
    elif what == "no_rotation_in_a_window_layer":
        patched(monkeypatch, trinity, "apply_rope", lambda x, pos, theta: x)
    elif what == "no_gate":
        project = trinity._project
        patched(monkeypatch, trinity, "_project",
                lambda *a: (lambda q, k, v, gate: (q, k, v, jnp.ones_like(gate)))(*project(*a)))
    elif what == "no_head_norms":
        norm = trinity.rms_norm
        patched(monkeypatch, trinity, "rms_norm", lambda x, w, eps: x if x.ndim == 4 else norm(x, w, eps))
    else:
        how["state"] = jax.tree.map(lambda a: a.astype(jnp.bfloat16), trinity.make_slot_state(cfg, 4))
    tokens, got = prefill_then_decode(cfg, params, n_decode=0, **how)
    assert np.abs(got - reference_of(params, tokens)).max() > 30 * ATOL


def test_a_disagreement_on_a_number_fails_the_tolerance(cfg, params):
    """The same served path against a reference made to DISAGREE with it on
    one number, which is the same disagreement as a program that has it wrong
    and compiles no step program: the window's mask one position short or long,
    the chosen scores not renormalised (``route_norm``) or not scaled
    (``route_scale`` 1), embeddings without ``sqrt(hidden)``, a choice made under
    another selection bias. Each is apart by 30 x ATOL and more."""
    tokens, got = prefill_then_decode(cfg, params, n_decode=0)
    np.testing.assert_allclose(got, reference_of(params, tokens), atol=ATOL)
    for change in ({"sliding_window": WINDOW - 1}, {"sliding_window": WINDOW + 1}, {"route_norm": False},
                   {"route_scale": 1.0}, {"mup_enabled": False}):
        off = np.abs(got - reference_of(params, tokens, {**SHAPE, **change})).max()
        assert off > 30 * ATOL, (change, off)
    # a bias as large as the scores' spread: it moves choices in a prompt of 57 tokens
    biased = {**params, "layers": tuple(
        {**lp, "e_bias": 0.5 * jnp.sign(lp["e_bias"])} if "e_bias" in lp else lp for lp in params["layers"])}
    assert np.abs(got - reference_of(biased, tokens)).max() > 30 * ATOL


def test_the_shares_of_eight_chips_add_up_to_the_uncut_expert_layer(cfg, params):
    """The cut is one chip's share of an expert layer over four chips here (4
    of 16 experts a chip; eight chips at the published 32 of 256): the routed
    parts that the shares ``first_expert`` 0, 4, 8, 12 give, each from its own
    four experts' matrices, with the shared expert that every chip computes
    alike counted once, add up to what the uncut reference gives for the whole
    layer (the model-configs guide, section 4), in the program and in the
    reference alike."""
    whole_cfg = dataclasses.replace(cfg, num_experts=16)
    lp = trinity.init_params(jax.random.PRNGKey(11), whole_cfg)["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(12), (3, 20, 64), jnp.float32)
    whole_shape = {**SHAPE, "num_experts": 16}
    flat = x.reshape(60, 64)
    whole = ref.shared_part(lp, flat) + ref.routed_part(lp, whole_shape, flat)
    valid = jnp.ones((3, 20), bool)
    shared = np.asarray(ref.shared_part(lp, flat))
    of_reference, of_program = shared.copy(), shared.copy()
    for first in range(0, 16, 4):
        share = {**lp, **{w: lp[w][first:first + 4] for w in ("w_gate", "w_up", "w_down")}}
        of_reference += np.asarray(ref.routed_part(share, {**SHAPE, "first_expert": first}, flat))
        y, stats = trinity.feed_forward(share, dataclasses.replace(cfg, first_expert=first), x, valid)
        of_program += np.asarray(y).reshape(60, 64) - shared
        assert int(stats[3]) == 2 * 60 and 0 < int(stats[1]) < 2 * 60  # routed pairs; those held here
    np.testing.assert_allclose(of_reference, np.asarray(whole), atol=2e-5)
    np.testing.assert_allclose(of_program, np.asarray(whole), atol=2e-5)


def test_a_ring_holds_the_window_and_one_block_whatever_the_model_length(cfg):
    """The window layers' cache is ``slots x window layers x (sliding_window +
    one block)`` positions and nothing of ``--max-model-len`` is in it: at the
    cell's shape 8 x 4 x 4,112 positions of 8 KV heads of 128, float32."""
    state = jax.eval_shape(lambda: trinity.make_slot_state(cfg, 8))
    assert set(state) == {"k", "v"} and len(state["k"]) == N_WINDOW
    assert all(a.shape == (8, 2, RING, 16) and a.dtype == jnp.float32 for a in jax.tree.leaves(state))
    published = config_from_card(card(published_shape("afmoe")))
    state = jax.eval_shape(lambda: trinity.make_slot_state(published, 8))
    assert all(a.shape == (8, 8, 4096 + 16, 128) for a in jax.tree.leaves(state))
    assert sum(a.size * 4 for a in jax.tree.leaves(state)) == 1_077_936_128
    pool = jax.eval_shape(lambda: trinity.make_kv_cache(published, 6144, 16))
    assert pool["k"].shape == (1, 6144, 16, 8, 128) and pool["k"].dtype == jnp.float32


def test_held_positions_names_what_an_entry_holds():
    held = np.asarray(ring.held_positions(jnp.asarray([0, 5, 48, 50, 107, -1]), 48))
    assert (held[0] < 0).all() and (held[5] < 0).all()
    assert held[1, :5].tolist() == [0, 1, 2, 3, 4] and (held[1, 5:] < 0).all()
    assert held[2].tolist() == list(range(48))
    assert held[3].tolist() == [48, 49] + list(range(2, 48))
    assert sorted(held[4].tolist()) == list(range(59, 107)) and all(p % 48 == e for e, p in enumerate(held[4]))
    assert ring.ring_tile(4096) == 512 and ring.ring_tile(32) == 32 and ring.ring_tile(48) == 16


# -- the refusals ---------------------------------------------------------------

@pytest.mark.parametrize("change, named", [
    ({"score_func": "softmax"}, "score_func"), ({"n_group": 2}, "n_group"), ({"topk_group": 2}, "topk_group"),
    ({"num_expert_groups": 4}, "num_expert_groups"), ({"num_limited_groups": 2}, "num_limited_groups"),
    ({"num_shared_experts": 2}, "num_shared_experts"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4.0}}, "rope_scaling"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"layer_types": ["sliding_attention"] * 4 + ["linear_attention"]}, "layer_types"),
    ({"layer_types": ["sliding_attention"] * 4}, "layer_types"),
    ({"num_attention_heads": 1}, "num_key_value_heads"),
])
def test_what_the_module_does_not_compute_is_refused_by_name(change, named):
    with pytest.raises(ValueError, match=f"model_type 'afmoe' with {named} = "):
        config_from_card(card({**SHAPE, **change}))


def test_the_parent_s_refusal_of_the_model_type_is_gone_and_other_expert_cards_still_meet_it():
    assert type(config_from_card(card(SHAPE))).__name__ == "TrinityConfig"
    with pytest.raises(ValueError, match="no module here runs it"):
        config_from_card(card({**SHAPE, "model_type": "afmoe2"}))


@pytest.mark.parametrize("slots", [8, 4], ids=["under_the_full_width", "at_the_full_width"])
def test_a_dispatch_that_would_write_one_ring_entry_twice_is_refused_where_it_is_traced(
        cfg, params, monkeypatch, slots):
    """A lane's rows of one dispatch are the engine's to deal and the module's
    to bound (``lane_rows_most``: 3 rows of 16 in a ring of 48). A bound the
    ring cannot hold, here four rows, is refused where a program of four rows
    is traced, at the full width as under it."""
    assert trinity.lane_rows_most(cfg, 16) == 3
    patched(monkeypatch, trinity, "lane_rows_most", lambda config, width: 4)
    with pytest.raises(ValueError, match="a lane's 4 rows of 16 positions pass the 48 positions a window layer"):
        dispatch_rows(cfg, params, [[(2, 16)]], rows=4, slots=slots)


def test_a_row_wider_than_a_ring_is_refused_and_the_served_geometries_trace(cfg):
    """One row is the least a lane takes: a chunk wider than the ring is
    refused whatever is dealt. What is served traces: the cell's 8 slots and 64
    slots, at the CLI's chunk of 128 and the published window, every rung, the
    full width among them (64 rows of 128 would pass a ring of 4,112 if one
    lane filled them; the deal gives a lane 32)."""
    def lower(c, rows, slots, width):
        made = jax.eval_shape(lambda: (trinity.init_params(jax.random.PRNGKey(0), c),
                                       trinity.make_kv_cache(c, 9, 16), trinity.make_slot_state(c, slots)))
        return jax.eval_shape(lambda p, cache, st, t, pos, tb, ln: trinity.forward_chunk(
            p, c, t, pos, cache, tb, st, ln), *made, i32(rows, width), i32(rows, width), i32(rows, 8), i32(rows))

    with pytest.raises(ValueError, match="a lane's 1 rows of 64 positions pass the 48 positions"):
        lower(cfg, 2, 4, 64)
    published = dataclasses.replace(config_from_card(card(published_shape("afmoe"))), vocab_size=256)
    assert trinity.lane_rows_most(published, 128) == 32
    for slots in (8, 64):
        for rows in chunk_row_ladder(slots):
            assert lower(published, rows, slots, 128)[0].shape == (rows, 128, 3072)


# -- the engine -----------------------------------------------------------------

def greedy_of_the_reference(params, prompt, answered):
    """Whether ``answered`` is what the reference chooses after ``prompt``,
    token by token (teacher-forced on the answer itself)."""
    seq = jnp.asarray(list(prompt) + list(answered[:-1]), jnp.int32)
    want = np.asarray(reference_program(ref, SHAPE)(params, seq, jnp.arange(len(prompt) - 1, len(seq))))
    return want.argmax(-1).tolist()


def test_the_engine_serves_what_the_reference_chooses_past_the_window_and_in_a_used_slot(engine, params):
    """Through ``JaxServingEngine``, ONE engine for all of it (its programs
    compile once, on the worker this test lands on). (1) Admission, a prompt of
    75 tokens in chunk dispatches of one and two rows (the ladder [1, 2, 8]),
    pipelined decode dispatches of 4 steps past 90 positions, sampling and
    log-probabilities: the reference's greedy tokens and their log-probabilities.
    (2) The same prompt again: its pages are in the prefix cache, the rings that
    go with them are not, so the hit is declined, it prefills from position 0,
    and the answer is the first one's. (3) Eight long requests fill every slot
    and leave their rings full; a short one admitted into a used slot, beside
    another that still decodes, answers as the reference does: nothing resets a
    ring, and the mask lets nothing of the last request through. (4) What would
    hand pages over without the rings is refused by name."""
    prompt = prompt_of(75)
    toks, lps, finish = served(engine, prompt, 18, logprobs=5)
    seq = jnp.asarray(prompt + toks[:-1], jnp.int32)
    want = np.asarray(reference_program(ref, SHAPE)(params, seq, jnp.arange(len(prompt) - 1, len(seq))))
    assert toks == want.argmax(-1).tolist() and len(toks) == 18 and finish == "length"
    logp = want - np.log(np.exp(want).sum(-1, keepdims=True))
    np.testing.assert_allclose(lps, logp[np.arange(18), toks], atol=ATOL)
    snap = engine.metrics_snapshot()
    assert set(trinity.COUNTERS) <= set(snap) and len(trinity.COUNTERS) == 11
    assert snap["swa_layer_calls"] > 0 and snap["full_layer_calls"] > 0 and snap["moe_layer_calls"] > 0
    # the lane passed the window: the window layers read less than its whole history holds
    assert 0 < snap["swa_history_positions_live"] < snap["swa_history_positions_read"]
    assert snap["swa_history_positions_read"] < snap["swa_history_positions_whole"]
    assert trinity.LANE_TAKES_ROWS and engine._lane_rows and engine._chunk_rungs == [1, 2, 8]
    assert snap["chunk_rows_live"] > snap["chunk_lanes_fed"] > 0  # a lane took two rows of a dispatch
    assert set(engine.cache) == {"k", "v"} and engine.cache["k"].shape == (1, engine.num_blocks, 8, 2, 16)
    assert all(a.shape == (8, 2, RING, 16) for a in jax.tree.leaves(engine.slot_state))

    declined = engine.prefix_hits_declined
    again = submit(engine, prompt, 18)
    step(engine)
    assert again.alloc.cached_tokens == 0 and again.alloc.declined_tokens == 72
    run_out(engine)
    assert answer(again)[0] == toks and engine.prefix_hits_declined == declined + 1

    for salt in range(8):
        submit(engine, prompt_of(70 + salt, salt=salt), 6)
    run_out(engine)
    long_one = submit(engine, prompt_of(60, salt=5), 24)
    for _ in range(6):
        step(engine)
    assert long_one.slot is not None
    late = submit(engine, prompt_of(21, salt=9), 8)
    run_out(engine)
    short = answer(late)[0]
    assert len(short) == 8 and short == greedy_of_the_reference(params, prompt_of(21, salt=9), short)

    with pytest.raises(StateNotPortable, match="TrinityConfig keeps state per slot"):
        engine._refuse_for_state("a migration")


def test_five_lanes_admitted_at_once_share_the_eight_rows_of_the_full_width(engine, params):
    """Five prompts of five and six chunks admitted in one step: more lanes than
    the second rung holds, so the dispatches are the full width's, whose three
    spare rows go to the oldest lanes' further pieces (``chunk_rows_of`` with
    the module's ``FULL_WIDTH_TAKES_ROWS``), a lane never more than the ring's
    three rows (``lane_rows_most``). Every answer is the reference's greedy one."""
    assert trinity.FULL_WIDTH_TAKES_ROWS and engine._top_takes_rows and engine._lane_rows_most == 3
    before = engine.metrics_snapshot()
    wide = engine.chunk_dispatches_by_rows.get(8, 0)
    prompts = [prompt_of(70 + 3 * i, salt=20 + i) for i in range(5)]
    seqs = [submit(engine, p, 5) for p in prompts]
    def prefilled(seq):
        return len(seq.prompt) if seq.prefill_pos is None else seq.prefill_pos

    rows_a_step = set()  # what a lane took of one dispatch, over the run
    while busy(engine):
        was = [prefilled(s) for s in seqs]
        step(engine)
        rows_a_step |= {-(-(prefilled(s) - at) // 16) for s, at in zip(seqs, was)}
    assert max(rows_a_step) == 3
    for prompt, seq in zip(prompts, seqs):
        toks = answer(seq)[0]
        assert len(toks) == 5 and toks == greedy_of_the_reference(params, prompt, toks)
    snap = engine.metrics_snapshot()
    rows, lanes = (snap[k] - before[k] for k in ("chunk_rows_live", "chunk_lanes_fed"))
    assert rows == sum(-(-len(p) // 16) for p in prompts) == 26
    # 26 rows in three dispatches of the full width (8, 8, 8: fifteen lane-dispatches where
    # one row a lane took six of five) and one of the second rung for the two rows left
    assert engine.chunk_dispatches_by_rows[8] - wide == 3 and rows > lanes
    assert snap["prompt_dispatches"] - before["prompt_dispatches"] < 5 * 5


def test_an_engine_of_64_slots_deals_a_lane_no_more_rows_than_a_ring_holds(cfg, params):
    """Ladder [8, 16, 64]: at 64 rows of 16 a lane could be dealt 47 where a
    ring holds 3. Eighteen prompts of five chunks at once (more lanes than the
    16-row rung holds): the full width, three rows a lane, twice."""
    eng = JaxServingEngine(cfg, params, dataclasses.replace(ENGINE_CFG, max_slots=64))
    try:
        assert eng._chunk_rungs == [8, 16, 64] and eng._top_takes_rows and eng._lane_rows_most == 3
        prompts = [prompt_of(66 + i % 3, salt=40 + i) for i in range(18)]
        seqs = [submit(eng, p, 3) for p in prompts]
        step(eng)
        assert [s.prefill_pos for s in seqs] == [48] * 18 and eng.chunk_dispatches_by_rows == {64: 1}
        run_out(eng)
        assert eng.chunk_dispatches_by_rows == {64: 2} and eng.chunk_rows_live == 18 * 5
    finally:
        eng.close()
    for prompt, seq in list(zip(prompts, seqs))[::7]:
        toks = answer(seq)[0]
        assert len(toks) == 3 and toks == greedy_of_the_reference(params, prompt, toks)


def test_preemption_recomputes_past_the_window(cfg, params, run):
    """Out of blocks, a lane past the window is preempted and recomputed from
    position 0 into the same rings: greedy output as the reference's."""
    tight = dataclasses.replace(ENGINE_CFG, max_slots=2, max_model_len=96, num_kv_blocks=14)
    eng = JaxServingEngine(cfg, params, tight)
    try:
        async def both():
            return await asyncio.gather(collect(eng, prompt_of(40, 1), max_tokens=30),
                                        collect(eng, prompt_of(40, 2), max_tokens=30))

        got = [r[0] for r in run(both())]
        assert eng.preemptions > 0
    finally:
        eng.close()
    for salt, toks in zip((1, 2), got):
        assert len(toks) == 30 and toks == greedy_of_the_reference(params, prompt_of(40, salt), toks)


def test_the_step_programs_carry_the_two_scopes(cfg):
    """``swa`` and ``full_attn`` around the two kinds of attention, ``moe``
    around the expert layer: what a compile report and a profile tell apart."""
    c, mb = 16, 16
    made = jax.eval_shape(lambda: (trinity.init_params(jax.random.PRNGKey(0), cfg),
                                   trinity.make_kv_cache(cfg, 33, 8), trinity.make_slot_state(cfg, 4)))
    text = jax.jit(lambda p, cache, st, t, pos, tb, ln: trinity.forward_chunk(
        p, cfg, t, pos, cache, tb, st, ln)).lower(
        *made, i32(2, c), i32(2, c), i32(2, mb), i32(2)).as_text(debug_info=True)
    for scope in ("swa", "full_attn", "moe"):
        assert f"/{scope}/" in text, scope
