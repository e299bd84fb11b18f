"""Mellum 2 (``model_type: mellum``) on the served path, at a tiny size on the
CPU (hidden 64, two periods of window, window, window, full; 8 experts, 2 a
token; eight query heads over four key/value heads of 16; ``sliding_window``
32, so a window layer's ring holds 48 positions a slot and EVERY test crosses
the window and wraps the ring), on ONE device and on a mesh of four virtual
CPU devices (``tp=4``: two query heads, one KV head and two experts a shard).

The program (``models/mellum.py``) is held against the benchmark's plain
reference (``benchmark/reference_mellum.py``: one sequence, the whole prompt at
once, naive masked attention, every expert for every token, both rotary tables
written out, no cache, no shards); the engine against both, on the mesh against
the one-device engine too, and against the refusals a model with per-slot state
owes whatever would hand its pages over without it.
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_mellum as ref
from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
from dynamo_tpu.engine_jax.weights import config_from_card
from dynamo_tpu.kv.pages import StateNotPortable
from dynamo_tpu.models import mellum, module_for
from dynamo_tpu.ops import moe
from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

from .step_programs import (  # noqa: F401  (highest_precision: autouse, for this file's tests)
    answer, card, chunk_program, collect, decode_program, highest_precision, patched, prompt_of,
    published_shape, reference_program, run_out, served, step, submit, teacher_forcing,
)

# float32 on the CPU at the highest matmul precision on both sides: the program
# and the reference differ by the order of their sums alone (flash partials
# over tiles of a ring, sorted rows of an expert, the shards' partial sums):
# 2e-4 on logits of magnitude 3 is what the other modules are allowed for the
# same reason (measured here: 5e-6, on the mesh as on one device). Each fault a
# test below puts in moves a logit by 30 x that and more.
ATOL = 2e-4
WINDOW = 32
ROPE = {"full_attention": {"rope_type": "yarn", "rope_theta": 10000, "factor": 16,
                           "original_max_position_embeddings": 64, "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000}}
SHAPE = {
    "model_type": "mellum", "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 8,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2, "mlp_layer_types": ["sparse"] * 8,
    "sliding_window": WINDOW, "use_sliding_window": True, "max_window_layers": 0,
    "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 16, "attention_bias": False,
    "moe_intermediate_size": 32, "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "hidden_act": "silu", "rms_norm_eps": 1e-6, "rope_parameters": ROPE, "tie_word_embeddings": False,
    "vocab_size": 96, "max_position_embeddings": 131072,
}
PERIODS, N_WINDOW, N_FULL, LAYERS = 2, 6, 2, 8
RING = WINDOW + mellum.RING_BLOCK
# ladder [1, 2, 8]: a lane fills two rows of a dispatch under the full width
ENGINE_CFG = EngineConfig(max_slots=8, kv_block_size=8, max_model_len=128,
                          prefill_chunk=16, decode_steps=4, top_logprobs=5)


def reshaped(**rope_changes):
    """``SHAPE`` with keys of a section of ``rope_parameters`` changed: ``full={...}``."""
    return {**SHAPE, "rope_parameters": {
        **ROPE, "full_attention": {**ROPE["full_attention"], **rope_changes.get("full", {})}}}


@pytest.fixture(scope="module")
def cfg():
    return config_from_card(card(SHAPE), jnp.float32)


@pytest.fixture(scope="module")
def params(cfg):
    """Seeded weights, the head norms' and the two layer norms' too (ones as
    published would hide a norm that is not applied)."""
    made = mellum.init_params(jax.random.PRNGKey(3), cfg)
    names = ("q_norm", "k_norm", "in_norm", "mlp_norm")
    keys = jax.random.split(jax.random.PRNGKey(100), len(names))
    return {**made, "layers": {**made["layers"], **{
        n: 1.0 + 0.3 * jax.random.normal(k, made["layers"][n].shape) for n, k in zip(names, keys)}}}


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(MeshConfig(tp=4))


@pytest.fixture(scope="module")
def sharded(cfg, params, mesh):
    return jax.device_put(params, mellum.param_shardings(cfg, mesh))


@pytest.fixture(scope="module")
def engine(cfg, params):
    eng = JaxServingEngine(cfg, params, ENGINE_CFG)
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def mesh_engine(cfg, sharded, mesh):
    eng = JaxServingEngine(cfg, sharded, ENGINE_CFG, mesh=mesh)
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def run():
    loop = asyncio.new_event_loop()
    yield loop.run_until_complete
    loop.close()


def test_the_published_card_is_served_whole():
    shape = published_shape("mellum")
    c = config_from_card(card(shape))
    assert c.layer_types == (("sliding_attention",) * 3 + ("full_attention",)) * 7
    assert (c.num_layers, c.num_periods, c.num_experts, c.num_experts_per_tok) == (28, 7, 64, 8)
    assert (c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim) == (2304, 32, 4, 128)
    assert (c.moe_intermediate_size, c.sliding_window, c.ring_positions, c.vocab_size) == (896, 1024, 1040, 98304)
    assert (c.rope_theta, c.yarn_factor, c.yarn_original_positions) == (500000.0, 16.0, 8192)
    assert c.attention_factor == 1.2772588722239782 == 0.1 * np.log(16.0) + 1.0
    assert module_for(c) is mellum and mellum.SERVES_ON_MESH
    # YaRN's blend at these keys: the plain frequency up to pair 18, a sixteenth of it from pair 35
    plain = [500000.0 ** (-2.0 * j / 128) for j in range(64)]
    table = c.full_inv_freq
    assert table[:19] == tuple(plain[:19]) and table[18] != table[19] / 1.0 and table[19] < plain[19]
    np.testing.assert_allclose(table[35:], [f / 16 for f in plain[35:]], rtol=1e-12)
    np.testing.assert_allclose(table, ref.table(shape, "full_attention")[0], rtol=1e-12)
    assert ref.table(shape, "sliding_attention") == (plain, 1.0)


# -- the step programs against the reference --------------------------------------

def dispatch_rows(cfg, params, dispatches, rows=2, slots=4, mb=16, n_decode=3, c=16, state=None,
                  mesh=None):
    """``tests/test_trinity.py:dispatch_rows`` for this module, on one device
    or on ``mesh``: chunk dispatches of ``rows`` rows of ``c`` positions over
    ``slots`` slots, then ``n_decode`` teacher-forced decode steps of every slot
    fed, off the pages and rings the dispatches left. A dispatch is a list of
    its rows in order, ``(slot, n)`` = the slot's next ``n`` prompt tokens or
    ``None`` = a padding row. Every ring starts stale (7.0 everywhere). Returns
    ({slot: (its tokens, logits)}, state, cache, the dispatches' counters, the
    decode steps')."""
    bs = 8
    fed = list(dict.fromkeys(row[0] for d in dispatches for row in d if row))
    length = {slot: sum(row[1] for d in dispatches for row in d if row and row[0] == slot) for slot in fed}
    toks_of = {slot: np.asarray(prompt_of(length[slot] + n_decode, salt=slot), np.int32) for slot in fed}
    table = {slot: 1 + k * mb + np.arange(mb, dtype=np.int32) for k, slot in enumerate(fed)}
    cache = mellum.make_kv_cache(cfg, 1 + len(fed) * mb, bs, mesh=mesh)
    if state is None:
        state = jax.tree.map(lambda a: a + 7.0, mellum.make_slot_state(cfg, slots, mesh=mesh))
    at, got, sums = dict.fromkeys(fed, 0), {slot: [] for slot in fed}, []
    chunk = chunk_program(mellum, cfg, mesh=mesh)
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
                got[row[0]].append(np.asarray(mellum.lm_head(params, cfg, h[r, :row[1]]), np.float32))
        sums.append(dict(zip(mellum.COUNTERS, np.asarray(counted).tolist())))
    if not n_decode:
        return {slot: (toks_of[slot], np.concatenate(got[slot])) for slot in fed}, state, cache, sums, None
    lanes_tables = np.zeros((slots, mb), np.int32)
    toks, pos = np.zeros((slots,), np.int32), np.full((slots,), -1, np.int32)
    forcing = np.zeros((slots, bs * mb), np.int32)
    for slot in fed:
        lanes_tables[slot], toks[slot], pos[slot] = table[slot], toks_of[slot][length[slot]], length[slot]
        forcing[slot, :len(toks_of[slot])] = toks_of[slot]
    out = decode_program(mellum, cfg, n_decode, bs * mb - 1, mesh=mesh)(
        params, jnp.asarray(toks), jnp.asarray(pos), cache, jnp.asarray(lanes_tables), state, jnp.asarray(forcing))
    counted = dict(zip(mellum.COUNTERS, np.asarray(out[6]).tolist()))
    assert [int(out[1][slot]) for slot in fed] == [length[slot] + n_decode for slot in fed]
    decoded = np.asarray(out[3], np.float32)
    return ({slot: (toks_of[slot], np.concatenate(got[slot] + [decoded[:, slot]])) for slot in fed},
            out[5], out[4], sums, counted)


def i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def reference_of(params, tokens, shape=SHAPE):
    return np.asarray(reference_program(ref, shape)(params, jnp.asarray(tokens), jnp.arange(len(tokens))))


def a_chunk_a_dispatch(*chunks):
    return dict(dispatches=[[(2, n)] for n in chunks])


# four chunkings; every prompt is longer than a ring's 48 positions, so every window layer wraps,
# and longer than the window's 32, so the mask binds in the ring and in the fresh keys
LAYOUTS = {
    "a_chunk_a_dispatch": a_chunk_a_dispatch(16, 16, 16, 16, 9),
    "a_short_first_chunk": a_chunk_a_dispatch(7, 16, 16, 16, 14),
    # a lane's successive pieces in consecutive rows of ONE dispatch, beside another lane's
    "two_pieces_a_dispatch": dict(dispatches=[
        [(2, 16), (2, 16)], [(2, 16), (2, 16)], [(0, 16), (2, 5)], [(0, 16), (0, 16)], [(0, 16), (0, 3)]]),
    # the full width (4 rows over 4 slots): a lane's three pieces, a whole ring of positions
    "pieces_at_the_full_width": dict(rows=4, dispatches=[
        [(1, 16), (1, 16), (1, 16), (3, 9)], [(3, 16), (1, 16), (1, 12), (0, 16)],
        [(3, 16), (3, 16), (0, 16), (0, 16)], [(3, 2), (0, 16), (0, 16), (0, 3)]]),
}


@pytest.mark.parametrize("where", ["one_device", "mesh"])
@pytest.mark.parametrize("layouts", [
    ("a_chunk_a_dispatch", "a_short_first_chunk", "two_pieces_a_dispatch"), ("pieces_at_the_full_width",)],
    ids=["dispatches_of_two_rows", "dispatches_of_the_full_width"])
def test_chunked_prefill_then_decode_agrees_with_the_plain_reference(cfg, params, request, layouts, where):
    """A prompt fed in chunks whose boundaries lie inside it, each reading the
    pages and the rings the last one left (keys rotated at their own positions
    by their layer's table, past the ring's end too), then three decode steps
    off the same caches, against the reference's one pass over the whole
    sequence: logits, at every position, on one device and over four shards."""
    on = request.getfixturevalue("mesh") if where == "mesh" else None
    prm = request.getfixturevalue("sharded") if where == "mesh" else params
    for layout in layouts:
        how = {"rows": 2, "slots": 4, **LAYOUTS[layout]}
        got, state, cache, sums, decoded = dispatch_rows(cfg, prm, mesh=on, **how)
        for slot, (tokens, logits) in got.items():
            assert len(tokens) > RING
            np.testing.assert_allclose(logits, reference_of(params, tokens), atol=ATOL, err_msg=f"slot {slot}")
        idle = tuple(i for i in range(how["slots"]) if i not in got)
        for leaf in jax.tree.leaves(state):  # the slots no row fed, of every window layer: untouched
            assert leaf.shape == (PERIODS, 3, how["slots"], 4, RING, 16)
            assert float(leaf[:, :, idle].min()) == float(leaf[:, :, idle].max()) == 7.0
        assert not np.asarray(cache["k"][:, 0]).any() and cache["k"].shape[0] == N_FULL
        for d, counted in zip(how["dispatches"], sums):
            tokens = sum(n for _, n in d)
            assert counted["swa_layer_calls"] == N_WINDOW and counted["full_layer_calls"] == N_FULL
            assert counted["moe_layer_calls"] == LAYERS
            assert counted["moe_routed_pairs"] == 2 * LAYERS * tokens == counted["moe_held_rows"]
            assert counted["moe_pairs_all_shards"] == counted["moe_routed_pairs"]
            if on is None:
                assert counted["moe_pairs_fullest_shard"] == counted["moe_pairs_all_shards"]
                assert counted["exchange_rows"] == 0
            else:  # the fullest of four shards holds a quarter at the least; two all-reduces a layer
                assert counted["moe_pairs_all_shards"] / 4 <= counted["moe_pairs_fullest_shard"] < \
                    counted["moe_pairs_all_shards"]
                assert counted["exchange_rows"] == 2 * LAYERS * how["rows"] * 16
        # a decode lane reads its ring, 48 entries a window layer and step, whatever lies behind it
        assert decoded["swa_history_positions_read"] == 3 * N_WINDOW * RING * len(got)
        assert decoded["swa_history_positions_read"] < decoded["swa_history_positions_whole"]
        assert decoded["exchange_rows"] == (0 if on is None else 3 * 2 * LAYERS * how["slots"])


def prefill(cfg, params, mesh=None, **how):
    """A prompt of 57 tokens in four chunks through the ring's wrap: (tokens, logits)."""
    got, *_ = dispatch_rows(cfg, params, **a_chunk_a_dispatch(16, 16, 16, 9), n_decode=0, mesh=mesh, **how)
    return got[2]


@pytest.mark.parametrize("what", ["the_plain_table_in_a_full_layer", "yarn_s_table_in_a_window_layer",
                                  "no_head_norms", "bfloat16_rings"])
def test_a_wrong_or_coarser_program_fails_the_tolerance(cfg, params, monkeypatch, what):
    """What ATOL is there to catch: the served path with a fault put into the
    PROGRAM is off the reference by 30 x ATOL and more: a full layer rotated by
    the window layers' table or a window layer by the full layers', q and k
    without their head norms, rings in bfloat16."""
    how = {}
    if what == "bfloat16_rings":
        how["state"] = jax.tree.map(lambda a: a.astype(jnp.bfloat16), mellum.make_slot_state(cfg, 4))
    elif what == "no_head_norms":
        norm = mellum.rms_norm
        patched(monkeypatch, mellum, "rms_norm", lambda x, w, eps: x if x.ndim == 4 else norm(x, w, eps))
    else:
        rotated = mellum._rotated
        kind = mellum.WINDOW if what == "the_plain_table_in_a_full_layer" else mellum.FULL
        patched(monkeypatch, mellum, "_rotated", lambda c, _, x, pos: rotated(c, kind, x, pos))
    tokens, got = prefill(cfg, params, **how)
    assert np.abs(got - reference_of(params, tokens)).max() > 30 * ATOL


@pytest.mark.parametrize("what", ["a_sum_over_the_shards_left_out", "the_same_first_expert_on_every_shard"])
def test_a_mesh_program_that_exchanges_wrongly_fails_the_tolerance(cfg, sharded, params, mesh, monkeypatch, what):
    """The same on the mesh: an all-reduce left out (a shard's own part of the
    out-projection and of the experts alone), or every shard taking its two
    experts for ids 0 and 1."""
    if what == "a_sum_over_the_shards_left_out":
        patched(monkeypatch, mellum, "_all_reduce", lambda y, axis: y)
    else:
        patched(monkeypatch, mellum, "_first_held", lambda axis, held: 0)
    tokens, got = prefill(cfg, sharded, mesh=mesh)
    assert np.abs(got - reference_of(params, tokens)).max() > 30 * ATOL


def test_a_disagreement_on_a_number_fails_the_tolerance(cfg, params):
    """The same served path against a reference made to DISAGREE with it on
    one number, which is the same disagreement as a program that has it wrong:
    the window's mask one position short or long, ``attention_factor`` 1, the
    ramp's ``low`` or ``high`` one pair off (``beta_fast`` 3 moves ``low`` from 0
    to 1, ``beta_slow`` 1.5 ``high`` from 3 to 2, at these keys), the chosen
    probabilities not renormalised. Each is apart by 30 x ATOL and more."""
    tokens, got = prefill(cfg, params)
    np.testing.assert_allclose(got, reference_of(params, tokens), atol=ATOL)
    assert ref.table(reshaped(full={"beta_fast": 3}), "full_attention")[0][1] != \
        ref.table(SHAPE, "full_attention")[0][1]
    for change in ({"sliding_window": WINDOW - 1}, {"sliding_window": WINDOW + 1}, {"norm_topk_prob": False},
                   reshaped(full={"attention_factor": 1.0}), reshaped(full={"beta_fast": 3}),
                   reshaped(full={"beta_slow": 1.5})):
        changed = {**SHAPE, **change}
        off = np.abs(got - np.asarray(ref.logits(params, changed, jnp.asarray(tokens),
                                                 jnp.arange(len(tokens))))).max()
        assert off > 30 * ATOL, (change, off)


def test_the_shares_of_four_shards_add_up_to_the_uncut_expert_layer(cfg, params):
    """16 of 64 experts a chip at the published size, 2 of 8 here: the parts
    that ``dropless_experts`` gives for the shards' ids (``first_expert`` 0, 2,
    4, 6; each from its own two experts' matrices out of the layers' stack) add
    up to what the uncut reference gives for the whole layer, and the pairs
    each shard computes are what ``shard_pairs`` says every shard can count."""
    lp = jax.tree.map(lambda a: a[1, 2], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(12), (60, 64), jnp.float32)
    whole = np.asarray(ref.experts(lp, SHAPE, x))
    ids, weights = moe.route_softmax_topk(x, lp["router"], 2, True)
    pairs = np.asarray(moe.shard_pairs(ids, jnp.ones((60,), bool), 2, 4))
    assert pairs.sum() == 120 and pairs.max() < 120
    total = np.zeros_like(whole)
    layer = 1 * 4 + 2  # where the layer lies in a shard's stack of eight layers
    for shard, first in enumerate(range(0, 8, 2)):
        stack = [params["layers"][w][:, :, first:first + 2].reshape(-1, *params["layers"][w].shape[3:])
                 for w in ("w_gate", "w_up", "w_down")]
        y, stats = moe.dropless_experts(x, ids - first, weights, *stack, num_experts_total=8,
                                        stacked_at=jnp.int32(layer), experts_a_layer=2)
        assert int(stats[1]) == pairs[shard] and int(stats[3]) == 120
        total += np.asarray(y)
    np.testing.assert_allclose(total, whole, atol=2e-5)


def test_the_rings_are_one_kv_head_a_shard_whatever_the_model_length(mesh):
    """The window layers' cache is ``slots x window layers x (sliding_window +
    one block)`` positions, nothing of ``--max-model-len`` is in it, and on the
    four-chip mesh a chip holds ONE KV head of it: 16 slots x 21 layers x 1,040
    positions x 128 x float32, twice (K and V)."""
    published = config_from_card(card(published_shape("mellum")))
    state = jax.eval_shape(lambda: mellum.make_slot_state(published, 16))
    assert set(state) == {"k", "v"} and state["k"].shape == (7, 3, 16, 4, 1040, 128)
    assert state["k"].dtype == jnp.float32
    pool, rings = mellum._cache_specs("tp")
    sharding = jax.sharding.NamedSharding(mesh, rings)
    assert sharding.shard_shape(state["k"].shape) == (7, 3, 16, 1, 1040, 128)
    a_chip = 2 * 7 * 3 * 16 * 1040 * 128 * 4
    assert a_chip == 357_826_560 and 4 * a_chip == sum(a.size * 4 for a in jax.tree.leaves(state))
    cache = jax.eval_shape(lambda: mellum.make_kv_cache(published, 6144, 16))
    assert cache["k"].shape == (7, 6144, 16, 4, 128) and cache["k"].dtype == jnp.float32
    assert jax.sharding.NamedSharding(mesh, pool).shard_shape(cache["k"].shape) == (7, 6144, 16, 1, 128)
    # at the tiny size the makers create them so
    made = mellum.make_slot_state(config_from_card(card(SHAPE), jnp.float32), 4, mesh=mesh)
    assert made["k"].sharding.shard_shape(made["k"].shape) == (PERIODS, 3, 4, 1, RING, 16)


def test_a_step_program_s_text_does_not_grow_with_the_depth(cfg, mesh):
    """Both step programs are a scan over the periods: at 28 layers (seven
    periods) the lowered text is as long as it is at 8 (two). And it carries
    the scopes a compile report and a profile tell the layers' parts by: the two
    kinds of attention, the expert layer, and on a mesh the two all-reduces."""
    def lowered(c, what, on=None, debug_info=False):
        made = jax.eval_shape(lambda: (mellum.init_params(jax.random.PRNGKey(0), c),
                                       mellum.make_kv_cache(c, 33, 8), mellum.make_slot_state(c, 4)))
        if what == "chunk":
            return jax.jit(lambda p, cache, st, t, pos, tb, ln: mellum.forward_chunk(
                p, c, t, pos, cache, tb, st, ln, mesh=on)).lower(
                *made, i32(2, 16), i32(2, 16), i32(2, 16), i32(2)).as_text(debug_info=debug_info)
        return jax.jit(lambda p, cache, st, t, pos, tb: mellum.decode(
            p, c, t, pos, cache, tb, st, 2, 127, teacher_forcing(None), None, mesh=on)).lower(
            *made, i32(4), i32(4), i32(4, 16)).as_text()

    text = lowered(cfg, "chunk", mesh, debug_info=True)
    for scope in ("swa", "full_attn", "moe", "moe_exchange", "attn_exchange"):
        assert f'"{scope}/' in text, scope
    assert '"moe_exchange/psum' in text and '"attn_exchange/psum' in text
    deep = dataclasses.replace(cfg, num_layers=28, layer_types=cfg.period * 7)
    for what in ("chunk", "decode"):
        shallow, at_28 = (len(lowered(c, what).splitlines()) for c in (cfg, deep))
        assert at_28 == shallow, (what, shallow, at_28)


# -- the refusals -------------------------------------------------------------------

@pytest.mark.parametrize("change, named", [
    ({"mlp_layer_types": ["sparse"] * 7 + ["dense"]}, "mlp_layer_types"),
    ({"layer_types": ["sliding_attention"] * 7 + ["linear_attention"]}, "layer_types"),
    ({"layer_types": ["sliding_attention"] * 4}, "layer_types"),
    ({"layer_types": ["sliding_attention"] * 3 + ["full_attention"] + ["sliding_attention"] * 4}, "layer_types"),
    ({"rope_parameters": {"full_attention": ROPE["full_attention"]}}, "rope_parameters"),
    ({"rope_parameters": {**ROPE, "full_attention": {**ROPE["full_attention"], "rope_type": "llama3"}}},
     "rope_parameters"),
    ({"rope_parameters": {**ROPE, "sliding_attention": {"rope_type": "yarn", "rope_theta": 10000}}},
     "rope_parameters"),
    ({"rope_parameters": {**ROPE, "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}},
     "rope_parameters"),
    ({"norm_topk_prob": False}, "norm_topk_prob"), ({"attention_bias": True}, "attention_bias"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"), ({"use_sliding_window": False}, "use_sliding_window"),
    ({"num_attention_heads": 6}, "num_key_value_heads"),
])
def test_what_the_module_does_not_compute_is_refused_by_name(change, named):
    with pytest.raises(ValueError, match=f"model_type 'mellum' with {named} = "):
        config_from_card(card({**SHAPE, **change}))


def test_the_flat_spelling_of_the_two_rotary_sections_reads_as_the_nested_group(cfg):
    """``benchmark/run.py`` writes a config.json of scalar and list keys alone."""
    flat = {k: v for k, v in SHAPE.items() if k != "rope_parameters"}
    flat.update({f"rope_parameters_{kind}_{k}": v for kind, group in ROPE.items() for k, v in group.items()})
    assert config_from_card(card(flat), jnp.float32) == cfg
    assert ref.table(flat, "full_attention") == ref.table(SHAPE, "full_attention")


@pytest.mark.parametrize("key, size", [("num_attention_heads", 6), ("num_key_value_heads", 2),
                                       ("num_experts", 6), ("vocab_size", 98)])
def test_what_the_mesh_axis_does_not_divide_is_refused_at_engine_build_by_name(mesh, key, size):
    heads = {"num_attention_heads": 12, "num_key_value_heads": 2} if key != "num_attention_heads" and \
        key == "num_key_value_heads" else {"num_key_value_heads": 2} if key == "num_attention_heads" else {}
    c = config_from_card(card({**SHAPE, **heads, key: size}), jnp.float32)
    with pytest.raises(ValueError, match=f"model_type 'mellum' with {key} = {size} on a mesh axis 'tp' of 4"):
        mellum.param_shardings(c, mesh)
    with pytest.raises(ValueError, match="serves over ONE mesh axis"):
        mellum.param_shardings(c, make_mesh(MeshConfig(tp=2, dp=2)))


@pytest.mark.parametrize("model_type", ["kimi_linear", "jamba", "lfm2_moe", "qwen3_next", "pangu_ultra_moe",
                                        "xing4_0", "afmoe"])
def test_the_seven_other_modules_with_their_own_programs_are_still_refused_a_mesh(mesh, model_type):
    """In the parent's words, and before anything is made on a device."""
    c = config_from_card(card(published_shape(model_type)))
    assert not getattr(module_for(c), "SERVES_ON_MESH", False)
    with pytest.raises(ValueError, match=f"{type(c).__name__} runs on one device, with bf16 weights and native pages"):
        JaxServingEngine(c, None, ENGINE_CFG, mesh=mesh)
    with pytest.raises(NotImplementedError):
        module_for(c).param_shardings(c, mesh)


def test_quantized_weights_and_int8_pages_stay_refused(cfg, params, sharded, mesh):
    for on, prm in ((None, params), (mesh, sharded)):
        for change in ({"quantize": "int8"}, {"kv_dtype": "int8"}):
            with pytest.raises(ValueError, match="MellumConfig runs on one device, with bf16 weights and native pages"):
                JaxServingEngine(cfg, prm, dataclasses.replace(ENGINE_CFG, **change), mesh=on)


# -- the engine -----------------------------------------------------------------------

def teacher_forced(params, prompt, answered):
    """The reference's logits at every answered position, after ``prompt`` and
    the answer's own tokens before it."""
    seq = jnp.asarray(list(prompt) + list(answered[:-1]), jnp.int32)
    return np.asarray(reference_program(ref, SHAPE)(params, seq, jnp.arange(len(prompt) - 1, len(seq))))


def held_to_the_reference(params, prompt, toks, lps):
    """Logits and not tokens: the tokens are the reference's first choices
    AND the log-probabilities served with them are the reference's."""
    want = teacher_forced(params, prompt, toks)
    assert toks == want.argmax(-1).tolist()
    logp = want - np.log(np.exp(want).sum(-1, keepdims=True))
    np.testing.assert_allclose(lps, logp[np.arange(len(toks)), toks], atol=ATOL)


@pytest.mark.timeout(300)  # four programs compile inside it; 60-70 s alone, more beside five busy workers
@pytest.mark.parametrize("where", ["one_device", "mesh"])
def test_the_engine_serves_what_the_reference_gives_past_the_window(request, params, where):
    """Through ``JaxServingEngine`` (admission, a prompt of 75 tokens in chunk
    dispatches of one and two rows, pipelined decode dispatches of 4 steps past
    90 positions, log-probabilities), on one device and on the ``tp=4`` mesh:
    teacher-forced against the reference. A prompt again: the prefix hit is
    declined for want of the rings. Four long requests, then a short one into
    a used slot. What hands pages over without the rings is refused by name."""
    eng = request.getfixturevalue("mesh_engine" if where == "mesh" else "engine")
    prompt = prompt_of(75)
    toks, lps, finish = served(eng, prompt, 18, logprobs=5)
    assert len(toks) == 18 and finish == "length"
    held_to_the_reference(params, prompt, toks, lps)
    snap = eng.metrics_snapshot()
    assert set(mellum.COUNTERS) <= set(snap) and len(mellum.COUNTERS) == 14
    assert snap["swa_layer_calls"] > 0 and snap["full_layer_calls"] > 0 and snap["moe_layer_calls"] > 0
    assert 0 < snap["swa_history_positions_live"] < snap["swa_history_positions_read"]
    assert snap["swa_history_positions_read"] < snap["swa_history_positions_whole"]
    assert eng._lane_rows and eng._top_takes_rows and eng._chunk_rungs == [1, 2, 8] and eng._lane_rows_most == 3
    assert snap["chunk_rows_live"] > snap["chunk_lanes_fed"] > 0  # a lane took two rows of a dispatch
    shards = 4 if where == "mesh" else 1
    assert eng.cache["k"].shape == (PERIODS, eng.num_blocks, 8, 4, 16)
    assert eng.cache["k"].sharding.shard_shape(eng.cache["k"].shape)[3] == 4 // shards
    rings = eng.slot_state["k"]
    assert rings.shape == (PERIODS, 3, 8, 4, RING, 16)
    assert rings.sharding.shard_shape(rings.shape)[3] == 4 // shards
    assert snap["slot_state_bytes_a_chip"] == 2 * rings.size * 4 // shards
    if where == "mesh":
        assert snap["exchange_rows"] > 0 and snap["moe_pairs_fullest_shard"] < snap["moe_pairs_all_shards"]
        assert snap["decode_history_tiles_read"] < snap["decode_history_tiles_full"]
    else:
        assert snap["exchange_rows"] == 0 and snap["moe_pairs_fullest_shard"] == snap["moe_pairs_all_shards"]

    declined = eng.prefix_hits_declined
    again = submit(eng, prompt, 18, logprobs=5)  # log-probabilities throughout: one family of programs
    step(eng)
    assert again.alloc.cached_tokens == 0 and again.alloc.declined_tokens == 72
    run_out(eng)
    assert answer(again)[0] == toks and eng.prefix_hits_declined == declined + 1

    for salt in range(4):
        submit(eng, prompt_of(70 + salt, salt=salt), 6, logprobs=5)
    run_out(eng)
    late = submit(eng, prompt_of(21, salt=9), 8, logprobs=5)
    run_out(eng)
    short, lps, _ = answer(late)
    assert len(short) == 8
    held_to_the_reference(params, prompt_of(21, salt=9), short, lps)
    with pytest.raises(StateNotPortable, match="MellumConfig keeps state per slot"):
        eng._refuse_for_state("a migration")


@pytest.mark.timeout(300)
def test_the_mesh_engine_answers_as_the_one_device_engine_does(engine, mesh_engine, params):
    """Five prompts admitted at once (the full width's spare rows go to the
    oldest lanes' further pieces): the two engines give the same tokens, and
    log-probabilities that differ by the order of the shards' sums alone."""
    prompts = [prompt_of(70 + 3 * i, salt=20 + i) for i in range(5)]
    got = []
    for eng in (engine, mesh_engine):
        seqs = [submit(eng, p, 5, logprobs=5) for p in prompts]
        run_out(eng)
        got.append([answer(s) for s in seqs])
    for prompt, one, four in zip(prompts, *got):
        assert one[0] == four[0] and len(one[0]) == 5
        np.testing.assert_allclose(one[1], four[1], atol=ATOL)
        held_to_the_reference(params, prompt, four[0], four[1])


@pytest.mark.timeout(300)
def test_preemption_recomputes_past_the_window_on_the_mesh(cfg, sharded, params, mesh, run):
    """Out of blocks, a lane past the window is preempted and recomputed from
    position 0 into the same rings, one KV head a shard: the reference's."""
    tight = dataclasses.replace(ENGINE_CFG, max_slots=2, max_model_len=96, num_kv_blocks=14)
    eng = JaxServingEngine(cfg, sharded, tight, mesh=mesh)
    try:
        async def both():
            return await asyncio.gather(collect(eng, prompt_of(40, 1), max_tokens=30, with_lp=True),
                                        collect(eng, prompt_of(40, 2), max_tokens=30, with_lp=True))

        got = run(both())
        assert eng.preemptions > 0
    finally:
        eng.close()
    for salt, (toks, lps, _) in zip((1, 2), got):
        assert len(toks) == 30
        held_to_the_reference(params, prompt_of(40, salt), toks, lps)


def test_the_mesh_engine_warms_up_its_programs_ahead(cfg, sharded, mesh):
    """``warmup`` compiles a module's own programs on a mesh as on one device
    (ahead, from shapes in their shardings), and the engine serves off them."""
    eng = JaxServingEngine(cfg, sharded, dataclasses.replace(ENGINE_CFG, max_slots=4), mesh=mesh)
    try:
        timings = eng.warmup("greedy")
        assert {"decode(sample=False)", "chunk(sample=False,history=True)"} <= set(timings)
        toks, _, finish = served(eng, prompt_of(40), 6)
        assert len(toks) == 6 and finish == "length"
    finally:
        eng.close()


@pytest.mark.timeout(300)
def test_the_exchange_probe_tells_a_sum_that_is_wrong_from_one_that_is_rounded(mesh, monkeypatch):
    """``tools/exchange_probe.py`` at its tiny size (what it read on the four
    chips is in PERF.md 7): one all-reduce is float32's sum to a rounding or
    three and the same bits on every shard; the mesh's program, with the
    all-reduce as it is and as an all-gather added up, and the same program on
    one device lie as close to the reference as each other; the reference with
    its embeddings moved by 1e-5 lies some 1e-5 from itself (the model hands a
    difference on, it does not multiply it); and an exchange that loses the
    other shards' parts stands out by five orders."""
    from tools import exchange_probe as probe

    for read in probe.arithmetic(mesh, "tp", ((2, 16, 64),), 0):
        assert read["in_float32_roundings"] < 4 and read["shards_hold_the_same_bits"]
    runs = ["mesh:psum:engine", "mesh:gathered:engine", "one_chip:psum:engine"]
    how = dict(mesh=mesh, layers=4, vocab=96, prompt_tokens=300, tiny=True, seed=0, perturbed=[1e-5])
    got = [r for r in probe.program(runs=runs, **how) if r["prompt_tokens"] == 300]
    moved, *programs = got
    assert 3e-6 < moved["hidden_rms_error_over_rms"] < 1e-4
    assert [r["where"] + ":" + r["all_reduce"] + ":" + r["deal"] for r in programs] == runs
    assert all(r["hidden_rms_error_over_rms"] < 5e-6 for r in programs)
    assert programs[-1]["the_mesh_s_against_it"]["hidden_rms_error_over_rms"] < 5e-6
    monkeypatch.setattr(probe, "gathered", lambda y, axis: y)
    lost = probe.program(runs=runs[1:2], **{**how, "perturbed": []})[-1]
    assert lost["hidden_rms_error_over_rms"] > 0.1 and mellum._all_reduce is probe.ALL_REDUCE
