"""Xing4.0 on the served path, at a tiny size on the CPU in float32 (hidden
64, 4 residual streams, 4 heads of 16 + 8 rotated with YaRN's frequencies, a
compressed query of 24, a latent of 32; one dense layer and two expert layers
of 8 experts top-2 under a selection bias, the prediction module behind them:
the structure of ``xing4.0-29b-a4b`` whole, every width small).

The program (``models/xing4.py``: chunked prefill through latent pages, then
decode, the residual path of ``ops/mhc.py`` around every sublayer; the
prediction module beside both) is held against the benchmark's plain
reference (``benchmark/reference_xing4.py``: one sequence, naive attention, no
cache, the state one ``[T, n, C]`` array); the residual path against its
claims (doubly stochastic after 20 sweeps and not after 2; pinned maps reduce
the model to a one-stream pre-norm model); the engine against both, for what
openPangu's contract gives a module with its own programs and NO state beside
the pages: a prefix hit, ``verify`` with the module's own drafts, preemption.
"""

import asyncio
import dataclasses
import inspect
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_xing4 as ref
from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
from dynamo_tpu.engine_jax.weights import config_from_card
from dynamo_tpu.models import llama, module_for
from dynamo_tpu.models import xing4 as xm
from dynamo_tpu.ops import mhc

from .latent_harness import BS, LANE_ROWS, MB, YARN, check_lane_rows, check_lanes_decode_as_each_does_alone, feed
from .latent_harness import XING4_SHAPE as SHAPE
from .step_programs import (  # noqa: F401  (highest_precision: autouse, for this file's tests)
    answer, card, collect, decode_program, highest_precision, patched, prompt_of, reference_program, run_out,
    submit,
)

# ATOL: float32 on the CPU, at the highest matmul precision on both sides. The
# program and the reference order their sums differently (absorbed against
# expanded latent attention, the experts' rows batched against every token
# through every expert, a chunk against the whole sequence, the Sinkhorn
# sweeps with the tokens in the minor axis against the major): 2e-4 on logits
# of magnitude 4 is what tests/test_openpangu.py allows for the same reasons
# (measured here: 6e-6). Plain RoPE for YaRN, the score scale without its
# 2.0047, a dropped selection bias, 2 sweeps for 20 or bfloat16 activations in
# the maps each move a logit by 2e-3 and more (the tests below).
ATOL = 2e-4

ENGINE_CFG = EngineConfig(max_slots=4, kv_block_size=8, max_model_len=96,
                          prefill_chunk=16, decode_steps=4, top_logprobs=5)


@pytest.fixture(scope="module")
def cfg():
    return config_from_card(card(SHAPE), jnp.float32)


@pytest.fixture(scope="module")
def params(cfg):
    """Seeded weights, the norm weights moved off one (a norm whose weight is
    dropped, or applied twice, must show) and the selection bias made large
    enough to move choices (0.1 x a normal moves few among 8 experts)."""
    tree = xm.init_params(jax.random.PRNGKey(3), cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    moved = []
    for i, (path, leaf) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        if "norm" in name:
            leaf = leaf * (1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(i), leaf.shape))
        elif "e_bias" in name:
            leaf = leaf * 5.0
        moved.append(leaf)
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


def program_logits(cfg, params, tokens):
    """A 14-token prompt's logits from one chunk dispatch."""
    got, _, _, _ = feed(xm, cfg, params, xm.make_kv_cache(cfg, 32, BS), tokens, 0, len(tokens), np.arange(1, 9))
    return np.asarray(got)


def test_the_module_is_found_by_its_config_and_keeps_nothing_per_slot(cfg):
    assert module_for(cfg) is xm and module_for(llama.LLAMA_PRESETS["tiny"]) is llama
    assert [xm.is_expert_layer(cfg, i) for i in range(3)] == [False, True, True]
    # nothing per slot, and openPangu's own statement that a lane may fill several rows, with what it rests on
    assert not hasattr(xm, "make_slot_state") and xm.LANE_TAKES_ROWS is xm.base.LANE_TAKES_ROWS is True
    assert xm.chunk_history_tiles is xm.base.chunk_history_tiles
    assert list(inspect.signature(xm.chunk_history_tiles).parameters)[3:] == ["lanes"]
    assert xm.COUNTERS[:10] == xm.base.COUNTERS and xm.COUNTERS[10:] == ("mhc_mix_calls", "mhc_rows_mixed")
    pool = xm.make_kv_cache(cfg, 16, BS)
    assert list(pool) == ["latent"] and pool["latent"].shape == (3, 16, BS, 128)
    assert xm.make_kv_cache(cfg, 16, BS, drafting=True)["latent"].shape[0] == 4
    tree = xm.init_params(jax.random.PRNGKey(0), cfg)
    lp = tree["layers"][1]
    assert "post_attn_norm" not in lp and "post_mlp_norm" not in lp and lp["e_bias"].shape == (8,)
    assert lp["attn_hc"]["phi"].shape == (24, 4 * 64) and lp["mlp_hc"]["b"].shape == (24,)
    assert "e_bias" not in tree["layers"][0] and "attn_hc" in tree["mtp"]["layer"]


@pytest.mark.parametrize("chunks", [(16, 16, 5), (7, 16, 14), (16, 9), (16,)],
                         ids=["full_chunks", "a_short_first_chunk", "two_chunks", "one_chunk"])
def test_chunked_prefill_then_decode_agrees_with_the_plain_reference(cfg, params, chunks):
    """A prompt fed in chunks whose boundaries lie inside it, each attending
    what the last ones left in the latent pages, then three decode steps; the
    prediction module's logits at every position of both; the counters."""
    n_prompt, n_decode = sum(chunks), 3
    tokens = np.asarray(prompt_of(n_prompt + n_decode + 1, salt=len(chunks)), np.int32)
    want = np.asarray(reference_program(ref, SHAPE)(params, jnp.asarray(tokens), jnp.arange(len(tokens))))
    want_draft = np.asarray(reference_program(ref, SHAPE, "draft_logits")(
        params, jnp.asarray(tokens), jnp.arange(len(tokens) - 1)))
    cache = xm.make_kv_cache(cfg, 32, BS, drafting=True)
    table = np.arange(1, 9)
    got, got_draft, at = [], [], 0
    for n in chunks:
        logits, drafts, cache, sums = feed(xm, cfg, params, cache, tokens, at, n, table,
                                           drafting=True, following=tokens[1:])
        got.append(logits), got_draft.append(drafts)
        counts = dict(zip(xm.COUNTERS, sums))
        assert counts["mla_layer_calls"] == 4 and counts["mtp_layer_calls"] == 1
        assert counts["moe_layer_calls"] == 3  # two expert layers and the module's
        assert counts["mla_history_positions_live"] == 4 * (at + n)
        # two sublayers of three layers and of the module's one, each over the row's n tokens
        assert counts["mhc_mix_calls"] == 8 and counts["mhc_rows_mixed"] == 8 * n
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
    out = decode_program(xm, cfg, n_decode, 95, draft=True)(
        params, jnp.asarray(toks), jnp.asarray(pos), cache, jnp.asarray(lanes_tables), None, jnp.asarray(forcing))
    np.testing.assert_allclose(np.asarray(out[3])[:, slot], want[n_prompt:n_prompt + n_decode], atol=ATOL)
    assert out[5] is None and int(out[1][slot]) == n_prompt + n_decode
    assert int(out[7][slot]) == int(want_draft[n_prompt + n_decode - 1].argmax())
    counts = dict(zip(xm.COUNTERS, np.asarray(out[6])))
    assert counts["mla_layer_calls"] == 4 * n_decode and counts["mtp_layer_calls"] == n_decode
    assert counts["mhc_mix_calls"] == 8 * n_decode and counts["mhc_rows_mixed"] == 8 * n_decode  # one lane decodes
    # the gather reads every slot's whole table once; a step's row is scored against its block's tiles (one
    # block of four lanes, this table's one tile: the host's count) and the dispatch's steps
    assert xm.decode_history_tiles(pos, BS, MB) == slots
    assert counts["mla_history_positions_read"] == 4 * (slots * MB * BS + n_decode * (MB * BS + n_decode))
    plain = decode_program(xm, cfg, n_decode, 95)(
        params, jnp.asarray(toks), jnp.asarray(pos), {"latent": cache["latent"][:3]}, jnp.asarray(lanes_tables),
        None, jnp.asarray(forcing))
    counts = dict(zip(xm.COUNTERS, np.asarray(plain[6])))
    assert len(plain) == 7 and counts["mtp_layer_calls"] == 0 and counts["mhc_mix_calls"] == 6 * n_decode
    np.testing.assert_allclose(np.asarray(plain[3])[:, slot], np.asarray(out[3])[:, slot], atol=ATOL)


@pytest.mark.parametrize("drafting", [False, True], ids=["decode", "decode_drafting"])
def test_two_lanes_of_a_decode_dispatch_answer_as_each_does_alone(cfg, params, drafting):
    check_lanes_decode_as_each_does_alone(xm, cfg, params, drafting)


def test_a_lane_that_starts_past_position_zero_is_rotated_at_its_own_positions(cfg, params):
    """A row whose first token stands at position 16 (a prefix hit) rotates
    its queries and keys with YaRN's frequencies at 16 on and attends the
    cached, rotated keys before it."""
    tokens = np.asarray(prompt_of(29, salt=5), np.int32)
    want = np.asarray(reference_program(ref, SHAPE)(params, jnp.asarray(tokens), jnp.arange(29)))
    cache = xm.make_kv_cache(cfg, 32, BS)
    _, _, cache, _ = feed(xm, cfg, params, cache, tokens, 0, 16, np.asarray([5, 6, 0, 0, 0, 0, 0, 0]))
    logits, _, cache, _ = feed(xm, cfg, params, cache, tokens, 16, 13, np.asarray([5, 6, 9, 10, 0, 0, 0, 0]))
    np.testing.assert_allclose(logits, want[16:], atol=ATOL)
    fresh, _, _, _ = feed(xm, cfg, params, xm.make_kv_cache(cfg, 32, BS), tokens[16:], 0, 13, np.arange(1, 9))
    assert np.abs(np.asarray(fresh) - want[16:]).max() > 100 * ATOL


@pytest.mark.parametrize("layout", list(LANE_ROWS))
def test_a_later_row_of_one_dispatch_attends_the_rows_before_it_through_the_pool(cfg, params, monkeypatch, layout):
    """``tests/test_openpangu.py``'s layouts through THIS module's programs:
    successive pieces of a prompt in consecutive rows of one chunk dispatch
    (within a group of ``_in_groups``, across groups, beside another lane,
    across a padding row, behind a prefix hit and an earlier dispatch) answer
    as the reference does over the whole prompt, the prediction module's own
    layer included: the maps of the residual path are a token's own, so the
    rows meet in the pool alone, as openPangu's do."""
    check_lane_rows(xm, ref, SHAPE, cfg, params, layout, monkeypatch)


# -- what the tolerance sees: each departure is another model ----------------------

def _reference_with(shape_changes=None, **patches):
    """The reference's logits over a 14-token prompt with keys of the shape
    changed, or attributes of the reference module replaced."""
    def logits(params, monkeypatch):
        for name, value in patches.items():
            monkeypatch.setattr(ref, name, value)
        tokens = np.asarray(prompt_of(14, salt=2), np.int32)
        return np.asarray(ref.logits(params, {**SHAPE, **(shape_changes or {})}, jnp.asarray(tokens), jnp.arange(14)))
    return logits


DEPARTURES = {
    "plain_rope_in_yarns_place": _reference_with({"rope_scaling": None}, score_scale=lambda shape: 24 ** -0.5 * 2.0047397),
    "the_score_scale_without_its_mscale": _reference_with(score_scale=lambda shape: 24 ** -0.5),
    "two_sinkhorn_sweeps_in_place_of_twenty": _reference_with({"hc_sinkhorn_iters": 2}),
    "the_clamp_at_1": _reference_with({"mhc_h_res_clamp_min": -1, "mhc_h_res_clamp_max": 1}),
}


@pytest.mark.parametrize("departure", list(DEPARTURES))
def test_a_departure_from_the_equations_cannot_hide(cfg, params, monkeypatch, departure):
    """The program against the reference WITH one departure (plain RoPE for
    YaRN, the score scale without 2.0047, 2 sweeps for 20, another clamp):
    further than any tolerance here. (19 sweeps against 20 differ by less than
    float32 resolves, and rows before columns reach the same limit: no
    tolerance is asked to see those.)"""
    tokens = np.asarray(prompt_of(14, salt=2), np.int32)
    got = program_logits(cfg, params, tokens)
    sound = np.asarray(reference_program(ref, SHAPE)(params, jnp.asarray(tokens), jnp.arange(14)))
    np.testing.assert_allclose(got, sound, atol=ATOL)
    departed = DEPARTURES[departure](params, monkeypatch)
    assert np.abs(got - departed).max() > 10 * ATOL, np.abs(got - departed).max()


def test_a_dropped_selection_bias_cannot_hide(cfg, params):
    """The program WITHOUT the router's selection bias chooses other experts
    in some token-layers, and its logits leave the reference's."""
    tokens = np.asarray(prompt_of(14, salt=2), np.int32)
    want = np.asarray(reference_program(ref, SHAPE)(params, jnp.asarray(tokens), jnp.arange(14)))
    without = jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.zeros_like(leaf) if "e_bias" in jax.tree_util.keystr(path) else leaf, params)
    assert np.abs(program_logits(cfg, without, tokens) - want).max() > 10 * ATOL


def test_bfloat16_activations_in_the_maps_would_fail(cfg, params, monkeypatch):
    """The maps are float32's: the program with the state rounded to bfloat16
    in front of ``x̂ φ`` (one bfloat16 part where three are stated) misses the
    tolerance; and so does the reference with its activations rounded in front
    of every weight product."""
    tokens = np.asarray(prompt_of(14, salt=2), np.int32)
    want = np.asarray(reference_program(ref, SHAPE)(params, jnp.asarray(tokens), jnp.arange(14)))
    wdot = mhc.wdot
    patched(monkeypatch, mhc, "wdot", lambda spec, x, w: wdot(spec, x.astype(jnp.bfloat16).astype(jnp.float32), w))
    assert np.abs(program_logits(cfg, params, tokens) - want).max() > 10 * ATOL
    monkeypatch.undo()

    def coarse(x, w):
        return jnp.dot(x.astype(jnp.bfloat16).astype(jnp.float32), w.astype(jnp.float32))

    low = np.asarray(ref.logits(params, SHAPE, jnp.asarray(tokens), jnp.arange(14), dot=coarse))
    assert np.abs(low - want).max() > 10 * ATOL


# -- the residual path and its claims -----------------------------------------------

def test_twenty_sweeps_make_the_residual_map_doubly_stochastic_and_two_do_not():
    """256 seeded logit matrices spread over [-5, 5]: after 2 sweeps NOT ONE
    has its columns summing to 1 within 1e-3 (the rows, normalised last, do);
    after 20 the rows sum to 1 within 1e-5, the columns within 1e-3 for the
    median matrix and 0.05 for the worst (measured: 1e-4 and 0.026; ISSUE 61
    asked for 1e-5 there, which 20 sweeps give at a spread of [-2, 2], held
    below, and 100 sweeps do not give at [-5, 5]: Sinkhorn converges at the rate
    of the matrix's spread). Every entry stays positive."""
    def off_one(spread, iters):
        logits = jax.random.uniform(jax.random.PRNGKey(7), (4, 4, 256), jnp.float32, -spread, spread)
        done = np.asarray(mhc.sinkhorn(jnp.exp(logits), iters, 1e-6))
        assert (done > 0).all()
        return np.abs(done.sum(axis=0) - 1).max(axis=0), np.abs(done.sum(axis=1) - 1).max(axis=0), done, logits

    columns, rows, _, _ = off_one(5.0, 2)
    assert columns.min() > 1e-3 and rows.max() < 1e-5
    columns, rows, done, logits = off_one(5.0, 20)
    assert rows.max() < 1e-5 and np.median(columns) < 1e-3 and columns.max() < 0.05
    columns, rows, _, _ = off_one(2.0, 20)
    assert rows.max() < 1e-5 and columns.max() < 1e-5
    # the reference's sweeps over [T, n, n] are the same numbers
    np.testing.assert_allclose(np.moveaxis(np.asarray(ref.sinkhorn(jnp.moveaxis(jnp.exp(logits), -1, 0), 20, 1e-6)), 0, -1),
                               done, rtol=1e-5)


def test_the_seeded_maps_are_alive_at_the_cells_widths():
    """The condition the seeded mHC sets are under (ISSUE 61): at the cell's
    widths (4 streams of 3,584, bf16 sets) over streams that differ, ``H_pre``
    lies in (0, 1), ``H_post`` in (0, 2), both vary between tokens, and
    ``H_res`` is measurably neither the identity nor uniform."""
    c = xm.Xing4Config(num_layers=1, first_k_dense=1)
    hp = xm._init_hc(jax.random.PRNGKey(1), c)
    assert hp["phi"].shape == (24, 14336) and hp["phi"].dtype == jnp.bfloat16
    streams = tuple(jax.random.normal(jax.random.PRNGKey(10 + j), (64, 3584)) for j in range(4))
    h_pre, h_post, h_res = (np.asarray(a) for a in mhc.mhc_maps(
        streams, hp["phi"], hp["b"], hp["alpha"], 20, 1e-6, 1e-6, (-30.0, 30.0)))
    assert h_pre.shape == (64, 4) and h_post.shape == (64, 4) and h_res.shape == (64, 4, 4)
    assert 0 < h_pre.min() and h_pre.max() < 1 and 0 < h_post.min() and h_post.max() < 2
    assert h_pre.std(axis=0).min() > 0.05 and h_post.std(axis=0).min() > 0.1
    # rows normalised last: exact; the columns as far as 20 sweeps bring logits of N(0, 1) + 2 I
    np.testing.assert_allclose(h_res.sum(axis=2), 1.0, atol=1e-5)
    assert np.median(np.abs(h_res.sum(axis=1) - 1)) < 1e-4 and np.abs(h_res.sum(axis=1) - 1).max() < 0.05
    diagonal = h_res[:, np.arange(4), np.arange(4)]
    assert 0.4 < diagonal.mean() < 0.95  # not uniform (0.25), not the identity (1)
    assert np.abs(h_res - np.eye(4)).max(axis=(1, 2)).min() > 0.05 and h_res.std(axis=0).min() > 0.01


def test_with_the_maps_pinned_the_model_is_the_one_stream_pre_norm_model(cfg, params):
    """``φ = 0``, ``b_res`` a large multiple of I (``H_res`` = I), ``b_pre`` =
    -ln 3 (``H_pre`` = 1/4) and ``b_post`` = 0 (``H_post`` = 1): every stream
    stays a copy of the one-stream residual ``x += F(N(x))``, so the model's
    logits are a one-stream pre-norm model's built from the same weights, up to
    the final norm's epsilon on a sum four times as large (1e-6 against
    activations of order 1: under float32's resolution here)."""
    n = cfg.hc_mult
    pinned_b = jnp.concatenate([jnp.full((n,), -math.log(n - 1.0)), jnp.zeros((n,)),
                                (60.0 * jnp.eye(n) - 30.0).reshape(-1)])

    def pin(path, leaf):
        name = jax.tree_util.keystr(path)
        if "phi" in name:
            return jnp.zeros_like(leaf)
        return pinned_b.astype(leaf.dtype) if name.endswith("_hc']['b']") else leaf

    pinned = jax.tree_util.tree_map_with_path(pin, params)
    tokens = np.asarray(prompt_of(14, salt=2), np.int32)
    got = program_logits(cfg, pinned, tokens)

    # the one-stream model, from the reference's own sublayers
    eps = SHAPE["rms_norm_eps"]
    x = ref._f32(pinned["embed"][jnp.asarray(tokens)])
    for i, lp in enumerate(pinned["layers"]):
        x = x + ref.mla_mixer(lp, SHAPE, ref._norm(x, lp["in_norm"], eps))
        m = ref._norm(x, lp["pre_mlp_norm"], eps)
        x = x + (ref.expert_layer(lp, SHAPE, m) if i >= 1 else ref.swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"]))
    # four copies summed, then the final norm: the norm of 4 x is the norm of x but for its epsilon
    one_stream = np.asarray(ref._dot(ref._norm(4.0 * x, pinned["final_norm"], eps), pinned["lm_head"]))
    np.testing.assert_allclose(got, one_stream, atol=ATOL)
    # and the seeded maps are NOT that model
    assert np.abs(program_logits(cfg, params, tokens) - one_stream).max() > 100 * ATOL


# -- YaRN ---------------------------------------------------------------------------

def test_yarns_frequencies_and_score_scale_at_the_published_keys():
    """``inv_freq`` against the closed form at the published keys (32 values
    for a rotated part of 64: low 10, high 23), the score scale 2.0047 x
    192^-0.5, and the reference's own table."""
    c = xm.Xing4Config()
    got = np.asarray(c.rope_inv_freq)
    i = np.arange(32)
    f = 10000.0 ** (-2.0 * i / 64)
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    np.testing.assert_allclose(got, f / 64 * ramp + f * (1 - ramp), rtol=1e-12)
    assert got[0] == 1.0 and got[10] == pytest.approx(f[10], rel=1e-12) and got[23] == pytest.approx(f[23] / 64, rel=1e-12)
    assert f[15] / 64 < got[15] < f[15]
    assert c.score_scale == pytest.approx(192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2)
    assert c.score_scale * 192 ** 0.5 == pytest.approx(2.0047, abs=1e-4)
    published = {"qk_rope_head_dim": 64, "qk_nope_head_dim": 128, "rope_theta": 10000,
                 "rope_scaling": {**YARN, "original_max_position_embeddings": 4096}}
    np.testing.assert_allclose(ref.yarn_inv_freq(published), got, rtol=1e-12)
    assert ref.score_scale(published) == pytest.approx(c.score_scale)
    # a card without the group rotates by rope_theta alone, at the plain scale
    plain = dataclasses.replace(c, yarn_factor=None)
    assert plain.rope_inv_freq is None and plain.score_scale == 192 ** -0.5


def test_apply_rope_without_a_table_is_what_it_was_and_with_one_turns_by_it():
    """Callers that pass no table get the parent's rotation bit for bit (the
    formula it had, written out here); a table of ``theta``'s own frequencies
    gives the same numbers; YaRN's table turns the slow pairs a 64th as fast."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 64))
    positions = jnp.asarray([[0, 1, 2, 3, 4], [100, 101, 102, -1, -1]])

    def parents(x, positions, theta):
        d = x.shape[-1]
        freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        angles = jnp.clip(positions, 0).astype(jnp.float32)[..., None] * freqs
        cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
        x1, x2 = x[..., : d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)

    np.testing.assert_array_equal(np.asarray(llama.apply_rope(x, positions, 10000.0)),
                                  np.asarray(parents(x, positions, 10000.0)))
    lowered = jax.jit(lambda x, p: llama.apply_rope(x, p, 10000.0)).lower(x, positions).as_text()
    assert lowered == jax.jit(lambda x, p: parents(x, p, 10000.0)).lower(x, positions).as_text()
    own = [10000.0 ** (-2.0 * i / 64) for i in range(32)]
    np.testing.assert_allclose(np.asarray(llama.apply_rope(x, positions, 10000.0, own)),
                               np.asarray(parents(x, positions, 10000.0)), atol=1e-5)
    scaled = np.asarray(llama.apply_rope(x, positions, 10000.0, xm.Xing4Config().rope_inv_freq))
    assert np.abs(scaled - np.asarray(parents(x, positions, 10000.0)))[1].max() > 0.1


# -- the engine's contract: own programs, no state beside the pages ----------------

def test_a_prefix_hit_is_served_from_latent_pages_and_the_counters_rise(cfg, params, engine):
    """A second request that shares a 3-block prefix prefills from the first
    uncached block, reports the hit, and its log-probabilities are the
    reference's, teacher-forced over what the engine emitted; ``/debug/engine``'s
    snapshot carries the module's sums."""
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
    seq = np.asarray(shared + [4, 5, 6, 7] + toks, np.int32)
    at = np.arange(30, 30 + len(toks))
    logits = np.asarray(reference_program(ref, SHAPE)(params, jnp.asarray(seq), jnp.asarray(at)))
    want = jax.nn.log_softmax(logits)[np.arange(len(toks)), toks]
    np.testing.assert_allclose(lps, want, atol=ATOL)
    for name in ("mhc_mix_calls", "mhc_rows_mixed", "mla_layer_calls", "moe_layer_calls", "moe_held_rows"):
        assert after[name] > before[name] > 0, name
    assert after["mtp_layer_calls"] == 0
    # six sublayers a pass over the 7 tokens behind the hit and the steps decoded (two dispatches of 4: the
    # lane steps on to the dispatch's end)
    rows, calls = after["mhc_rows_mixed"] - before["mhc_rows_mixed"], after["mhc_mix_calls"] - before["mhc_mix_calls"]
    assert calls % 6 == 0 and rows % 6 == 0 and 6 * (7 + 5) <= rows <= 6 * (7 + 8)


def test_the_step_programs_carry_their_scopes(engine, drafting_engine):
    """The device trace finds the mechanisms by name: ``mhc``, ``mla``, ``mlp``
    (the leading dense layer), ``moe`` and ``moe/shared`` are scopes of every
    step program; ``mtp`` of the ``spec_k`` > 0 variants alone, with the
    module's own ``mtp/mhc``, ``mtp/mla`` and ``mtp/moe`` under it."""
    import re

    def sd(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    s, c, mb = ENGINE_CFG.max_slots, ENGINE_CFG.prefill_chunk, ENGINE_CFG.max_blocks_per_seq
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    own = {"mhc", "mla", "mlp", "moe", "moe/shared"}
    for eng, want in ((engine, own),
                      (drafting_engine, own | {"mtp", "mtp/mhc", "mtp/mla", "mtp/moe", "mtp/moe/shared"})):
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
            names = set(re.findall(r'loc\("(?:jit\([^"/]*\)/)*((?:mtp/)?(?:mhc|mla|mlp|moe/shared|moe)|mtp)/',
                                   program.as_text(debug_info=True)))
            assert names == want, names


def test_with_one_draft_the_greedy_stream_is_the_undrafted_one(cfg, params, engine, drafting_engine, run):
    """``spec_k`` = 1: the draft comes from the prediction module on the
    device, ``verify`` runs through the module's own program over the streams,
    and the stream is token for token ``spec_k`` = 0's, with the
    log-probabilities it had."""
    eng = drafting_engine
    assert eng._device_drafts and eng.cache["latent"].shape[0] == cfg.num_layers + 1
    prompt = prompt_of(21, salt=3)
    golden, golden_lps, _ = run(collect(engine, prompt, max_tokens=14, with_lp=True))
    before = eng.metrics_snapshot()
    toks, lps, _ = run(collect(eng, prompt, max_tokens=14, with_lp=True))
    after = eng.metrics_snapshot()
    assert toks == golden
    np.testing.assert_allclose(lps, golden_lps, atol=ATOL)
    assert after["spec_drafted_tokens"] > before["spec_drafted_tokens"] and eng._verify_fns
    assert after["mtp_layer_calls"] > before["mtp_layer_calls"]


def test_preemption_recomputes_as_for_any_model_without_state(cfg, params, run):
    """Out of blocks, a lane is preempted and recomputed: greedy output as
    with room to spare."""
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
    got, preemptions = served(tight)
    assert none == 0 and preemptions > 0 and got == golden


def test_the_host_tier_takes_latent_blocks_and_gives_them_back(cfg, params, run):
    eng = JaxServingEngine(cfg, params, dataclasses.replace(
        ENGINE_CFG, max_slots=2, max_model_len=64, num_kv_blocks=8, host_cache_blocks=32))
    try:
        t1, _, _ = run(collect(eng, prompt_of(32, 1), max_tokens=4))
        run(collect(eng, prompt_of(32, 2), max_tokens=4))
        assert eng.host_pool.offloaded > 0
        hits = eng.host_pool.hits
        t2, _, _ = run(collect(eng, prompt_of(32, 1), max_tokens=4))
        assert eng.host_pool.hits > hits and t2 == t1
    finally:
        eng.close()


# -- the card ----------------------------------------------------------------------

def _published():
    import json

    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f if '"Xing4.0-29B-A4B"' in line]
    if not rows:
        pytest.skip("the catalog beside the model-configs guide is not here")
    return rows[0]["config"]


def test_the_published_row_maps_onto_the_modules_config():
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs", "xing4.0-29b-a4b.json")
    with open(path) as f:
        shape = json.load(f)
    c = config_from_card(card(shape))
    assert isinstance(c, xm.Xing4Config) and c.dtype == jnp.bfloat16
    assert (c.hidden_size, c.num_heads, c.q_lora_rank, c.kv_lora_rank) == (3584, 32, 768, 512)
    assert (c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim) == (128, 64, 128)
    assert (c.intermediate_size, c.moe_intermediate_size) == (9216, 1024)
    assert (c.num_experts, c.num_experts_published, c.num_experts_per_tok) == (64, 64, 4)
    assert (c.num_layers, c.first_k_dense, c.num_mtp_layers, c.vocab_size) == (5, 1, 1, 131072)
    assert (c.hc_mult, c.hc_sinkhorn_iters, c.hc_eps, c.hc_clamp) == (4, 20, 1e-6, (-30.0, 30.0))
    assert c.rope_theta == 10000.0 and c.routed_scaling_factor == 2.0 and c.moe_renormalize
    assert (c.yarn_factor, c.yarn_original_positions, c.yarn_beta_fast, c.yarn_beta_slow) == (64.0, 4096, 32.0, 1.0)
    assert (c.latent_dim, c.latent_width) == (576, 640)
    # the harness writes scalar and list keys only: the flat spelling alone gives the same config
    flat = {k: v for k, v in shape.items() if not isinstance(v, dict)}
    assert "rope_scaling" not in flat and config_from_card(card(flat)) == c
    # the published row itself: 40 layers, two leading dense ones
    whole = config_from_card(card(_published()))
    assert (whole.num_layers, whole.first_k_dense) == (40, 2)
    assert dataclasses.replace(whole, num_layers=5, first_k_dense=1) == c


@pytest.mark.parametrize("key, value", [
    ("rope_scaling", {"type": "linear", "factor": 4.0}), ("rope_scaling", {**YARN, "mscale": 0.707}),
    ("n_group", 8), ("topk_group", 4), ("scoring_func", "softmax"), ("topk_method", "greedy"),
    ("n_shared_experts", 2), ("num_nextn_predict_layers", 2), ("attention_bias", True),
    ("hc_mult", 1), ("num_key_value_heads", 2),
])
def test_what_the_module_does_not_run_is_refused_by_its_name(key, value):
    with pytest.raises(ValueError, match=f"xing4_0.*{key}"):
        config_from_card(card({**SHAPE, key: value}))
