"""Jamba on the served path, at a tiny size on the CPU (hidden 64, six layers
in the pattern Mamba, attention, Mamba, Mamba, attention, Mamba; a state of
8 x 128 a layer, dt rank 8, a convolution of 4, four query heads over ONE
key/value head of 16).

The program (``models/jamba.py``: chunked prefill through per-slot state and
K/V pages, then decode) is held against the benchmark's plain reference
(``benchmark/reference_jamba.py``: one sequence, token by token, no cache); the
engine against both, and against the refusals a model with per-slot state owes
whatever would hand its pages over without it.
"""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_jamba as ref
from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
from dynamo_tpu.engine_jax.weights import config_from_card
from dynamo_tpu.kv.pages import MigrationRejected, StateNotPortable
from dynamo_tpu.models import jamba, llama, module_for
from dynamo_tpu.ops.pallas.selective_scan import ROWS, selective_scan, selective_step

from .jamba_harness import (  # noqa: F401  (the fixtures are this file's too)
    ATOL, ATOL_BF16, ENGINE_CFG, N_MAMBA, SHAPE, SPARE_BLOCKS, cfg, dispatch_rows, engine,
    lowered_step_programs, params, recurrence_inputs,
)
from .step_programs import (  # noqa: F401  (highest_precision: autouse, for this file's tests)
    answer, card, highest_precision, patched, prompt_of, reference_program, run_out, served, step, submit,
)

PUBLISHED = "benchmark/configs/jamba2-3b.json"


def test_the_layer_kinds_are_the_published_pattern(cfg):
    """Attention at 7 and 21 of 28 (``i % 14 == 7``), the other 26 Mamba, in
    runs of 7, 13 and 6; the tiny shape keeps both kinds and a run of two."""
    with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), PUBLISHED)) as f:
        published = config_from_card(card(json.load(f)))
    kinds = jamba.layer_kinds(published)
    assert [i for i, k in enumerate(kinds) if k == "attn"] == [7, 21] and len(kinds) == 28
    assert jamba.segments(published) == (
        ("mamba", 7), ("attn", 1), ("mamba", 13), ("attn", 1), ("mamba", 6))
    assert (published.d_inner, published.head_dim, published.num_kv_heads) == (5120, 128, 1)
    assert ref.sizes(SHAPE)["kinds"] == jamba.layer_kinds(cfg) == (
        "mamba", "attn", "mamba", "mamba", "attn", "mamba")
    assert module_for(cfg) is jamba and module_for(llama.LLAMA_PRESETS["tiny"]) is llama


def prefill_then_decode(cfg, params, chunks, n_decode=3, between=None):
    """A prompt fed a chunk a dispatch into slot 2 of 4 (the dispatch's second
    row is padding) and decoded: (tokens, logits ``[sum(chunks) + n_decode,
    V]``, state, cache, the chunks' counters)."""
    served, state, cache, sums = dispatch_rows(
        cfg, params, [[(2, n)] for n in chunks], rows=2, slots=4, n_decode=n_decode, between=between,
        salt=len(chunks))
    tokens, _, logits = served[2]
    return tokens, logits, state, cache, sums


@pytest.mark.parametrize("dtype, atol", [(jnp.float32, ATOL), (jnp.bfloat16, ATOL_BF16)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("chunks", [(16,), (16, 16, 5), (7, 16, 14)],
                         ids=["one_chunk", "a_prompt_that_ends_mid_chunk", "a_short_first_chunk"])
def test_chunked_prefill_then_decode_agrees_with_the_plain_reference(chunks, dtype, atol):
    """A prompt fed in chunks whose boundaries lie inside it, each starting
    from the slot's Mamba state and the K/V pages the last one left, then three
    decode steps off the same state, against the reference's one pass over the
    whole sequence. The other slots' state and the other pages stay as they
    were, and the first chunk alone resets the slot."""
    cfg = config_from_card(card(SHAPE), dtype)
    params = jamba.init_params(jax.random.PRNGKey(3), cfg)
    tokens, got, state, cache, sums = prefill_then_decode(cfg, params, chunks)
    want = np.asarray(reference_program(ref, SHAPE)(params, jnp.asarray(tokens), jnp.arange(len(tokens))))
    np.testing.assert_allclose(got, want, atol=atol)
    for leaf in jax.tree.leaves(state):  # slots 0, 1 and 3 of every layer: untouched
        assert float(leaf[:, (0, 1, 3)].min()) == float(leaf[:, (0, 1, 3)].max()) == 7.0
    # pages outside the lane's table (block 0: where a padding row's table points; 9 on, the spare ones)
    assert cache["k"].shape[1] == 9 + SPARE_BLOCKS
    for pool in (np.asarray(cache["k"]), np.asarray(cache["v"])):
        assert not pool[:, 0].any() and not pool[:, 9:].any() and pool[:, 1].any()
    assert [s["slot_state_resets"] for s in sums] == [1] + [0] * (len(chunks) - 1)
    assert [s["ssm_chunk_tokens"] for s in sums] == [N_MAMBA * n for n in chunks]


def test_a_recurrence_taken_in_bfloat16_fails_the_float32_tolerance(cfg, params, monkeypatch):
    """What ATOL is there to catch: the same float32 program with the scan's
    inputs and state rounded to bfloat16 a token is off by more than ATOL."""
    scan = jamba._scan_tokens

    def low(a):  # a decode step's state comes with its layer's index, which stays
        return a.astype(jnp.bfloat16).astype(jnp.float32) if a.dtype == jnp.float32 else a

    def rounded(lp, s, delta, x, b, c, valid, above=None):
        y, s = scan(lp, *jax.tree.map(low, (s, delta, x, b, c)), valid, above)
        return y, jax.tree.map(low, s)

    patched(monkeypatch, jamba, "_scan_tokens", rounded)
    tokens, got, *_ = prefill_then_decode(cfg, params, (16, 9))
    want = np.asarray(reference_program(ref, SHAPE)(params, jnp.asarray(tokens), jnp.arange(len(tokens))))
    assert np.abs(got - want).max() > 5 * ATOL


def test_the_convolutions_tail_carries_across_a_chunk_boundary(cfg, params):
    """After a chunk of 7 tokens a layer's tail holds its inputs 4, 5 and 6
    (oldest first), and a second chunk that starts from a zeroed tail is
    wrong by far more than ATOL."""
    tokens, got, *_ = prefill_then_decode(cfg, params, (7, 9))
    want = np.asarray(reference_program(ref, SHAPE)(params, jnp.asarray(tokens), jnp.arange(len(tokens))))
    np.testing.assert_allclose(got, want, atol=ATOL)

    seen = {}

    def zeroed(state):
        seen.setdefault("tail", np.asarray(state["conv"][0][0, 2]))
        return {"s": state["s"], "conv": jax.tree.map(jnp.zeros_like, state["conv"])}

    _, cut, *_ = prefill_then_decode(cfg, params, (7, 9), between=zeroed)
    assert np.abs(cut[7:] - want[7:]).max() > 100 * ATOL
    np.testing.assert_allclose(cut[:7], want[:7], atol=ATOL)
    # layer 0's inputs: the x half of the in-projection of the normed embedding
    lp = jax.tree.map(lambda a: a[0], params["mamba"][0])
    u = llama.rms_norm(params["embed"][jnp.asarray(tokens[:7])], lp["mixer_norm"], cfg.rms_norm_eps)
    x = np.asarray(u @ lp["w_in"])[:, :cfg.d_inner]
    np.testing.assert_allclose(seen["tail"].reshape(3, cfg.d_inner), x[4:7], atol=1e-5)


def test_a_decode_steps_convolution_is_the_chunks_on_a_row_of_one_token(cfg, params):
    """``_convolve``'s one-token form (the tail flat, its taps slices of it)
    against its chunk form on rows whose first token alone is valid, or none:
    the mixer's ``x`` of that token and the new tail, bit for bit; a lane that
    does not decode keeps its tail."""
    lp = jax.tree.map(lambda a: a[0], params["mamba"][0])
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    d, taps = cfg.d_inner, cfg.mamba_d_conv - 1
    x, tail = jax.random.normal(ks[0], (5, 2, d)), jax.random.normal(ks[1], (5, taps * d))
    valid = jnp.asarray([[True, False]] * 3 + [[False, False]] * 2)
    want_x, want_tail = jamba._convolve(lp, cfg, x, valid, tail)
    got_x, got_tail = jamba._convolve(lp, cfg, x[:, :1], valid[:, :1], tail)
    assert np.array_equal(np.asarray(got_x[:, 0]), np.asarray(want_x[:, 0]))
    assert np.array_equal(np.asarray(got_tail), np.asarray(want_tail))
    assert np.array_equal(np.asarray(got_tail[3:]), np.asarray(tail[3:]))
    assert np.array_equal(np.asarray(got_tail[:3, -d:]), np.asarray(x[:3, 0]))


def one_token_at_a_time(lp, s0, delta, x, b, c, valid):
    """What the kernel replaces: ``_scan_tokens``'s one-token form (a decode
    step's) over the tokens in turn, outputs past a row's valid tokens zeroed."""
    def token(s, xs):
        y, s = jamba._scan_tokens(lp, s, *(a[:, None] for a in xs))
        return s, jnp.where(xs[4][:, None], y[:, 0], 0.0)

    s, y = jax.lax.scan(token, s0, tuple(jnp.moveaxis(a, 1, 0) for a in (delta, x, b, c, valid)))
    return jnp.moveaxis(y, 0, 1), s


def assert_float32_equal(s, y, want_s, want_y):
    """The tolerance the step and the chunk form have been held to since PR 41."""
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("t", [11, 40])
@pytest.mark.parametrize("start", ["zero", "carried"])
def test_a_decode_step_is_one_trip_of_the_chunks_token_loop(cfg, params, t, start):
    """The step form and the chunk form of the recurrence are one body: ``t``
    tokens taken one at a time (the step kernel, a token a call) give the chunk
    kernel's state and outputs, for rows that are full, partly valid (one
    token; a third of the row) and empty. Both kernels take the same products
    and sums in the same order, the sum over N from n = 0 in both, and on the
    chip they agree bit for bit (``tools/profile_decode.py mamba`` prints the
    largest difference). Interpreted on the CPU they agree to float32 rounding
    over tokens in a row: its compiler fuses ONE of the two products of
    ``exp(delta A) s + (delta x) B`` with the add, and not the same one in both
    kernels (the chunk's state is a loop's carry, the step's a load), so the sum
    is rounded once more in one of them. Where both products are exact it
    cannot matter: the tests of one step below hold bit for bit."""
    lp = jax.tree.map(lambda a: a[0], params["mamba"][0])
    xs, carried = recurrence_inputs(cfg, 4, t, seed=t)
    s0 = carried if start == "carried" else jnp.zeros_like(carried)
    n_valid = np.asarray([t, t // 3, 1, 0])
    valid = jnp.arange(t)[None, :] < jnp.asarray(n_valid)[:, None]
    y, s = jamba._scan_tokens(lp, s0, *xs, valid)
    want_y, want_s = one_token_at_a_time(lp, s0, *xs, valid)
    assert_float32_equal(s, y, want_s, want_y)
    assert np.array_equal(np.asarray(s[3]), np.asarray(s0[3]))  # a row of padding keeps its state


def test_an_empty_rows_state_comes_back_bit_for_bit_and_its_outputs_are_zeros(cfg, params):
    """A padding row of a rung: no valid token, whatever its inputs hold (here
    infinities and NaNs, which a computed-and-thrown-away token would spread)."""
    a = -jnp.exp(params["mamba"][0]["a_log"][0])
    (delta, x, b, c), s0 = recurrence_inputs(cfg, 3, 16, seed=1)
    x = x.at[1].set(jnp.nan)
    delta = delta.at[1].set(jnp.inf)
    y, s = selective_scan(delta, x, b, c, a, s0, jnp.asarray([16, 0, 5]), interpret=True)
    assert np.array_equal(np.asarray(s[1]), np.asarray(s0[1]))
    assert not np.asarray(y[1]).any() and not np.asarray(y[2, 5:]).any()
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(np.asarray(s)).all()
    assert np.asarray(y[2, :5]).all() and np.asarray(y[0]).all()


def test_the_chunk_kernel_takes_a_chunk_that_is_no_multiple_of_its_tile(cfg, params):
    """300 tokens are two tiles of 128 and 44 of a third (the chunk is padded to
    384 around the kernel): the state rides from a tile to the next on the
    chip, and a row that ends inside the second tile stops there."""
    from dynamo_tpu.ops.pallas import selective_scan as kernel

    lp = jax.tree.map(lambda a: a[0], params["mamba"][0])
    t = 2 * kernel.TILE + 44
    xs, s0 = recurrence_inputs(cfg, 3, t, seed=2, step=-2.0)
    valid = jnp.arange(t)[None, :] < jnp.asarray([t, kernel.TILE + 9, 0])[:, None]
    y, s = jamba._scan_tokens(lp, s0, *xs, valid)
    want_y, want_s = one_token_at_a_time(lp, s0, *xs, valid)
    assert y.shape == want_y.shape
    assert_float32_equal(s, y, want_s, want_y)


def test_the_chunk_kernel_stands_a_step_whose_decay_underflows(cfg, params):
    """A step size near 30: ``exp(delta A)`` is 0 in float32 for every state
    row but the first few, so a token forgets what came before it. Nothing
    overflows, nothing is NaN, and it is the step still."""
    lp = jax.tree.map(lambda a: a[0], params["mamba"][0])
    xs, s0 = recurrence_inputs(cfg, 2, 24, seed=3, step=30.0)
    assert float(jnp.exp(-xs[0].min() * cfg.mamba_d_state)) == 0.0
    valid = jnp.arange(24)[None, :] < jnp.asarray([24, 13])[:, None]
    y, s = jamba._scan_tokens(lp, s0, *xs, valid)
    want_y, want_s = one_token_at_a_time(lp, s0, *xs, valid)
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(np.asarray(s)).all()
    assert_float32_equal(s, y, want_s, want_y)


def one_step_inputs(cfg, slots, layers, seed, step=0.0):
    """One token of every slot as ``mamba_mixer`` makes it, and a run's carried
    state. The state, B and C are signed powers of two, so both products of
    ``exp(delta A) s + (delta x) B`` and each ``s C`` are exact in float32:
    whichever multiply the CPU's compiler fuses with an add, every sum is
    rounded once, and two kernels that take the same sums in the same order
    agree bit for bit (in another order, n = 0 last, they would not)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    n, d = cfg.mamba_d_state, cfg.d_inner

    def power_of_two(k, sign, shape):
        return jnp.exp2(jnp.round(2.0 * jax.random.normal(k, shape))) * jnp.where(
            jax.random.bernoulli(sign, 0.5, shape), 1.0, -1.0)

    delta = jax.nn.softplus(jax.random.normal(ks[0], (slots, d)) + step)
    x = jax.random.normal(ks[1], (slots, d))
    b, c = power_of_two(ks[2], ks[3], (slots, n)), power_of_two(ks[4], ks[5], (slots, n))
    return (delta, x, b, c), power_of_two(ks[6], ks[7], (layers, slots, n, d))


def chunk_of_one_token(a, s0, delta, x, b, c, valid):
    """The chunk kernel on a chunk of that one token a row."""
    y, s = selective_scan(delta[:, None], x[:, None], b[:, None], c[:, None], a, s0,
                          valid.astype(jnp.int32), interpret=True)
    return y[:, 0], s


@pytest.mark.parametrize("slots, decoding", [
    (ROWS, "all"), (ROWS, "some"), (ROWS, "none"), (4, "some"), (ROWS + 4, "some"), (2 * ROWS, "all"),
], ids=["a_full_block", "lanes_that_do_not_decode", "no_lane_decodes", "fewer_slots_than_a_block",
        "slots_that_are_no_multiple_of_the_block", "two_blocks"])
def test_the_step_kernel_is_the_chunk_kernel_on_a_chunk_of_one_token(cfg, params, slots, decoding):
    """``selective_step`` on the middle layer of a run of three against
    ``selective_scan`` on a one-token chunk from that layer's state: the state
    and the outputs bit for bit; a lane that does not decode (``pos < 0``) gets
    its state back bit for bit, whatever its inputs hold (NaNs here), and zeros
    for its output; the run's other layers are not touched. Slot counts under,
    at and over the kernel's row block, and one that is no multiple of it (the
    state's last block is a part block, the token arrays are padded)."""
    a = -jnp.exp(params["mamba"][0]["a_log"][0])
    (delta, x, b, c), run = one_step_inputs(cfg, slots, 3, seed=slots)
    valid = {"all": jnp.ones((slots,), bool), "none": jnp.zeros((slots,), bool),
             "some": jnp.arange(slots) % 3 != 1}[decoding]
    x = jnp.where(valid[:, None], x, jnp.nan)
    before = np.asarray(run)
    y, after = selective_step(delta, x, b, c, a, run, jnp.int32(1), valid, interpret=True)
    want_y, want_s = chunk_of_one_token(a, jnp.asarray(before[1]), delta, x, b, c, valid)
    y, after, live = np.asarray(y), np.asarray(after), np.asarray(valid)
    assert np.array_equal(after[1], np.asarray(want_s)) and np.array_equal(y, np.asarray(want_y))
    assert np.array_equal(after[1][~live], before[1][~live]) and not y[~live].any()
    assert np.array_equal(after[[0, 2]], before[[0, 2]])
    assert np.isfinite(after).all() and np.isfinite(y).all()
    if live.any():
        assert (after[1][live] != before[1][live]).any() and y[live].all()


def test_the_step_kernel_stands_a_step_whose_decay_underflows(cfg, params):
    """The twin of the chunk kernel's test: a step size near 30, so that
    ``exp(delta A)`` is 0 in float32 for every state row but the first few and
    a token forgets what came before it. Nothing overflows, nothing is NaN,
    and it is the chunk kernel's token still, bit for bit."""
    a = -jnp.exp(params["mamba"][0]["a_log"][0])
    (delta, x, b, c), run = one_step_inputs(cfg, ROWS, 1, seed=5, step=30.0)
    assert float(jnp.exp(-delta.min() * cfg.mamba_d_state)) == 0.0
    valid = jnp.arange(ROWS) != 2
    y, after = selective_step(delta, x, b, c, a, run, jnp.int32(0), valid, interpret=True)
    want_y, want_s = chunk_of_one_token(a, run[0], delta, x, b, c, valid)
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(np.asarray(after)).all()
    assert np.array_equal(np.asarray(after[0]), np.asarray(want_s))
    assert np.array_equal(np.asarray(y), np.asarray(want_y))
    # the last state row's decay is gone: the new state there is (delta x) B alone
    forgot = np.asarray((delta * x) * b[:, -1:])
    assert np.array_equal(np.asarray(after[0, :, -1])[np.asarray(valid)], forgot[np.asarray(valid)])


@pytest.mark.parametrize("shape, what", [
    (SHAPE, "accepted"),
    (dict(SHAPE, num_experts=16, num_experts_per_tok=2), "refused"),
    ({"model_type": "qwen2", "hidden_size": 64}, "llama"),
    ({"model_type": "some_moe", "num_experts": 64, "hidden_size": 64}, "impostor"),
], ids=["num_experts_1", "an_expert_jamba", "a_qwen_card", "another_expert_card"])
def test_config_from_card_picks_the_module_by_model_type(shape, what):
    """``num_experts: 1`` under ``model_type: jamba`` is the dense model and is
    read; an expert Jamba is refused by name; the other cards go where they
    went (the Kimi card: tests/test_kimi_linear.py)."""
    if what == "accepted":
        c = config_from_card(card(shape), jnp.float32)
        assert isinstance(c, jamba.JambaConfig) and module_for(c) is jamba
        assert (c.head_dim, c.d_inner, c.mamba_dt_rank, c.tie_embeddings) == (16, 128, 8, True)
    elif what == "refused":
        with pytest.raises(ValueError, match="model_type 'jamba' with num_experts = 16"):
            config_from_card(card(shape))
    elif what == "llama":
        assert isinstance(config_from_card(card(shape)), llama.LlamaConfig)
    else:
        with pytest.raises(ValueError, match="no module here runs it"):
            config_from_card(card(shape))


def test_serving_a_qwen_card_imports_no_other_models_module():
    """A third module costs a Qwen start-up nothing: ``config_from_card`` and
    ``module_for`` import a module in its own branch alone."""
    code = (
        "import sys, types\n"
        "from dynamo_tpu.engine_jax.weights import config_from_card\n"
        "from dynamo_tpu.models import module_for\n"
        "import dynamo_tpu.engine_jax.engine\n"
        "c = config_from_card(types.SimpleNamespace(model_config={'model_type': 'qwen2'}))\n"
        "assert module_for(c).__name__ == 'dynamo_tpu.models.llama'\n"
        "print([m for m in ('jamba', 'kimi_linear') if 'dynamo_tpu.models.' + m in sys.modules])\n"
    )
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=110,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0 and done.stdout.strip().endswith("[]"), done.stdout + done.stderr[-800:]


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
    assert snap["ssm_layer_calls"] > 0 and snap["slot_state_resets"] >= 1
    assert set(jamba.COUNTERS) <= set(snap) and not any(k.startswith(("moe_", "kda_")) for k in snap)
    # the module says what its programs read of the tables: the live part, not all
    assert 0 < snap["chunk_history_tiles_read"] <= snap["chunk_history_tiles_full"]
    # the module says a lane may fill several rows; this ladder, [1, 4], has no rung that holds them
    assert jamba.LANE_TAKES_ROWS and engine._lane_rows and len(jamba.COUNTERS) == 6
    assert snap["chunk_rows_live"] == snap["chunk_lanes_fed"] > 0 == snap["ssm_state_handovers"]
    assert 0 < snap["decode_history_tiles_read"] <= snap["decode_history_tiles_full"]
    assert set(engine.cache) == {"k", "v"} and engine.cache["k"].shape[0] == 2
    tiers = list(snap["attention_tiers"].values())
    assert tiers and all(t == {"tier": "dense", "interpret": False} for t in tiers)


@pytest.fixture(scope="module")
def wide_engine(cfg, params):
    """64 slots: the ladder [8, 16, 64], where a lane fills several rows of a dispatch."""
    eng = JaxServingEngine(cfg, params, EngineConfig(
        max_slots=64, kv_block_size=8, max_model_len=96, prefill_chunk=16, decode_steps=4))
    yield eng
    eng.close()


# (the engine; prompt tokens; chunk dispatches; rows dispatched; rows that hold a piece; lanes fed; groups
# the chunk program ran; their rows)
SERVED = {
    # ladder [1, 4]: a row a dispatch, and a rung of one row is one group
    "a_row_a_dispatch": ("engine", 40, 3, 3, 3, 3, 3, 3),
    # ladder [8, 16, 64], the 8-row rung: three rows are one group of four, five rows two groups (as a
    # prompt of 300 tokens and one of 600 in chunks of 128), the fifth row going on from its slot's entries
    "three_rows_of_an_eight_row_rung": ("wide_engine", 40, 1, 8, 3, 1, 1, 4),
    "five_rows_of_an_eight_row_rung": ("wide_engine", 75, 1, 8, 5, 1, 2, 8),
}


@pytest.mark.parametrize("case", list(SERVED))
def test_the_counters_count_what_a_served_prompt_did(request, case):
    """A prompt and 4 tokens answered: every one of the four Mamba layers
    advances the prompt's tokens in the chunk program, and the lane's state goes
    to the chip and back once a layer and GROUP of a dispatch's rows it has a
    row in (the kernel holds it there over the group's tokens); the groups and
    the decode steps each run the four layers. ``chunk_rows_computed`` rises by
    the rows of the groups the chunk program ran, as far as the last row that
    holds a piece, and not at all in a decode dispatch."""
    which, n, dispatches, dispatched, rows, lanes, groups, computed = SERVED[case]
    engine = request.getfixturevalue(which)
    before = engine.metrics_snapshot()
    served(engine, prompt_of(n, salt=11), 4)
    after = engine.metrics_snapshot()
    rise = {k: after[k] - before[k] for k in (
        *jamba.COUNTERS, "chunk_rows_live", "chunk_lanes_fed", "chunk_rows_dispatched", "prompt_dispatches")}
    assert (rise["prompt_dispatches"], rise["chunk_rows_dispatched"]) == (dispatches, dispatched)
    assert (rise["chunk_rows_live"], rise["chunk_lanes_fed"]) == (rows, lanes)
    assert (rise["ssm_chunk_tokens"], rise["ssm_state_passes"]) == (N_MAMBA * n, N_MAMBA * groups)
    assert rise["slot_state_resets"] == 1
    # the rows that took their state from the row above them in their group
    assert rise["ssm_state_handovers"] == rows - groups
    # the groups + the decode dispatches' 4 steps each (3 more tokens: 1 or 2 dispatches), which computed
    # no chunk row
    assert rise["ssm_layer_calls"] in (N_MAMBA * (groups + 4), N_MAMBA * (groups + 8))
    assert rise["chunk_rows_computed"] == computed


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
        with pytest.raises(StateNotPortable, match="JambaConfig keeps state per slot"):
            calls[what]()
    elif what == "the host tier":
        with pytest.raises(StateNotPortable, match="the host tier"):
            JaxServingEngine(cfg, params, EngineConfig(
                max_slots=2, kv_block_size=8, max_model_len=64, host_cache_blocks=4))
    else:
        with pytest.raises(ValueError, match="one device"):
            JaxServingEngine(cfg, params, ENGINE_CFG, mesh=object())
        with pytest.raises(NotImplementedError, match="one device"):
            jamba.param_shardings(cfg, object())


def test_the_step_programs_carry_the_three_scopes(engine):
    """The device trace finds the mechanisms by name: ``mamba``, ``attn`` and
    ``mlp`` are scopes of both step programs."""
    for program in lowered_step_programs(engine):
        names = set(re.findall(r'loc\("(?:[^"]*/)?(mamba|attn|mlp)/', program.as_text(debug_info=True)))
        assert names == {"mamba", "attn", "mlp"}, names
