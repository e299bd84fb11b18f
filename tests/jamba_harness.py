"""What ``tests/test_jamba.py`` and ``tests/test_jamba_lane_rows.py`` share (a
helper, not collected): Jamba's tiny shape and its tolerances, the fixtures both
files use under their own names, and the harness that feeds chunk dispatches
and decode steps through ``tests/step_programs.py``'s kept programs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
from dynamo_tpu.engine_jax.weights import config_from_card
from dynamo_tpu.models import jamba

from .step_programs import card, chunk_program, decode_program, prompt_of

# ATOL, the float32 build: float32 on the CPU at the highest matmul precision
# on both sides, so the program and the reference differ by the order of their
# sums alone (a chunk's convolution against the whole sequence's, flash partials
# against one softmax): 2e-4 on logits of magnitude 4 is what the dense decoder
# and Kimi-Linear are allowed for the same reason (measured here: 5e-6). A wrong
# state, tail or page moves a logit by 1e-1 and more, and the recurrence taken
# in bfloat16 by 1e-2 (tests/test_jamba.py holds that it fails this tolerance).
ATOL = 2e-4
# ATOL_BF16, the served build (bfloat16 weights, float32 activations, the
# Mamba mixers' four projections in two bfloat16 parts, attention, the
# feed-forwards and the head in one: the MXU's rounding of their inputs, 8 bits
# of mantissa, six layers deep) against the float32 reference over the same
# weights: measured 0.04 on logits of magnitude 3.8, held at twice that (the
# first build, bfloat16 activations throughout, read 0.13 and would fail it).
# The benchmark's comparison (logprob_rms) is the tight one for this build.
ATOL_BF16 = 0.08

SHAPE = {
    "model_type": "jamba", "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 6,
    "num_attention_heads": 4, "num_key_value_heads": 1, "attn_layer_period": 3,
    "attn_layer_offset": 1, "expert_layer_period": 2, "expert_layer_offset": 1, "num_experts": 1,
    "num_experts_per_tok": 1, "mamba_expand": 2, "mamba_d_state": 8, "mamba_dt_rank": 8,
    "mamba_d_conv": 4, "mamba_conv_bias": True, "mamba_proj_bias": False, "rms_norm_eps": 1e-6,
    "vocab_size": 96, "tie_word_embeddings": True,
}
N_MAMBA = 4
ENGINE_CFG = EngineConfig(max_slots=4, kv_block_size=8, max_model_len=96,
                          prefill_chunk=16, decode_steps=4, top_logprobs=5)


@pytest.fixture(scope="module")
def cfg():
    return config_from_card(card(SHAPE), jnp.float32)


@pytest.fixture(scope="module")
def params(cfg):
    return jamba.init_params(jax.random.PRNGKey(3), cfg)


@pytest.fixture(scope="module")
def engine(cfg, params):
    eng = JaxServingEngine(cfg, params, ENGINE_CFG)
    yield eng
    eng.close()


SPARE_BLOCKS = 8


def dispatch_rows(cfg, params, dispatches, rows=8, slots=10, mb=8, n_decode=3, between=None, salt=None,
                  padding_token=0, hidden_rows=None):
    """Chunk dispatches of ``rows`` rows over ``slots`` slots, then ``n_decode``
    teacher-forced decode steps of every slot fed, off the state and pages the
    dispatches left. A dispatch is a list of its rows in order, ``(slot, n)``
    = the slot's next ``n`` prompt tokens (a lane's rows of one dispatch are
    its successive pieces) or ``None`` = a padding row; the rows left are
    padding, and every position that holds no prompt token holds
    ``padding_token`` (under position -1). ``hidden_rows``: a list that takes
    each dispatch's hidden states whole, padding rows and all. The k-th slot
    fed has blocks ``1 + k * mb`` onwards, and the pool
    holds ``SPARE_BLOCKS`` more that no table names: no page but those a fed
    slot's tokens reach may be written, block 0 (where a padding row's table
    points) and the spare ones included, which is held here for every caller.
    Every slot's state starts stale (``between`` may change it after a
    dispatch); its tokens are ``prompt_of(., salt or the slot)``. Returns
    ({slot: (its tokens, hidden states of its prompt, logits ``[prompt +
    n_decode, V]``)}, state, cache, the dispatches' counters)."""
    c, bs = 16, 8
    fed = list(dict.fromkeys(row[0] for d in dispatches for row in d if row))
    length = {slot: sum(row[1] for d in dispatches for row in d if row and row[0] == slot) for slot in fed}
    toks_of = {slot: np.asarray(prompt_of(length[slot] + n_decode, salt=salt or slot), np.int32) for slot in fed}
    table = {slot: 1 + k * mb + np.arange(mb, dtype=np.int32) for k, slot in enumerate(fed)}
    cache = jamba.make_kv_cache(cfg, 1 + len(fed) * mb + SPARE_BLOCKS, bs)
    state = jax.tree.map(lambda a: a + 7.0, jamba.make_slot_state(cfg, slots))  # stale, every slot
    at, hidden, sums = dict.fromkeys(fed, 0), {slot: [] for slot in fed}, []
    chunk = chunk_program(jamba, cfg)
    for d in dispatches:
        toks, pos = np.full((rows, c), padding_token, np.int32), np.full((rows, c), -1, np.int32)
        tables, lanes = np.zeros((rows, mb), np.int32), np.full((rows,), slots, np.int32)
        for r, row in enumerate(d):
            if row is None:
                continue
            slot, n = row
            toks[r, :n], pos[r, :n] = toks_of[slot][at[slot]:at[slot] + n], np.arange(at[slot], at[slot] + n)
            tables[r], lanes[r] = table[slot], slot
            at[slot] += n
        h, cache, state, counted = chunk(
            params, jnp.asarray(toks), jnp.asarray(pos), cache, jnp.asarray(tables),
            state, jnp.asarray(lanes))
        for r, row in enumerate(d):
            if row is not None:
                hidden[row[0]].append(np.asarray(h[r, :row[1]], np.float32))
        if hidden_rows is not None:
            hidden_rows.append(np.asarray(h, np.float32))
        sums.append(dict(zip(jamba.COUNTERS, np.asarray(counted).tolist())))
        if between is not None:
            state = between(state)
    hidden = {slot: np.concatenate(hidden[slot]) for slot in fed}
    logits = {slot: [np.asarray(jamba.lm_head(params, cfg, jnp.asarray(hidden[slot])), np.float32)] for slot in fed}
    if n_decode:
        lanes_tables = np.zeros((slots, mb), np.int32)
        toks, pos = np.zeros((slots,), np.int32), np.full((slots,), -1, np.int32)
        forcing = np.zeros((slots, bs * mb), np.int32)  # a table's positions wide: one program a geometry
        for slot in fed:
            lanes_tables[slot], toks[slot], pos[slot] = table[slot], toks_of[slot][length[slot]], length[slot]
            forcing[slot, :len(toks_of[slot])] = toks_of[slot]

        out = decode_program(jamba, cfg, n_decode, 8 * mb - 1)(  # teacher forcing: each sequence's own next token
            params, jnp.asarray(toks), jnp.asarray(pos), cache, jnp.asarray(lanes_tables), state, jnp.asarray(forcing))
        assert [int(out[1][slot]) for slot in fed] == [length[slot] + n_decode for slot in fed]
        assert np.asarray(out[6]).tolist() == [n_decode * N_MAMBA] + [0] * (len(jamba.COUNTERS) - 1)
        for slot in fed:
            logits[slot].append(np.asarray(out[3], np.float32)[:, slot])
        state, cache = out[5], out[4]
    reached = np.zeros((cache["k"].shape[1],), bool)
    for slot in fed:
        reached[table[slot][:-(-(length[slot] + n_decode) // bs)]] = True
    for name in ("k", "v"):
        pool = np.asarray(cache[name], np.float32)
        assert not pool[:, ~reached].any(), f"{name}: a page outside what the fed slots' tokens reach was written"
        assert all(pool[:, block].any() for block in np.flatnonzero(reached)), name
    return ({slot: (toks_of[slot], hidden[slot], np.concatenate(logits[slot])) for slot in fed},
            state, cache, sums)


def recurrence_inputs(cfg, rows, t, seed=0, step=0.0):
    """Inputs as ``mamba_mixer`` makes them (the step size after its softplus,
    ``step`` added before it) and a carried state."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    n, d = cfg.mamba_d_state, cfg.d_inner
    delta = jax.nn.softplus(jax.random.normal(ks[0], (rows, t, d)) + step)
    x, b, c = (jax.random.normal(k, shape) for k, shape in zip(
        ks[1:4], [(rows, t, d), (rows, t, n), (rows, t, n)]))
    return (delta, x, b, c), jax.random.normal(ks[4], (rows, n, d))


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


