"""Model correctness: paged attention vs dense reference, prefill/decode parity,
tensor-parallel sharded forward vs single-device forward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.kv import pages as kv_pages
from dynamo_tpu.models.llama import (
    HISTORY_TILE,
    LLAMA_PRESETS,
    chunk_history_tiles,
    decode_history_tiles,
    dequantize_kv,
    flush_window,
    forward,
    forward_chunk,
    forward_window,
    gather_history,
    history_widths,
    init_params,
    make_kv_cache,
    param_shardings,
    quantize_kv,
    with_live_history,
)
from dynamo_tpu.ops.attention import (
    gather_pages,
    paged_attention,
    write_kv_to_pages,
    write_kv_to_pool,
)
from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

import dataclasses

# float32 variant of the tiny preset: numerics tests compare prefill-vs-decode
# and sharded-vs-unsharded paths, which only agree tightly above bf16 precision.
CFG = dataclasses.replace(LLAMA_PRESETS["tiny"], dtype=jnp.float32)
BLOCK = 8


def dense_causal_attention(q, k, v):
    """Plain causal attention reference: q,k,v [B,T,H,D] (same H)."""
    b, t, h, d = q.shape
    scores = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32) * d**-0.5
    mask = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, -1).astype(v.dtype), v)


def test_write_then_gather_roundtrip():
    rng = jax.random.PRNGKey(0)
    k_cache = jnp.zeros((6, BLOCK, 2, 4))
    v_cache = jnp.zeros((6, BLOCK, 2, 4))
    k_new = jax.random.normal(rng, (1, 10, 2, 4))
    positions = jnp.arange(10)[None, :]
    tables = jnp.array([[3, 1, 0]])  # logical blocks 0,1 → physical 3,1
    k_cache, v_cache = write_kv_to_pages(k_cache, v_cache, k_new, k_new, positions, tables)
    gathered = gather_pages(k_cache, tables)  # [1, 24, 2, 4]
    np.testing.assert_allclose(gathered[0, :10], k_new[0], rtol=1e-6)
    assert jnp.all(gathered[0, 10:] == 0)


def test_padding_positions_dropped():
    k_cache = jnp.zeros((2, BLOCK, 1, 2))
    k_new = jnp.ones((1, 4, 1, 2))
    positions = jnp.array([[0, 1, -1, -1]])
    tables = jnp.array([[0]])
    k_cache, _ = write_kv_to_pages(k_cache, k_cache, k_new, k_new, positions, tables)
    assert float(k_cache.sum()) == 4.0  # only 2 tokens × 2 dims written


def test_paged_attention_matches_dense():
    rng = jax.random.PRNGKey(1)
    b, t, h, d = 2, 12, 4, 8
    q = jax.random.normal(rng, (b, t, h, d))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (b, t, h, d))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (b, t, h, d))

    n_blocks = 1 + b * ((t + BLOCK - 1) // BLOCK)
    k_cache = jnp.zeros((n_blocks, BLOCK, h, d))
    v_cache = jnp.zeros((n_blocks, BLOCK, h, d))
    tables = jnp.array([[1, 2], [3, 4]])
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    k_cache, v_cache = write_kv_to_pages(k_cache, v_cache, k, v, positions, tables)

    out = paged_attention(q, k_cache, v_cache, tables, positions)
    ref = dense_causal_attention(q, k, v)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_gqa_paged_attention_matches_repeated_dense():
    rng = jax.random.PRNGKey(2)
    b, t, h, kvh, d = 1, 9, 4, 2, 8
    q = jax.random.normal(rng, (b, t, h, d))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (b, t, kvh, d))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (b, t, kvh, d))
    k_cache = jnp.zeros((4, BLOCK, kvh, d))
    v_cache = jnp.zeros((4, BLOCK, kvh, d))
    tables = jnp.array([[0, 1]])
    positions = jnp.arange(t)[None]
    k_cache, v_cache = write_kv_to_pages(k_cache, v_cache, k, v, positions, tables)
    out = paged_attention(q, k_cache, v_cache, tables, positions)
    ref = dense_causal_attention(q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@pytest.fixture(scope="module")
def tiny_model():
    params = init_params(jax.random.PRNGKey(0), CFG)
    return params


def _prefill_all(params, tokens, n_blocks=8):
    b, t = tokens.shape
    cache = make_kv_cache(CFG, n_blocks, BLOCK, dtype=jnp.float32)
    mb = n_blocks // b
    tables = jnp.arange(n_blocks, dtype=jnp.int32).reshape(b, mb)
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    logits, cache = forward(params, CFG, tokens, positions, cache, tables)
    return logits, cache, tables


def test_prefill_decode_parity(tiny_model):
    """Decoding token-by-token must reproduce the full-prefill logits."""
    params = tiny_model
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 10), 0, CFG.vocab_size)
    full_logits, _, _ = _prefill_all(params, tokens)

    cache = make_kv_cache(CFG, 8, BLOCK, dtype=jnp.float32)
    tables = jnp.arange(8, dtype=jnp.int32).reshape(1, 8)
    # prefill first 5, then decode 5 one at a time
    logits5, cache = forward(
        params, CFG, tokens[:, :5], jnp.arange(5)[None], cache, tables
    )
    step_logits = [logits5[:, -1]]
    for i in range(5, 10):
        lg, cache = forward(
            params, CFG, tokens[:, i : i + 1], jnp.array([[i]]), cache, tables
        )
        step_logits.append(lg[:, 0])
    np.testing.assert_allclose(
        jnp.stack(step_logits, 1), full_logits[:, 4:], rtol=1e-4, atol=1e-4
    )


def test_padded_batch_rows_ignored(tiny_model):
    """A padding row (positions = -1) must not disturb real rows."""
    params = tiny_model
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, 6), 0, CFG.vocab_size)
    solo_logits, _, _ = _prefill_all(params, tokens, n_blocks=2)

    padded_tokens = jnp.concatenate([tokens, jnp.zeros((1, 6), jnp.int32)])
    positions = jnp.stack([jnp.arange(6), jnp.full((6,), -1)])
    cache = make_kv_cache(CFG, 4, BLOCK, dtype=jnp.float32)
    tables = jnp.array([[0, 1], [2, 3]], jnp.int32)
    both_logits, _ = forward(params, CFG, padded_tokens, positions, cache, tables)
    np.testing.assert_allclose(both_logits[0], solo_logits[0], rtol=1e-5, atol=1e-5)


def test_tp_sharded_forward_matches_single_device(tiny_model):
    """tp=2, dp=2 sharded forward == unsharded forward (8 virtual CPU devices)."""
    params = tiny_model
    mesh = make_mesh(MeshConfig(dp=2, tp=2, sp=1), jax.devices()[:4])
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 6), 0, CFG.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(6), (2, 6))
    cache = make_kv_cache(CFG, 4, BLOCK, dtype=jnp.float32)
    tables = jnp.array([[0, 1], [2, 3]], jnp.int32)

    ref_logits, ref_cache = forward(params, CFG, tokens, positions, cache, tables)

    shardings = param_shardings(CFG, mesh)
    sharded_params = jax.device_put(params, shardings)
    sharded = jax.jit(lambda p, tk, ps, c, bt: forward(p, CFG, tk, ps, c, bt))(
        sharded_params, tokens, positions, cache, tables
    )
    np.testing.assert_allclose(sharded[0], ref_logits, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(sharded[1]["k"], ref_cache["k"], rtol=1e-5, atol=1e-5)


# -- the step programs' pool handling vs the plain forward path --------------
#
# forward_chunk and the decode dispatch (gather_history -> forward_window x W
# -> flush_window) read the pool by (layer, page) and write it with one
# scatter; `forward` writes layer by layer (write_kv_to_pages). Same rows, same
# values, nothing else touched.

LANES, MB, N_BLOCKS, CHUNK, WINDOW = 3, 3, 10, 6, 4  # a table holds 24 positions

# per case: each lane's start (history length; -1 = padding lane), how many of
# its chunk positions are real, its block table, and the decode's max_pos
POOL_CASES = {
    # lane 1 is padding throughout; lane 2's chunk is part padding
    "padding_lanes": dict(
        start=[5, -1, 0], valid=[6, 0, 4],
        tables=[[1, 2, 3], [0, 0, 0], [4, 5, 6]], max_pos=23),
    # lane 0's window runs past max_pos mid-dispatch (decode only)
    "past_max_pos": dict(
        start=[18, 3, 9], valid=[6, 6, 6],
        tables=[[1, 2, 3], [4, 5, 6], [7, 8, 9]], max_pos=19),
    # lane 0's positions run past the 24 its table holds
    "past_table": dict(
        start=[21, 3, 9], valid=[6, 6, 6],
        tables=[[1, 2, 3], [4, 5, 6], [7, 8, 9]], max_pos=100),
    # lanes 0 and 1 share the page of their common 8-token prefix
    "shared_prefix": dict(
        start=[8, 8, 2], valid=[6, 6, 6],
        tables=[[1, 2, 3], [1, 4, 5], [7, 8, 9]], max_pos=23),
    # every lane at position 0: the history partial is compiled out (chunk only)
    "first_chunk": dict(
        start=[0, -1, 0], valid=[6, 0, 3],
        tables=[[1, 2, 3], [0, 0, 0], [4, 5, 6]], max_pos=23),
}


# one compile per program for all cases (eager, every call compiles its scan anew)
_forward = jax.jit(lambda p, tk, ps, c, bt: forward(p, CFG, tk, ps, c, bt))
_forward_chunk = jax.jit(
    lambda p, tk, ps, c, bt, with_history=True: forward_chunk(
        p, CFG, tk, ps, c, bt, with_history=with_history),
    static_argnames="with_history",
)
_forward_window = jax.jit(
    lambda p, tk, ps, hk, hv, base, wk, wv, k: forward_window(
        p, CFG, tk, ps, ("dense", hk, hv), base, wk, wv, k)
)
_gather_history = jax.jit(lambda c, bt: gather_history(c, bt, out_dtype=jnp.float32))
_flush_window = jax.jit(flush_window, static_argnames="max_pos")


def _case(name):
    c = POOL_CASES[name]
    start = np.array(c["start"])
    return start, np.array(c["valid"]), jnp.array(c["tables"], jnp.int32), c["max_pos"]


def _sentinel_pool(quantized=False):
    """A pool with something in every row, so a row written by mistake shows."""
    cache = make_kv_cache(CFG, N_BLOCKS, BLOCK, dtype=jnp.float32, quantized=quantized)
    key = jax.random.PRNGKey(7)
    out = {}
    for i, (name, a) in enumerate(sorted(cache.items())):
        r = jax.random.normal(jax.random.fold_in(key, i), a.shape)
        out[name] = (r * 40).astype(a.dtype) if a.dtype == jnp.int8 else jnp.abs(r) + 0.5
    return out


def _with_history(params, cache, start, tables, tokens_hist):
    """The pool after each lane's first ``start`` tokens went through ``forward``."""
    t = np.arange(MB * BLOCK)[None]
    pos = np.where(t < start[:, None], t, -1)
    return _forward(params, tokens_hist, jnp.asarray(pos), cache, tables)[1]


def _written_rows(positions, tables):
    """bool [N_BLOCKS, BLOCK]: the rows these positions own through the tables."""
    rows = np.zeros((N_BLOCKS, BLOCK), bool)
    for b, lane in enumerate(np.asarray(positions)):
        for p in lane:
            if 0 <= p < MB * BLOCK:
                rows[int(tables[b, p // BLOCK]), p % BLOCK] = True
    return rows


def _assert_same_pool(got, ref, before, written, atol=1e-6):
    """Rows the dispatch does not own are untouched, bit for bit; the rows it
    owns hold what ``forward`` wrote — bit for bit in layer 0, where both run
    the same arithmetic, and to float32 rounding above it (the attention
    feeding the deeper layers is merged from two partials; ``atol`` for sums
    over a thousand positions of history taken in another order)."""
    for name in ("k", "v"):
        g, r, b0 = (np.asarray(x[name]) for x in (got, ref, before))
        np.testing.assert_array_equal(g[:, ~written], b0[:, ~written])
        np.testing.assert_array_equal(g[0][written], r[0][written])
        np.testing.assert_allclose(g[:, written], r[:, written], rtol=1e-5, atol=atol)


def _chunk_inputs(start, valid):
    tokens = jax.random.randint(jax.random.PRNGKey(11), (LANES, CHUNK), 0, CFG.vocab_size)
    j = np.arange(CHUNK)[None]
    positions = np.where((start[:, None] >= 0) & (j < valid[:, None]), start[:, None] + j, -1)
    return tokens, positions


@pytest.mark.parametrize(
    "case", ["padding_lanes", "past_table", "shared_prefix", "first_chunk"]
)
def test_chunk_dispatch_writes_the_pool_as_forward_does(tiny_model, case):
    params = tiny_model
    start, valid, tables, _ = _case(case)
    hist = jax.random.randint(jax.random.PRNGKey(9), (LANES, MB * BLOCK), 0, CFG.vocab_size)
    if case == "shared_prefix":
        hist = hist.at[1, :8].set(hist[0, :8])
    before = _with_history(params, _sentinel_pool(), start, tables, hist)
    tokens, positions = _chunk_inputs(start, valid)

    ref_logits, ref = _forward(params, tokens, jnp.asarray(positions), before, tables)
    logits, got = _forward_chunk(
        params, tokens, jnp.asarray(positions), before, tables,
        with_history=case != "first_chunk",
    )
    _assert_same_pool(got, ref, before, _written_rows(positions, tables))
    # a query past its table attends no page of its own in `forward`
    real = (positions >= 0) & (positions < MB * BLOCK)
    np.testing.assert_allclose(
        np.asarray(logits)[real], np.asarray(ref_logits)[real], rtol=1e-4, atol=1e-4
    )


def _decode_dispatch(params, cache, tables, base, tokens, max_pos):
    """One decode dispatch as the engine builds it: history gathered once, W
    windowed steps, one flush. Returns (logits [B, W, V], positions, pool)."""
    hk, hv = _gather_history(cache, tables)
    w = (CFG.num_layers, LANES, WINDOW, CFG.num_kv_heads, CFG.head_dim)
    wk, wv = jnp.zeros(w, jnp.float32), jnp.zeros(w, jnp.float32)
    pos, logits, positions = jnp.asarray(base), [], []
    for k in range(WINDOW):
        lg, wk, wv = _forward_window(
            params, tokens[:, k], pos, hk, hv, jnp.asarray(base), wk, wv, jnp.int32(k)
        )
        logits.append(lg)
        positions.append(np.asarray(pos))
        pos = jnp.where((pos >= 0) & (pos < max_pos), pos + 1, -1)
    cache = _flush_window(cache, tables, jnp.asarray(base), wk, wv, max_pos=max_pos)
    return jnp.stack(logits, 1), np.stack(positions, 1), cache


def _decode_by_forward(params, cache, tables, tokens, positions):
    logits = []
    for k in range(WINDOW):
        lg, cache = _forward(
            params, tokens[:, k:k + 1], jnp.asarray(positions[:, k:k + 1]), cache, tables
        )
        logits.append(lg[:, 0])
    return jnp.stack(logits, 1), cache


@pytest.mark.parametrize(
    "case", ["padding_lanes", "past_max_pos", "past_table", "shared_prefix"]
)
def test_decode_flush_writes_the_pool_as_forward_does(tiny_model, case):
    params = tiny_model
    start, _, tables, max_pos = _case(case)
    hist = jax.random.randint(jax.random.PRNGKey(9), (LANES, MB * BLOCK), 0, CFG.vocab_size)
    if case == "shared_prefix":
        hist = hist.at[1, :8].set(hist[0, :8])
    before = _with_history(params, _sentinel_pool(), start, tables, hist)
    tokens = jax.random.randint(jax.random.PRNGKey(13), (LANES, WINDOW), 0, CFG.vocab_size)
    base = start.astype(np.int32)

    logits, positions, got = _decode_dispatch(params, before, tables, base, tokens, max_pos)
    ref_logits, ref = _decode_by_forward(params, before, tables, tokens, positions)
    written = _written_rows(positions, tables)
    assert written.sum() == sum(
        min(WINDOW, max_pos + 1 - s, MB * BLOCK - s) for s in start if s >= 0
    )
    _assert_same_pool(got, ref, before, written)
    real = (positions >= 0) & (positions < MB * BLOCK)
    np.testing.assert_allclose(
        np.asarray(logits)[real], np.asarray(ref_logits)[real], rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize("program", ["chunk", "decode"])
def test_int8_pool_and_its_scale_tables_hold_the_quantized_float_rows(tiny_model, program):
    """An int8 pool after a dispatch: the rows the dispatch owns hold
    ``quantize_kv`` of what the float pool's dispatch wrote, values and
    per-token scales; every other row and scale is untouched, bit for bit."""
    params = tiny_model
    start, valid, tables, max_pos = _case("padding_lanes")
    tokens, positions = _chunk_inputs(np.where(start >= 0, 0, -1), valid)
    before_q, before_f = _sentinel_pool(quantized=True), _sentinel_pool()
    if program == "chunk":
        # every lane at position 0: no history, so both pools see one arithmetic
        _, got = _forward_chunk(params, tokens, jnp.asarray(positions), before_q, tables)
        _, ref = _forward_chunk(params, tokens, jnp.asarray(positions), before_f, tables)
    else:
        # history: the chunk above; the float pool holds its dequantized rows
        _, before_q = _forward_chunk(params, tokens, jnp.asarray(positions), before_q, tables)
        before_f = {
            n: dequantize_kv(before_q[n], before_q[n + "_scale"], jnp.float32)
            for n in ("k", "v")
        }
        base = np.where(start >= 0, valid, -1).astype(np.int32)
        dec = jax.random.randint(jax.random.PRNGKey(13), (LANES, WINDOW), 0, CFG.vocab_size)
        _, positions, got = _decode_dispatch(params, before_q, tables, base, dec, max_pos)
        _, _, ref = _decode_dispatch(params, before_f, tables, base, dec, max_pos)
    written = _written_rows(positions, tables)
    kq, vq, ks, vs = quantize_kv(ref["k"], ref["v"])
    for name, want in (("k", kq), ("v", vq), ("k_scale", ks), ("v_scale", vs)):
        g, b0, want = (np.asarray(x) for x in (got[name], before_q[name], want))
        np.testing.assert_array_equal(g[:, ~written], b0[:, ~written])
        if g.dtype == np.int8:
            # the decode's float history is the int8 one dequantized: the two
            # hidden states agree to rounding, so a value on a step may flip
            assert np.abs(g[:, written].astype(int) - want[:, written]).max() <= (
                0 if program == "chunk" else 1
            )
        else:
            np.testing.assert_allclose(g[:, written], want[:, written], rtol=1e-5)


# -- the chunk program's history loop ------------------------------------------
#
# forward_chunk reads the pool's history a tile of HISTORY_TILE positions at a
# time, as many tiles as the longest history of the dispatch fills, and folds
# the tiles' partials together; `forward` attends every page of the table at
# once. A table of four tiles, its pages scattered over the pool.

T_BLOCK, T_MB, T_LANES, T_CHUNK = 16, 4 * HISTORY_TILE // 16, 4, 8
T_WIDTH = T_MB * T_BLOCK

# per case: each lane's history length (-1 = padding lane), how many queries it
# brings (1 = a decode lane riding the dispatch), and the tiles the loop reads
TILE_CASES = {
    "no_history": ([0, 0, 0, -1], [8, 3, 1, 0], 0),
    "one_position": ([1, 0, -1, 1], [8, 8, 0, 1], 1),
    "a_tile_less_one": ([HISTORY_TILE - 1, 17, -1, 100], [8, 8, 0, 1], 1),
    "a_tile": ([HISTORY_TILE, 17, -1, 100], [8, 8, 0, 1], 1),
    "a_tile_and_one": ([HISTORY_TILE + 1, 17, -1, 100], [8, 8, 0, 1], 2),
    # one prefilling lane deep in its prompt, one at its start, two decode lanes
    "lanes_of_very_different_lengths": ([700, 0, 300, 699], [8, 8, 1, 1], 3),
    # the longest history is a decode lane's
    "decode_lane_longest": ([10, 600, -1, 5], [8, 1, 0, 8], 3),
    # lane 0's chunk ends on the table's last position, lane 1 decodes into it
    "full_width": ([T_WIDTH - 8, T_WIDTH - 1, 40, -1], [8, 1, 8, 0], 4),
}


_traced_tiles = jax.jit(chunk_history_tiles, static_argnums=(1, 2))


@pytest.fixture(scope="module")
def tiled_pool(tiny_model):
    """A float pool holding T_WIDTH tokens of history for every lane, written
    by ``forward``; the lanes' tables; the int8 pool of the same rows, and the
    float pool that holds exactly what the int8 one dequantizes to."""
    n_blocks = T_LANES * T_MB + 5
    tables = np.random.default_rng(3).permutation(n_blocks)[: T_LANES * T_MB]
    tables = jnp.asarray(tables.reshape(T_LANES, T_MB).astype(np.int32))
    hist = jax.random.randint(jax.random.PRNGKey(21), (T_LANES, T_WIDTH), 0, CFG.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(T_WIDTH), (T_LANES, T_WIDTH))
    empty = make_kv_cache(CFG, n_blocks, T_BLOCK, dtype=jnp.float32)
    _, pool = _forward(tiny_model, hist, pos, empty, tables)
    kq, vq, ks, vs = quantize_kv(pool["k"], pool["v"])
    int8 = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    rounded = {n: dequantize_kv(int8[n], int8[n + "_scale"], jnp.float32) for n in ("k", "v")}
    return tables, {"float": (pool, pool), "int8": (int8, rounded)}


@pytest.mark.parametrize("pool", ["float", "int8"])
@pytest.mark.parametrize("case", list(TILE_CASES))
def test_chunk_history_loop_attends_what_forward_attends(tiny_model, tiled_pool, case, pool):
    """Rows past a lane's history hold its OLD tokens' keys (the pool was
    filled to the table's end), so a tile read too far, or a mask off by one,
    shows in the logits."""
    start, valid, tiles = TILE_CASES[case]
    tables, pools = tiled_pool
    before, before_as_float = pools[pool]
    start, valid = np.array(start), np.array(valid)
    tokens = jax.random.randint(jax.random.PRNGKey(11), (T_LANES, T_CHUNK), 0, CFG.vocab_size)
    j = np.arange(T_CHUNK)[None]
    positions = np.where((start[:, None] >= 0) & (j < valid[:, None]), start[:, None] + j, -1)

    # the host's count of the tiles (numpy) is the program's trip count (traced)
    assert chunk_history_tiles(positions, T_BLOCK, T_MB) == tiles
    assert int(_traced_tiles(jnp.asarray(positions), T_BLOCK, T_MB)) == tiles

    ref_logits, ref = _forward(tiny_model, tokens, jnp.asarray(positions), before_as_float, tables)
    logits, got = _forward_chunk(tiny_model, tokens, jnp.asarray(positions), before, tables)
    real = positions >= 0
    np.testing.assert_allclose(
        np.asarray(logits)[real], np.asarray(ref_logits)[real], rtol=1e-4, atol=1e-4
    )
    if pool == "float":
        written = np.zeros((before["k"].shape[1], T_BLOCK), bool)
        for lane, p in zip(*np.nonzero(real)):
            at = positions[lane, p]
            written[int(tables[lane, at // T_BLOCK]), at % T_BLOCK] = True
        _assert_same_pool(got, ref, before, written, atol=1e-5)


# -- the decode program's live history -------------------------------------------
#
# On one device the decode program gathers and attends the (lane, tile) pairs
# that hold history, packed to the front of its dense buffer, as far as the last
# live pair rounded up to one of a few widths (with_live_history -> forward_window's
# "live" mode); a mesh engine keeps every table's full width (gather_history ->
# _window_attention). Same keys, same logits. Eight lanes under tables of four
# tiles: 32 slots, read 16 or 32 wide.

D_LANES = 8
D_SLOTS = D_LANES * T_WIDTH // HISTORY_TILE

# per case: each lane's base (its history is the positions < base; -1 = a lane
# that does not decode) and the slots the program reads
LIVE_CASES = {
    "ragged": ([0, 1, HISTORY_TILE - 1, HISTORY_TILE, HISTORY_TILE + 1, T_WIDTH - 1, 700, 40], 16),
    "padding_lanes_among_live": ([300, -1, -1, 5, 1000, -1, 256, -1], 16),
    "every_lane_empty": ([0, -1, 0, -1, -1, 0, -1, -1], 16),
    "in_length_order": ([1000, 600, 400, 300, 200, 100, 50, 3], 16),
    "in_reverse_order": ([3, 50, 100, 200, 300, 400, 600, 1000], 16),
    "a_pair_more_than_a_width": ([1000, 900, 600, 400, 300, 300, -1, -1], D_SLOTS),
    "every_table_full": ([T_WIDTH - 1] * D_LANES, D_SLOTS),
}


@pytest.fixture(scope="module")
def decode_pool(tiny_model):
    """As ``tiled_pool``, for D_LANES lanes: every table filled to its end."""
    n_blocks = D_LANES * T_MB + 5
    tables = np.random.default_rng(5).permutation(n_blocks)[: D_LANES * T_MB]
    tables = jnp.asarray(tables.reshape(D_LANES, T_MB).astype(np.int32))
    hist = jax.random.randint(jax.random.PRNGKey(23), (D_LANES, T_WIDTH), 0, CFG.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(T_WIDTH), (D_LANES, T_WIDTH))
    empty = make_kv_cache(CFG, n_blocks, T_BLOCK, dtype=jnp.float32)
    _, pool = _forward(tiny_model, hist, pos, empty, tables)
    kq, vq, ks, vs = quantize_kv(pool["k"], pool["v"])
    return tables, {"float": pool, "int8": {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}}


def _decode_steps(params, pool, tables, base, tokens, live):
    """WINDOW steps of the decode dispatch over one form of the history:
    (logits [B, W, V], window K, window V)."""
    def steps(history):
        w = (CFG.num_layers, D_LANES, WINDOW, CFG.num_kv_heads, CFG.head_dim)
        wk = wv = jnp.zeros(w, jnp.float32)
        pos, logits = base, []
        for k in range(WINDOW):
            lg, wk, wv = forward_window(
                params, CFG, tokens[:, k], pos, history, base, wk, wv, jnp.int32(k)
            )
            logits.append(lg)
            pos = jnp.where(pos >= 0, pos + 1, -1)
        return jnp.stack(logits, 1), wk, wv

    if live:
        return with_live_history(pool, tables, base, steps, out_dtype=jnp.float32)
    return steps(("dense",) + tuple(gather_history(pool, tables, out_dtype=jnp.float32)))


_decode_steps = jax.jit(_decode_steps, static_argnames="live")


def _unread_pairs(base):
    """(lane, tile) of the slots past what the program reads: it takes the
    pairs that hold history first, in (lane, tile) order."""
    tiles = T_WIDTH // HISTORY_TILE
    live = np.arange(tiles)[None] * HISTORY_TILE < np.clip(base, 0, T_WIDTH)[:, None]
    order = np.argsort(~live.reshape(-1), kind="stable")
    return [divmod(int(pair), tiles) for pair in order[int(decode_history_tiles(base, T_BLOCK, T_MB)):]]


@pytest.mark.parametrize("pool", ["float", "int8"])
@pytest.mark.parametrize("case", list(LIVE_CASES))
def test_live_history_attends_what_the_full_width_attends(tiny_model, decode_pool, case, pool):
    """Rows past a lane's history hold its OLD tokens' keys (the pool was
    filled to the table's end), so a slot read for the wrong lane, or a mask
    off by one, shows in the logits."""
    base = jnp.asarray(LIVE_CASES[case][0], jnp.int32)
    tables, pools = decode_pool
    tokens = jax.random.randint(jax.random.PRNGKey(13), (D_LANES, WINDOW), 0, CFG.vocab_size)
    want = _decode_steps(tiny_model, pools[pool], tables, base, tokens, live=False)
    got = _decode_steps(tiny_model, pools[pool], tables, base, tokens, live=True)
    decodes = np.asarray(base) >= 0
    np.testing.assert_allclose(
        np.asarray(got[0])[decodes], np.asarray(want[0])[decodes], rtol=1e-5, atol=1e-5
    )
    for g, w in zip(got[1:], want[1:]):
        # the window buffers: layer 0's hold the tokens' own keys and values,
        # the same arithmetic in both forms; above it to float32 rounding
        np.testing.assert_array_equal(np.asarray(g)[0], np.asarray(w)[0])
        np.testing.assert_allclose(
            np.asarray(g)[:, decodes], np.asarray(w)[:, decodes], rtol=1e-5, atol=1e-5
        )


@pytest.mark.parametrize("case", [c for c, (_, read) in LIVE_CASES.items() if read < D_SLOTS])
def test_what_the_decode_program_does_not_gather_it_does_not_read(tiny_model, decode_pool, case):
    """NaN in every page of every (lane, tile) pair past the program's own
    count: the same finite logits."""
    base = np.array(LIVE_CASES[case][0], np.int32)
    tables, pools = decode_pool
    pages = np.concatenate([
        np.asarray(tables)[lane, tile * HISTORY_TILE // T_BLOCK:(tile + 1) * HISTORY_TILE // T_BLOCK]
        for lane, tile in _unread_pairs(base)
    ])
    poisoned = {n: a.at[:, pages].set(jnp.nan) for n, a in pools["float"].items()}
    tokens = jax.random.randint(jax.random.PRNGKey(13), (D_LANES, WINDOW), 0, CFG.vocab_size)
    want = _decode_steps(tiny_model, pools["float"], tables, jnp.asarray(base), tokens, live=True)
    got = _decode_steps(tiny_model, poisoned, tables, jnp.asarray(base), tokens, live=True)
    assert np.isfinite(np.asarray(got[0])).all()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("case", ["ragged", "every_lane_empty", "a_pair_more_than_a_width"])
def test_steps_that_take_what_they_update_give_what_the_switch_gives(tiny_model, decode_pool, case):
    """``with_live_history(..., carried=)``, one conditional a width with the
    other widths handing ``carried`` on (the form for steps that update per-slot
    state in place: ``models/jamba.py``), against the one ``lax.switch``: the
    same logits and window buffers, bit for bit, at either width."""
    base = jnp.asarray(LIVE_CASES[case][0], jnp.int32)
    tables, pools = decode_pool
    tokens = jax.random.randint(jax.random.PRNGKey(13), (D_LANES, WINDOW), 0, CFG.vocab_size)
    want = _decode_steps(tiny_model, pools["float"], tables, base, tokens, live=True)

    def steps(history):
        def from_window(wk, wv, _):
            pos, logits = base, []
            for k in range(WINDOW):
                lg, wk, wv = forward_window(
                    tiny_model, CFG, tokens[:, k], pos, history, base, wk, wv, jnp.int32(k))
                logits.append(lg)
                pos = jnp.where(pos >= 0, pos + 1, -1)
            return wk, wv, jnp.stack(logits, 1)
        return from_window

    window = jnp.zeros((CFG.num_layers, D_LANES, WINDOW, CFG.num_kv_heads, CFG.head_dim), jnp.float32)
    wk, wv, logits = jax.jit(lambda: with_live_history(
        pools["float"], tables, base, steps, out_dtype=jnp.float32,
        carried=(window, window, jnp.zeros(want[0].shape, jnp.float32))))()
    for g, w in zip((logits, wk, wv), want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("case", list(LIVE_CASES))
def test_the_hosts_count_of_decode_history_is_the_programs_own(decode_pool, case):
    """``decode_history_tiles`` on a numpy array (the engine's counter) is
    what it is on a traced one, which is the width ``with_live_history``
    gathers and the steps attend: the history its ``steps`` is handed has
    that many slots."""
    base, read = LIVE_CASES[case]
    tables, pools = decode_pool
    assert decode_history_tiles(np.array(base, np.int32), T_BLOCK, T_MB) == read
    traced = jax.jit(lambda b: with_live_history(
        pools["float"], tables, b, lambda history: jnp.int32(history[1].own.shape[1])
    ))
    assert int(traced(jnp.asarray(base, jnp.int32))) == read


@pytest.mark.parametrize("table", ["pages", "scales"])
@pytest.mark.parametrize(
    "case", ["padding_lanes", "past_table", "shared_prefix", "first_chunk"]
)
def test_one_scatter_equals_the_scatter_per_layer(case, table):
    """write_kv_to_pool against the idiom it replaces in the step programs —
    write_kv_to_pages layer by layer — bit for bit, pages and scale tables."""
    start, valid, tables, _ = _case(case)
    _, positions = _chunk_inputs(start, valid)
    positions = jnp.asarray(positions)
    trail = (CFG.num_kv_heads, CFG.head_dim) if table == "pages" else ()
    key = jax.random.PRNGKey(17)
    pool = jax.random.normal(key, (CFG.num_layers, N_BLOCKS, BLOCK) + trail)
    new = jax.random.normal(
        jax.random.fold_in(key, 1), (CFG.num_layers, LANES, CHUNK) + trail
    )
    want = jnp.stack([
        write_kv_to_pages(pool[l], pool[l], new[l], new[l], positions, tables)[0]
        for l in range(CFG.num_layers)
    ])
    got = write_kv_to_pool(pool, new, positions, tables)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    written = _written_rows(positions, tables)
    np.testing.assert_array_equal(np.asarray(got)[:, ~written], np.asarray(pool)[:, ~written])
    assert (np.asarray(got)[:, written] != np.asarray(pool)[:, written]).all()


@pytest.mark.parametrize("quantized", [False, True], ids=["pages", "int8_with_scales"])
def test_taking_pages_is_the_block_axis_index(quantized):
    pool = _sentinel_pool(quantized=quantized)
    ids = jnp.array([7, 0, 3, 3, 9], jnp.int32)
    taken = kv_pages.take(pool, ids)
    assert set(taken) == set(pool)
    for name, a in pool.items():
        np.testing.assert_array_equal(np.asarray(taken[name]), np.asarray(a[:, ids]))


# -- a lane that fills several rows of one chunk dispatch -------------------------
#
# With the rows' lanes given, a lane may bring successive pieces of its prompt
# as rows of ONE dispatch: a later piece attends the pool below the lane's
# FIRST row, the fresh keys of the lane's earlier rows (chunk_sibling_partial)
# and its own. Held against the same pieces dispatched one at a time, one row a
# lane, the program as it was.

R_ROWS, R_CHUNK, R_SLOTS = 8, 128, 8
# (lane, first position, tokens) of each row; lane R_SLOTS = a padding row.
# Lane 2 is a prompt of 300 tokens in three rows, a padding row between them;
# lane 5 has 40 tokens in the pool already and brings two pieces, one in the
# first row and one five rows below it; lane 1 has one row.
R_LAYOUT = [
    (5, 40, 128), (2, 0, 128), (2, 128, 128), (R_SLOTS, -1, 0),
    (2, 256, 44), (5, 168, 100), (1, 7, 60), (R_SLOTS, -1, 0),
]

_forward_chunk_rows = jax.jit(
    lambda p, tk, ps, c, bt, lanes, cfg: forward_chunk(p, cfg, tk, ps, c, bt, lanes=lanes),
    static_argnames="cfg",
)
_forward_chunk_of = jax.jit(
    lambda p, tk, ps, c, bt, cfg: forward_chunk(p, cfg, tk, ps, c, bt), static_argnames="cfg",
)


def _rows_dispatch(layout, tokens_of, tables_of):
    tokens = np.zeros((R_ROWS, R_CHUNK), np.int32)
    positions = np.full((R_ROWS, R_CHUNK), -1, np.int32)
    tables = np.zeros((R_ROWS, T_MB), np.int32)
    lanes = np.full((R_ROWS,), R_SLOTS, np.int32)
    for r, (lane, start, n) in enumerate(layout):
        if n:
            lanes[r] = lane
            tokens[r, :n] = tokens_of[lane][start:start + n]
            positions[r, :n] = np.arange(start, start + n)
            tables[r] = tables_of[lane]
    return tokens, positions, tables, lanes


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 6e-2)],
                         ids=["float32", "bfloat16"])
def test_a_lanes_rows_of_one_dispatch_give_what_its_pieces_give_one_dispatch_each(dtype, tol):
    cfg = dataclasses.replace(CFG, dtype=dtype)
    params = init_params(jax.random.PRNGKey(0), cfg)
    n_blocks = R_SLOTS * T_MB + 3
    order = np.random.default_rng(5).permutation(n_blocks)[: R_SLOTS * T_MB]
    tables_of = order.reshape(R_SLOTS, T_MB).astype(np.int32)
    tokens_of = np.asarray(
        jax.random.randint(jax.random.PRNGKey(17), (R_SLOTS, 512), 0, cfg.vocab_size))
    before = make_kv_cache(cfg, n_blocks, T_BLOCK, dtype=dtype)
    # lane 5's 40 and lane 1's 7 tokens of history, through the program itself
    history = [(5, 0, 40), (1, 0, 7)] + [(R_SLOTS, -1, 0)] * 6
    tk, ps, bt, _ = _rows_dispatch(history, tokens_of, tables_of)
    _, before = _forward_chunk_of(params, tk, ps, before, bt, cfg)

    # the host's count of the history tiles is the program's: lane 5's 40
    # positions, not the 168 its second row starts at
    tk, ps, bt, lanes = _rows_dispatch(R_LAYOUT, tokens_of, tables_of)
    assert chunk_history_tiles(ps, T_BLOCK, T_MB) == 1
    assert chunk_history_tiles(ps, T_BLOCK, T_MB, lanes) == 1
    deep = [(5, 300, 128), (5, 428, 10)] + [(R_SLOTS, -1, 0)] * 6
    deep_ps, deep_lanes = _rows_dispatch(deep, tokens_of, tables_of)[1::2]
    assert chunk_history_tiles(deep_ps, T_BLOCK, T_MB) == 2
    assert chunk_history_tiles(deep_ps, T_BLOCK, T_MB, deep_lanes) == 2
    deep_ps[0, 0] = 255  # the lane's first row decides, whichever row it is
    assert chunk_history_tiles(deep_ps, T_BLOCK, T_MB, deep_lanes) == 1
    assert int(jax.jit(chunk_history_tiles, static_argnums=(1, 2))(
        jnp.asarray(deep_ps), T_BLOCK, T_MB, jnp.asarray(deep_lanes))) == 1

    logits, got = _forward_chunk_rows(params, tk, ps, before, bt, lanes, cfg)

    # one row a lane: a dispatch for each further piece of a lane
    want, want_logits = before, {}
    left = [(r, row) for r, row in enumerate(R_LAYOUT) if row[2]]
    while left:
        taken, now, later = set(), [], []
        for r, row in left:
            (later if row[0] in taken else now).append((r, row))
            taken.add(row[0])
        layout = [(R_SLOTS, -1, 0)] * R_ROWS
        for r, row in now:
            layout[r] = row
        one = _rows_dispatch(layout, tokens_of, tables_of)
        out, want = _forward_chunk_of(params, *one[:2], want, one[2], cfg)
        for r, (_, _, n) in now:
            want_logits[r] = np.asarray(out[r, n - 1], np.float32)
        left = later
    assert len(want_logits) == 6
    for r, (lane, start, n) in enumerate(R_LAYOUT):
        if n:  # the last token of every row, a lane's last row among them
            np.testing.assert_allclose(
                np.asarray(logits[r, n - 1], np.float32), want_logits[r], rtol=tol, atol=tol)
    for name in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(got[name], np.float32), np.asarray(want[name], np.float32),
            rtol=tol, atol=tol)
    # and no row but those the rows own was written
    untouched = np.ones(n_blocks, bool)
    for lane, start, n in R_LAYOUT + history:
        if n:
            untouched[tables_of[lane, : -(-(start + n) // T_BLOCK)]] = False
    np.testing.assert_array_equal(
        np.asarray(got["k"], np.float32)[:, untouched], 0.0)
