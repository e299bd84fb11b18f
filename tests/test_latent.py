"""``ops/latent.py:attend_absorbed_tiled`` (a chunk's latent attention, read
out of the pool a tile of the block table a trip, as far as the rows' last
position) and ``attend_absorbed_live`` (a decode step's, over the tiles of the
lanes' tables that hold history and the dispatch's own steps) against
``attend_absorbed`` over the whole gathered table under a mask, at a tiny size
on the CPU in float32: 4 heads of 16 + 8, a latent of 32 in a row of 48
(padded), blocks of 8, tiles of 2 blocks = 16 positions, a table of 7 blocks
(three tiles and a half)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import kimi_linear, llama, openpangu as op, xing4
from dynamo_tpu.ops import latent as ops
from dynamo_tpu.ops.latent import (
    attend_absorbed, attend_absorbed_live, attend_absorbed_tiled, gather_latent, live_latents,
    live_positions_attended, write_latent,
)

H, NOPE, ROPE, V, RANK, W, E = 4, 16, 8, 16, 32, 48, 64
BS, MB, TILE_BLOCKS, T = 8, 7, 2, 8
TILE = TILE_BLOCKS * BS
LAYERS, LAYER, BLOCKS = 3, 1, 40
DIMS = (RANK, NOPE, V, (NOPE + ROPE) ** -0.5)
# float32 on the CPU at the highest precision: the two forms differ in the order of their sums
# (a softmax over the table against partials merged tile by tile); outputs are of magnitude 1
ATOL = 2e-5

# a group's rows as (first position, tokens); tokens 0 = a padding row
GROUPS = {
    "a_row_from_position_0": [(0, 8)],
    "a_row_that_starts_past_0": [(20, 8)],
    "a_last_position_that_is_the_first_of_a_tile": [(25, 8), (0, 3)],
    "a_padding_row_beside_a_fed_one": [(0, 0), (9, 8)],
    "into_the_tile_that_hangs_over_the_table": [(44, 8)],
    "padding_rows_alone": [(0, 0), (0, 0)],
    "rows_of_every_kind_in_one_group": [(0, 8), (20, 8), (25, 8), (0, 0), (44, 8), (47, 5)],
}


@pytest.fixture(scope="module", autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def weights():
    k = jax.random.split(jax.random.PRNGKey(1), 2)
    return (jax.random.normal(k[0], (RANK, H * (NOPE + V))) / RANK ** 0.5,
            jax.random.normal(k[1], (H * V, E)) / (H * V) ** 0.5)


def group_of(rows, seed=0):
    """(queries, positions, block tables, the pool with every page of every
    layer random and the rows' own latents written where they belong, the
    trips the rows need)."""
    b = len(rows)
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    positions = np.full((b, T), -1, np.int32)
    for r, (first, n) in enumerate(rows):
        positions[r, :n] = np.arange(first, first + n)
    tables = 1 + np.arange(b * MB, dtype=np.int32).reshape(b, MB)  # page 0 is nobody's
    pool = jax.random.normal(k[0], (LAYERS, BLOCKS, BS, W)).at[..., RANK + ROPE:].set(0.0)
    fresh = jax.random.normal(k[1], (b, T, W)).at[..., RANK + ROPE:].set(0.0)
    pool = write_latent(pool, LAYER, fresh, jnp.asarray(positions), jnp.asarray(tables))
    q = jax.random.normal(k[2], (b, T, H, NOPE + ROPE))
    n_tiles = -(-(int(positions.max()) + 1) // TILE)
    return q, jnp.asarray(positions), jnp.asarray(tables), pool, n_tiles


def full_form(weights, q, positions, tables, pool):
    key_pos = jnp.arange(MB * BS)
    mask = (key_pos[None, None, :] <= positions[:, :, None]) & (positions >= 0)[:, :, None]
    return attend_absorbed(q, *weights, gather_latent(pool, LAYER, tables), mask, *DIMS)


@pytest.mark.parametrize("name", list(GROUPS))
def test_the_tiled_form_is_the_full_form_over_the_gathered_table(weights, name):
    """Rows of different histories in one group, the trips set by the longest:
    every row's answer is what the softmax over its whole table gives."""
    q, positions, tables, pool, n_tiles = group_of(GROUPS[name])
    want = np.asarray(full_form(weights, q, positions, tables, pool))
    tiled = jax.jit(attend_absorbed_tiled, static_argnums=(4, 8, 9, 10, 11, 12))
    got = np.asarray(tiled(q, *weights, pool, LAYER, tables, positions, n_tiles, TILE_BLOCKS, *DIMS))
    assert got.shape == (len(GROUPS[name]), T, E) and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=ATOL)
    fed = np.asarray(positions) >= 0
    assert np.abs(got[fed]).max(initial=1.0) > 0.1 and not got[~fed].any()
    # a trip more changes nothing (the mask), a trip fewer does (the bound is what is needed)
    if 0 < n_tiles < -(-MB // TILE_BLOCKS):
        more = tiled(q, *weights, pool, LAYER, tables, positions, n_tiles + 1, TILE_BLOCKS, *DIMS)
        np.testing.assert_allclose(np.asarray(more), want, atol=ATOL)
    if n_tiles:
        fewer = tiled(q, *weights, pool, LAYER, tables, positions, n_tiles - 1, TILE_BLOCKS, *DIMS)
        assert np.abs(np.asarray(fewer) - want).max() > 100 * ATOL


@pytest.mark.parametrize("name", ["a_row_from_position_0", "a_row_that_starts_past_0",
                                  "a_last_position_that_is_the_first_of_a_tile"])
def test_pages_past_the_trips_are_not_read(weights, name):
    """The bound is real, not a mask: with every page of the tiles PAST the
    trip count NaN, the tiled form answers as it did; the full form, which
    multiplies them by a weight of zero, answers NaN."""
    q, positions, tables, pool, n_tiles = group_of(GROUPS[name])
    want = np.asarray(full_form(weights, q, positions, tables, pool))
    past = np.asarray(tables)[:, n_tiles * TILE_BLOCKS:].reshape(-1)
    assert past.size
    poisoned = pool.at[:, past].set(jnp.nan)
    got = attend_absorbed_tiled(q, *weights, poisoned, LAYER, tables, positions, n_tiles, TILE_BLOCKS, *DIMS)
    np.testing.assert_allclose(np.asarray(got), want, atol=ATOL)
    fed = np.asarray(positions) >= 0
    assert np.isnan(np.asarray(full_form(weights, q, positions, tables, poisoned))[fed]).all()


def test_a_later_row_of_a_table_attends_the_row_before_it_through_the_pool(weights):
    """Two rows of one group with the SAME block table, the second starting
    where the first ends: the second row's answer is that of the same
    positions in a lane that holds the first row's latents as history."""
    q, positions, tables, pool, n_tiles = group_of([(12, 8), (20, 8)])
    tables = jnp.stack([tables[0], tables[0]])
    k = jax.random.split(jax.random.PRNGKey(7), 2)
    fresh = jax.random.normal(k[0], (2, T, W)).at[..., RANK + ROPE:].set(0.0)
    pool = write_latent(pool, LAYER, fresh, positions, tables)
    got = attend_absorbed_tiled(q, *weights, pool, LAYER, tables, positions, n_tiles, TILE_BLOCKS, *DIMS)
    alone = attend_absorbed_tiled(q[1:], *weights, pool, LAYER, tables[1:], positions[1:], n_tiles,
                                  TILE_BLOCKS, *DIMS)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(alone[0]), atol=ATOL)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full_form(weights, q, positions, tables, pool)),
                               atol=ATOL)
    # and the first row's keys ARE in what the second sees: without them it answers otherwise
    without = write_latent(pool, LAYER, jnp.zeros_like(fresh[:1]), positions[:1], tables[:1])
    other = attend_absorbed_tiled(q[1:], *weights, without, LAYER, tables[1:], positions[1:], n_tiles,
                                  TILE_BLOCKS, *DIMS)
    assert np.abs(np.asarray(other[0]) - np.asarray(got[1])).max() > 100 * ATOL


@pytest.mark.parametrize("last, block_size, table_blocks, trips", [
    (-1, 16, 128, 0), (0, 16, 128, 1), (255, 16, 128, 1), (256, 16, 128, 2), (1023, 16, 128, 4),
    (2047, 16, 128, 8), (39, 8, 12, 1), (95, 8, 12, 1), (300, 8, 64, 2), (511, 8, 70, 2), (512, 8, 70, 3),
])
def test_the_trips_are_the_tiles_up_to_the_last_position(last, block_size, table_blocks, trips):
    """``models/openpangu.py:chunk_history_tiles``: the tiles of
    ``models/llama.py:history_tile`` positions that hold positions 0 ... the
    rows' last, for the host's numpy array and the program's traced one alike;
    a padding row (< 0) asks for none."""
    positions = np.full((3, 6), -1, np.int32)
    if last >= 0:
        positions[1, :4] = np.arange(last - 3, last + 1).clip(0)
        positions[2, :1] = 0
    assert op.chunk_history_tiles(positions, block_size, table_blocks) == trips
    traced = jax.jit(op.chunk_history_tiles, static_argnums=(1, 2))(jnp.asarray(positions), block_size, table_blocks)
    assert int(traced) == trips
    assert trips <= llama.history_tiles_full(block_size, table_blocks)


# -- a decode dispatch's live form -------------------------------------------------
#
# Lanes by the history they hold (-1: the lane does not decode). The table holds 56 positions in four
# tiles of 16 (the last hangs over it) and a dispatch makes 4 steps, so a lane may start at 52 at most.
STEPS, LAST = 4, MB * BS - 1
LANES = {
    "lanes_of_every_kind": [-1, 0, TILE - 1, TILE, LAST + 1 - STEPS, 30, 1, 2 * TILE + 1, 5, 3 * TILE],
    "every_lane_empty": [-1, -1, 0, -1, 0, -1, -1, -1],
    "every_lane_full": [LAST + 1 - STEPS] * 8,
    "one_lane": [21],
}


@pytest.fixture
def tiles_of_16(monkeypatch):
    monkeypatch.setattr(llama, "HISTORY_TILE", TILE)
    assert (llama.history_tile(BS, MB), llama.history_tiles_full(BS, MB)) == (TILE, 4)


def dispatch_of(base, seed=0):
    """(the lanes' base, block tables, a pool with every page of every layer
    random, the steps' queries ``[STEPS, B, 1, H, D]`` and fresh latents ``[B,
    STEPS, W]``)."""
    base = np.asarray(base, np.int32)
    b = len(base)
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    tables = 1 + np.arange(b * MB, dtype=np.int32).reshape(b, MB)  # page 0 is nobody's
    pool = jax.random.normal(k[0], (LAYERS, b * MB + 1, BS, W)).at[..., RANK + ROPE:].set(0.0)
    fresh = jax.random.normal(k[1], (b, STEPS, W)).at[..., RANK + ROPE:].set(0.0)
    q = jax.random.normal(k[2], (STEPS, b, 1, H, NOPE + ROPE))
    return base, jnp.asarray(tables), pool, q, fresh


def live_steps(weights, base, tables, pool, q, fresh):
    """The four steps of a dispatch through the live form, as a decode
    program makes them: the history gathered once, a step's latent into the
    buffer with the lanes' tiles and the buffer attended. ``[STEPS, B, E]``."""
    def steps(base, tables, pool, q, fresh):
        live = live_latents(pool, LAYERS, tables, base)
        recent, out = jnp.zeros_like(fresh), []
        for k in range(STEPS):
            fed = (base >= 0) & (base + k <= LAST)
            got, recent = attend_absorbed_live(q[k], *weights, live, LAYER, recent, fresh[:, k:k + 1], k, fed, *DIMS)
            out.append(got[:, 0])
        return jnp.stack(out)
    return jax.jit(steps)(jnp.asarray(base), tables, pool, q, fresh)


def full_steps(weights, base, tables, pool, q, fresh):
    """The same steps over every lane's WHOLE table under a mask, the step's
    latent written into the dense buffer at the lane's position: the form the
    decode programs had."""
    history = gather_latent(pool, LAYER, tables)
    key_pos, lanes, out = jnp.arange(MB * BS), jnp.arange(len(base)), []
    for k in range(STEPS):
        pos = jnp.where((base >= 0) & (base + k <= LAST), base + k, -1)
        history = history.at[lanes, jnp.where(pos >= 0, pos, MB * BS)].set(fresh[:, k], mode="drop")
        mask = (key_pos[None, None, :] <= pos[:, None, None]) & (pos >= 0)[:, None, None]
        out.append(attend_absorbed(q[k], *weights, history, mask, *DIMS)[:, 0])
    return jnp.stack(out)


@pytest.mark.parametrize("name", list(LANES))
def test_the_live_form_is_the_full_form_over_whole_tables(weights, tiles_of_16, name):
    """Lanes of mixed histories in one dispatch (a padding lane, a lane at 0,
    one under and one at a tile's edge, one that ends at the table's last
    position; every lane empty; every lane full: the last trip of every
    block), the steps' buffer folded in over four steps: every lane's answer
    at every step is what the softmax over its whole table gives."""
    base, tables, pool, q, fresh = dispatch_of(LANES[name])
    want = np.asarray(full_steps(weights, base, tables, pool, q, fresh))
    got = np.asarray(live_steps(weights, base, tables, pool, q, fresh))
    assert got.shape == (STEPS, len(base), E) and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=ATOL)
    assert not got[:, base < 0].any()
    if (base >= 0).any():
        assert np.abs(got[:, base >= 0]).max() > 0.1


def test_the_live_form_attends_no_tile_past_a_blocks_trips(weights, tiles_of_16):
    """The bound is real, not a mask: with every page of the tiles past each
    block's trips NaN in the pool, the live form answers as it did; the full
    form, which multiplies them by a weight of zero, answers NaN."""
    base, tables, pool, q, fresh = dispatch_of(LANES["lanes_of_every_kind"])
    want = np.asarray(full_steps(weights, base, tables, pool, q, fresh))
    order, held, trips = ops._live_blocks(base, BS, MB)
    assert trips.tolist() == [4, 3, 1, 1, 0] and held.shape == (5, 2)  # ten lanes: blocks of two
    past = np.concatenate([np.asarray(tables)[lane, trips[r // 2] * TILE_BLOCKS:] for r, lane in enumerate(order)])
    poisoned = pool.at[:, past].set(jnp.nan)
    got = np.asarray(live_steps(weights, base, tables, poisoned, q, fresh))
    np.testing.assert_allclose(got, want, atol=ATOL)
    assert np.isnan(np.asarray(full_steps(weights, base, tables, poisoned, q, fresh))[:, base == 5]).all()


@pytest.mark.parametrize("module", [op, xing4, kimi_linear], ids=lambda m: m.__name__.rsplit(".", 1)[-1])
@pytest.mark.parametrize("base, tiles", [
    ([-1, -1, -1, -1], 0), ([0, -1, 0, -1], 0), ([1, -1, -1, -1], 4), ([256, 255, 1, 0], 4), ([257, 0, 0, 0], 8),
    ([2047] * 8, 64), ([300, -1, 700, 20, 5, 1100, 256, 90], 4 * 5 + 4 * 1),
    ([513, 10, 20, 30, 40, 50, 60, 70, 80, 90, -1, -1], 4 * 3 + 4 * 1 + 4 * 1),
])
def test_the_decode_count_is_the_tiles_the_live_form_walks(module, base, tiles):
    """``decode_history_tiles`` of the three latent modules: the lanes longest
    first in blocks of ``lanes_at_once``, a block the tiles of 256 its longest
    lane holds; the host's numpy array and the program's traced one alike,
    and what the program's own counters are fed (``live_positions_attended``:
    a lane's row is scored against its block's tiles and the steps' buffer)."""
    base = np.asarray(base, np.int32)
    assert module.decode_history_tiles(base, 16, 128) == tiles
    traced = jax.jit(module.decode_history_tiles, static_argnums=(1, 2))(jnp.asarray(base), 16, 128)
    assert int(traced) == tiles <= len(base) * llama.history_tiles_full(16, 128)
    pool = jnp.zeros((1, 2, 16, 8))
    live = live_latents(pool, 1, jnp.zeros((len(base), 128), jnp.int32), jnp.asarray(base))
    attended = np.asarray(live_positions_attended(live, STEPS))
    assert attended.shape == base.shape and (attended - STEPS).sum() == tiles * 256
    assert (attended - STEPS >= base.clip(0)).all()  # no lane holds history past what its block walks
