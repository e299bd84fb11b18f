"""Jamba decoder: Mamba-1 mixers (a diagonal state-space recurrence, with
Jamba's three inner RMS norms) beside a few attention layers without any
positional encoding, a dense gated feed-forward after every mixer.

Nearly every layer is a Mamba layer (26 of 28 in Jamba2-3B), so the layers are
kept by RUN: ``params["mamba"]`` is a tuple of runs of consecutive Mamba
layers, each a tree stacked on a leading layer axis and traced once under
``lax.scan``; ``params["attn"]`` is a tuple of the attention layers between
them (:func:`segments`). No conditional on the layer kind sits inside a layer
loop, and the depth is not unrolled. Two kinds of state live side by side:

- the attention layers' pages: the Llama layout, ``{"k", "v"}``
  ``[L_attn, N, bs, KVH, D]``, written, gathered and attended by
  ``models/llama.py``'s and ``ops/attention.py``'s own functions with the
  rotation left out (the chunk's history a tile at a time, a decode
  dispatch's through ``with_live_history``). Paged, and it travels through
  ``kv/pages.py`` like any member.
- the Mamba layers' state, PER SLOT (:class:`SlotState`, owned here), a run
  at a time: float32 ``s`` ``[n, S, N, D]`` and the convolution's last
  ``K - 1`` inputs ``[n, S, (K - 1) * D]``. ``D`` is the minor axis of both
  (``[D, N]`` as published would pad 16 to 128 lanes in HBM), and
  ``a_log`` is held ``[N, D]`` with it. The chunk and decode programs read the
  state and hand it back; a chunk row whose first position is 0 starts from
  zeros, which is how a slot is reset when a request is admitted to it; padding
  rows, padding positions and lanes that do not decode leave it untouched.
  Nothing outside this module indexes it, and ``pages.take`` / ``put`` never
  see it.

The weights are bfloat16 and the activations float32 from the embedding to
the head (``ops/parts.py:dot_parts``, here ``_dot``). Nothing here routes, and
bfloat16 activations were the first build; on the chip they read
``logprob_rms`` 0.077 against the float32 reference where the dense decoders
read 0.017 (random weights of this
architecture carry a rounding 4.5 x as far: PERF.md 6, PR 41), and by part: the
stream and the products' outputs rounded to bfloat16 are over half of it (free
to keep in float32), then the inputs of the in-projection, of the
out-projection and of the two small projections that make the step size, B and
C. Those four take their activation in TWO bfloat16 parts (16 bits of it; the
parts stacked into one product, so a decode step reads the weight once); the
attention layers' four, the feed-forwards and the head take one part, the
MXU's own rounding, which is all that is left of bfloat16's noise (0.025).
Float32 inside the recurrence, as the published kernels compute it: the
convolution over the float32 tail, the three inner norms, the step size after
its softplus, ``exp(step x A)``, the state, the sum over N. The recurrence
advances ``[rows, N, D]`` token by token (:func:`_scan_tokens`), in one of the two
kernels of ``ops/pallas/selective_scan.py``: a chunk's holds a row's state on the
chip from the row's first token to its last valid one (HBM sees it once in and
once out), and over the rows that go on from it where a lane fills several rows
of a dispatch with successive pieces of its prompt (``LANE_TAKES_ROWS``; as far
as a group of ``ROWS_AT_ONCE`` rows reaches: :func:`forward_chunk`); a decode step's, one token of every slot, updates a layer of the run's
state in place, read once and written once. The token count the code sees picks
the kernel, the token's arithmetic is one. ``[rows, T, N, D]`` never exists.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.models.llama import (  # noqa: F401  (the two tile counts are this module's too)
    _chunk_self_partial, _live_window_attention, _merge_partials, _pool_pages,
    chunk_history_partial, chunk_history_tiles, chunk_rows_above_partial, decode_history_tiles,
    embed_lookup, flush_window, history_tile, history_tiles_full, lane_first_positions, rms_norm,
    sibling_rows_back, with_live_history,
)
from dynamo_tpu.ops.pallas.selective_scan import selective_scan, selective_step
from dynamo_tpu.ops.parts import dot_parts as _dot

Params = Dict[str, Any]
KVCache = Dict[str, jax.Array]  # {"k", "v"}: [L_attn, N, bs, KVH, D]
SlotState = Dict[str, Tuple[jax.Array, ...]]  # {"s": per run [n, S, N, D], "conv": [n, S, (K-1)*D]}

# sums the step programs return, in this order (engine: /debug/engine):
# Mamba layers run (a group of a chunk dispatch's rows or a decode step each count their 26);
# valid tokens the chunks' recurrences advanced, and the times a chunk row's
# state went from HBM to the chip and back (one a LANE of a GROUP of a
# dispatch's rows: a real row that holds a valid token and continues no row
# above it in its group; the kernel keeps the state there over the tokens of the
# lane's rows, where the scan it replaced made a pass a token), both summed over
# the Mamba layers; rows that
# started a request; real rows of a chunk dispatch that took their state and
# the convolution's tail from the row above them (the rows of a dispatch less
# the lanes it fed, a lane counted once a GROUP of rows it has a row in); rows of
# the groups a chunk dispatch computed (ROWS_AT_ONCE a group as far as the last
# row that holds a token; the engine's own ``chunk_rows_dispatched`` is the rung)
COUNTERS = ("ssm_layer_calls", "ssm_chunk_tokens", "ssm_state_passes", "slot_state_resets",
            "ssm_state_handovers", "chunk_rows_computed")
# A lane may fill several rows of one chunk dispatch with successive pieces of
# its prompt (engine_jax/engine.py:chunk_rows_of; docs/kv_cache_manager.md,
# "State per slot", says what a module with state per slot owes for it): under
# the full width a row whose lane is that of the row above it starts the
# recurrence from the state that row ends with, inside the kernel, and the
# convolution from that row's last inputs, and attends its lane's earlier rows'
# fresh keys; one row of a lane alone leaves the slot each part of its state
LANE_TAKES_ROWS = True
# Rows of a chunk dispatch computed at once (:func:`forward_chunk`): a dispatch is
# its groups of this many rows one after another, as far as the last row that
# holds a token, so three rows of a prompt cost four and not the rung's eight.
# The model is dense, 1,000 flop a weight byte at four rows where the chip
# balances at 240, so a group's time is nearly its rows': on the chip 7.2-7.4 ms
# a row at 4, 7.0 at 8, 8.3-8.9 at 2 (``tools/profile_decode.py groups``;
# PERF.md 6, PR 67). A loop over groups of 8 aborts the chip's compiler.
ROWS_AT_ONCE = 4


@dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_layers: int = 28
    num_heads: int = 20
    num_kv_heads: int = 1
    head_dim: int = 128
    # layer i (0-based) is attention where i % period == offset
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_expand: int = 2
    mamba_d_state: int = 16
    mamba_dt_rank: int = 160
    mamba_d_conv: int = 4
    rms_norm_eps: float = 1e-6
    tie_embeddings: bool = True
    dtype: Any = jnp.bfloat16

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size


def layer_kinds(c: JambaConfig) -> Tuple[str, ...]:
    """``"attn"`` or ``"mamba"`` for each layer, as the published code reads
    ``attn_layer_period`` and ``attn_layer_offset``."""
    return tuple("attn" if i % c.attn_layer_period == c.attn_layer_offset else "mamba"
                 for i in range(c.num_layers))


def segments(c: JambaConfig) -> Tuple[Tuple[str, int], ...]:
    """The layers in order as (kind, count): a run of consecutive Mamba layers
    is one segment, an attention layer one of its own."""
    out = []
    for kind in layer_kinds(c):
        if kind == "mamba" and out and out[-1][0] == "mamba":
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return tuple((kind, n) for kind, n in out)


def _runs(c: JambaConfig) -> Tuple[int, ...]:
    return tuple(n for kind, n in segments(c) if kind == "mamba")


# -- parameters ---------------------------------------------------------------

def init_params(rng: jax.Array, config: JambaConfig) -> Params:
    """Random init with fan-in scaling. ``a_log`` = log(1..N) a channel and
    ``d_skip`` = 1 as the published code initialises them; ``b_dt`` the inverse
    softplus of U(0.001, 0.1), Mamba's own initialiser, so that the step size
    is a trained model's; the convolution's bias small seeded values, so that a
    test can tell it is added."""
    c = config
    e, f, d = c.hidden_size, c.intermediate_size, c.d_inner
    n, r, kk = c.mamba_d_state, c.mamba_dt_rank, c.mamba_d_conv

    def dense(key, shape, fan_in, dtype=None):
        w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
        return w.astype(dtype or c.dtype)

    def mlp(key):
        k = jax.random.split(key, 3)
        return {"mlp_norm": jnp.ones((e,), jnp.float32), "w_gate": dense(k[0], (e, f), e),
                "w_up": dense(k[1], (e, f), e), "w_down": dense(k[2], (f, e), f)}

    def mamba(key):
        k = jax.random.split(key, 8)
        dt = jax.random.uniform(k[5], (d,), jnp.float32, 0.001, 0.1)
        return {
            "mixer_norm": jnp.ones((e,), jnp.float32),
            "w_in": dense(k[0], (e, 2 * d), e),
            "conv_w": dense(k[1], (kk, d), kk, jnp.float32),
            "conv_b": 0.1 * jax.random.normal(k[2], (d,), jnp.float32),
            "w_x": dense(k[3], (d, r + 2 * n), d),
            "dt_norm": jnp.ones((r,), jnp.float32),
            "b_norm": jnp.ones((n,), jnp.float32),
            "c_norm": jnp.ones((n,), jnp.float32),
            "w_dt": dense(k[4], (r, d), r),
            "b_dt": dt + jnp.log(-jnp.expm1(-dt)),
            "a_log": jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None], (n, d)),
            "d_skip": jnp.ones((d,), jnp.float32),
            "w_out": dense(k[6], (d, e), d),
            **mlp(k[7]),
        }

    def attn(key):
        k = jax.random.split(key, 5)
        return {
            "mixer_norm": jnp.ones((e,), jnp.float32),
            "wq": dense(k[0], (e, c.q_dim), e), "wk": dense(k[1], (e, c.kv_dim), e),
            "wv": dense(k[2], (e, c.kv_dim), e), "wo": dense(k[3], (c.q_dim, e), c.q_dim),
            **mlp(k[4]),
        }

    runs, layers, first = [], [], 0
    for kind, count in segments(c):
        keys = jnp.stack([jax.random.fold_in(rng, first + i) for i in range(count)])
        if kind == "mamba":
            runs.append(jax.vmap(mamba)(keys))
        else:
            layers.append(attn(keys[0]))
        first += count
    params = {
        "embed": dense(jax.random.fold_in(rng, 1000), (c.vocab_size, e), e),
        "final_norm": jnp.ones((e,), jnp.float32),
        "mamba": tuple(runs),
        "attn": tuple(layers),
    }
    if not c.tie_embeddings:
        params["lm_head"] = dense(jax.random.fold_in(rng, 1001), (e, c.vocab_size), e)
    return params


def param_shardings(config: JambaConfig, mesh):
    raise NotImplementedError(
        "jamba runs on one device: a slot's state over the chips of a host has no "
        "sharding rule yet"
    )


# -- the two kinds of state ---------------------------------------------------

def make_kv_cache(
    config: JambaConfig, num_blocks: int, block_size: int, dtype: Any = None,
    quantized: bool = False,
) -> KVCache:
    """The attention layers' page pool, in the Llama layout."""
    if quantized:
        raise ValueError("jamba has no int8 page layout")
    c = config
    shape = (layer_kinds(c).count("attn"), num_blocks, block_size, c.num_kv_heads, c.head_dim)
    return {"k": jnp.zeros(shape, dtype or c.dtype), "v": jnp.zeros(shape, dtype or c.dtype)}


def make_slot_state(config: JambaConfig, slots: int) -> SlotState:
    """The Mamba layers' state of every slot, zeroed, a run at a time: float32
    ``[n, S, N, D]`` and the convolution's ``K - 1`` last inputs, oldest first,
    side by side along the minor axis ``[n, S, (K - 1) * D]``."""
    c = config
    return {
        "s": tuple(jnp.zeros((n, slots, c.mamba_d_state, c.d_inner), jnp.float32)
                   for n in _runs(c)),
        "conv": tuple(jnp.zeros((n, slots, (c.mamba_d_conv - 1) * c.d_inner), jnp.float32)
                      for n in _runs(c)),
    }


# -- the Mamba mixer ----------------------------------------------------------

# bfloat16 parts of an activation that the in-projection, the out-projection,
# x_proj and dt_proj multiply (what is left of it after the first part, rounded
# again): 16 bits of the float32 activation. With every product so the chip
# reads 0.0006 against the reference, with none 0.051 (PERF.md 6, PR 41).
PARTS = 2


def lm_head(params: Params, config: JambaConfig, h: jax.Array) -> jax.Array:
    """Final hidden states to float32 logits (the head is the embedding
    table where it is tied)."""
    return _dot(h, params["embed"].T if config.tie_embeddings else params["lm_head"])


def mlp(lp: Params, c: JambaConfig, h: jax.Array) -> jax.Array:
    """``h + MLP(RMSNorm(h))``, the dense gated feed-forward."""
    x = rms_norm(h, lp["mlp_norm"], c.rms_norm_eps)
    return h + _dot(jax.nn.silu(_dot(x, lp["w_gate"])) * _dot(x, lp["w_up"]), lp["w_down"])


def _scan_tokens(lp: Params, s: jax.Array, delta: jax.Array, x: jax.Array, b: jax.Array,
                 c: jax.Array, valid: jax.Array, above=None):
    """The selective scan over ``[B, T]`` tokens from the rows' state ``s``
    ``[B, N, D]``, all float32: ``s = exp(delta A) * s + (delta x) B``, ``y =
    s C`` (summed over N). A token that is not valid leaves the state as it is.
    Returns (``y`` ``[B, T, D]``, zeros where not valid; the state after the
    last valid token). More tokens than one (a chunk): the kernel that keeps a
    row's state on the chip over its valid tokens, a prefix of the row; a row
    that goes on from the row ``above`` it (``[B]`` bool) starts from that
    row's last state on the chip and not from ``s``, and the state comes back
    at the FIRST of such rows, after the last of them. One (a decode step): the
    step kernel, which updates in place, so ``s`` may be (a run's state ``[n,
    B, N, D]``, the layer to advance) and comes back so."""
    a = -jnp.exp(lp["a_log"])  # [N, D]
    if delta.shape[1] > 1:
        return selective_scan(delta, x, b, c, a, s, valid.sum(axis=1), above,
                              interpret=jax.default_backend() == "cpu")
    run, layer = s if isinstance(s, tuple) else (s[None], 0)  # rows' own state: a run of one layer
    y, run = selective_step(delta[:, 0], x[:, 0], b[:, 0], c[:, 0], a, run, layer, valid[:, 0],
                            interpret=jax.default_backend() == "cpu")
    return y[:, None], (run, layer) if isinstance(s, tuple) else run[0]


def mamba_mixer(lp: Params, c: JambaConfig, u: jax.Array, valid: jax.Array,
                s: jax.Array, tail: jax.Array, above=None):
    """The Mamba mixer over ``[B, T, E]`` normed inputs whose valid tokens are
    a prefix of each row, from the rows' state ``s`` ``[B, N, D]`` (or a layer
    of a run's, as :func:`_scan_tokens` takes it) and the convolution's tail
    ``[B, (K - 1) * D]``: (output ``[B, T, E]``, the state after the last valid
    token, the new tail: the row's last ``K - 1`` valid inputs). ``above``
    ``[B]`` bool: a row that goes on where the row above it ends, a FULL row of
    the same sequence, takes its state and its tail from that row and not from
    ``s`` and ``tail``, and the state of the sequence comes back at its first row.
    """
    d, n, r = c.d_inner, c.mamba_d_state, c.mamba_dt_rank
    xz = _dot(u, lp["w_in"], PARTS)
    x, gate = xz[..., :d], xz[..., d:]
    # the causal depthwise convolution over the tail and the tokens, and the new tail: a chunk's
    # form or a decode step's, by the token count (:func:`_convolve`, under the chunk program)
    x, new_tail = _convolve(lp, c, x, valid, tail, above)

    dbc = _dot(x, lp["w_x"], PARTS)
    dt = rms_norm(dbc[..., :r], lp["dt_norm"], c.rms_norm_eps)
    b = rms_norm(dbc[..., r:r + n], lp["b_norm"], c.rms_norm_eps)
    cc = rms_norm(dbc[..., r + n:], lp["c_norm"], c.rms_norm_eps)
    delta = jax.nn.softplus(_dot(dt, lp["w_dt"], PARTS) + lp["b_dt"])  # [B, T, D]
    y, s = _scan_tokens(lp, s, delta, x, b, cc, valid, above)
    y = (y + lp["d_skip"] * x) * jax.nn.silu(gate)
    return _dot(y, lp["w_out"], PARTS), s, new_tail


# -- the attention mixer ------------------------------------------------------

def _project_qkv(lp: Params, c: JambaConfig, x: jax.Array):
    """q, k, v of normed inputs ``[B, T, E]``, split into heads and in the
    pages' dtype (attention's own arithmetic is ``models/llama.py``'s): no
    bias, no rotation, no q/k norm."""
    b, t, _ = x.shape
    return (_dot(x, lp["wq"]).astype(c.dtype).reshape(b, t, c.num_heads, c.head_dim),
            _dot(x, lp["wk"]).astype(c.dtype).reshape(b, t, c.num_kv_heads, c.head_dim),
            _dot(x, lp["wv"]).astype(c.dtype).reshape(b, t, c.num_kv_heads, c.head_dim))


# -- the step programs --------------------------------------------------------

def forward_chunk(
    params: Params, config: JambaConfig, tokens: jax.Array, positions: jax.Array,
    kv_cache: KVCache, block_tables: jax.Array, state: SlotState, lanes: jax.Array,
):
    """A ``[R, C]`` block of prompt tokens (``lanes`` ``[R]``: the row's slot;
    ``max_slots`` and above = a padding row), valid tokens (position >= 0) a
    prefix of each row. Under the full width (``R`` < the state's slots) a lane
    may fill several CONSECUTIVE rows with successive pieces of its prompt, in
    order, each full but the last; at it, one row a lane.

    Returns (hidden ``[R, C, E]`` after the final norm, the pool with the
    rows' K and V written, the slot state with the rows' slots advanced, the
    counters ``[len(COUNTERS)]``). A row whose first position is 0 starts from
    a zeroed state: a slot is reset by the first chunk of the request admitted
    to it.

    The rows are taken in groups of ``ROWS_AT_ONCE``, one after another and
    only as far as the last row that holds a token (the engine packs its rows
    to the front): a group of padding rows alone is not computed, and its
    hidden states stay zeros. The loop over the groups carries the runs' state
    whole and the dispatch's fresh K and V so far. Inside a group a run of Mamba
    layers is one ``lax.scan`` whose carry holds the run's state: a layer
    gathers its rows' slots by ONE flat index and scatters them back in place,
    so a group costs what its rows touch and nothing copies the state. The
    attention layers read the pool as ``models/llama.py:forward_chunk`` does
    (history a tile at a time, the group's own keys in hand); the pool is only
    read inside the loop and takes the dispatch's K and V after it, one scatter
    a pool array.

    A row whose lane is that of the row above it IN ITS GROUP (both real:
    ``above``) goes on where that row ends, inside the program. In a Mamba
    layer the recurrence starts from the state that row ends with, which the
    kernel keeps on the chip from the lane's first row of the group to its
    last, and the convolution from that row's last inputs
    (:func:`mamba_mixer`); one write a slot and group: the lane's FIRST row of
    the group holds the state after its last (``selective_scan`` leaves it
    there) and scatters it, the lane's LAST row scatters the tail, and the
    lane's other rows send their index past the run's state, as padding rows
    do. A lane whose rows straddle two groups meets itself through its slot's
    own entries: the later group's first row is not fresh and reads the state
    and the tail the group before wrote, float32 there and back. In an
    attention layer a row's pool history ends where its lane's first row of
    the DISPATCH starts, and one more partial attends the fresh keys of its
    lane's rows above it, in its group or in an earlier one
    (``chunk_rows_above_partial`` over the loop's carry). Where every lane has
    one row nothing is taken from a row above and the sibling loop makes no
    trip; at the full width none of it is in the program."""
    from dynamo_tpu.ops.attention import write_kv_to_pool

    c = config
    rows, t = positions.shape
    slots = state["s"][0].shape[1]
    # a rung that is no whole number of groups is one group, as is one of ROWS_AT_ONCE rows or fewer
    n = ROWS_AT_ONCE if rows % ROWS_AT_ONCE == 0 else rows
    handed = rows < slots  # at the full width a lane has one row, and the program holds nothing of this
    live = (lanes < slots) & (positions[:, 0] >= 0)

    scale = c.head_dim ** -0.5
    num_blocks, block_size = kv_cache["k"].shape[1:3]
    table_blocks = block_tables.shape[1]
    pages = _pool_pages(kv_cache)
    tile_blocks = history_tile(block_size, table_blocks) // block_size
    tables = jnp.pad(block_tables, (
        (0, 0), (0, history_tiles_full(block_size, table_blocks) * tile_blocks - table_blocks)))
    starts = positions[:, 0]  # where each row's pool history ends
    goes_on = None
    if handed:
        # a row that goes on from the row above it: same lane, both live, and in ONE group
        goes_on = jnp.concatenate([jnp.zeros((1,), bool), (lanes[1:] == lanes[:-1]) & live[1:] & live[:-1]])
        goes_on &= jnp.arange(rows) % n != 0
        starts = lane_first_positions(positions, lanes)  # at its lane's first row of the dispatch
        n_back = sibling_rows_back(positions, lanes)
        # the flash partial of no keys: what the sibling loop starts from (below)
        no_keys = (jnp.zeros((n, t, c.num_heads, c.head_dim), jnp.float32),
                   jnp.full((n, c.num_heads, t), -1e30, jnp.float32),
                   jnp.zeros((n, c.num_heads, t), jnp.float32))

    def group(g, carry):
        h_all, k_all, v_all, s_all, conv_all = carry
        top = g * n  # the dispatch's row that is the group's first
        toks, pos, tabs, lns, ends = (
            jax.lax.dynamic_slice_in_dim(a, top, n) for a in (tokens, positions, tables, lanes, starts))
        valid = pos >= 0
        fresh = pos[:, 0] == 0
        lane = jnp.clip(lns, 0, slots - 1)
        real = lns < slots
        above = under = None
        if handed:
            above = jax.lax.dynamic_slice_in_dim(goes_on, top, n)
            under = jnp.concatenate([above[1:], jnp.zeros((1,), bool)])  # the row under it goes on from it
        history_len = jnp.clip(ends, 0, table_blocks * block_size)
        n_tiles = chunk_history_tiles(ends[:, None], block_size, table_blocks)

        def mamba_layer(carry, xs):
            h, s_run, conv_run = carry  # [n * S, N, D], [n * S, (K-1) * D]: the run's state, flat
            lp, layer = xs
            with jax.named_scope("mamba"):
                at = layer * slots + lane
                s0 = jnp.where(fresh[:, None, None], 0.0, s_run[at])
                tail0 = jnp.where(fresh[:, None], 0.0, conv_run[at])
                y, s1, tail1 = mamba_mixer(
                    lp, c, rms_norm(h, lp["mixer_norm"], c.rms_norm_eps), valid, s0, tail0, above)
                # a padding row writes nowhere: its index lies past the run's state
                back = s_back = jnp.where(real, at, s_run.shape[0])
                if above is not None:
                    # nor do a lane's rows but ONE: the first holds the state after the last, the last the tail
                    s_back = jnp.where(above, s_run.shape[0], back)
                    back = jnp.where(under, s_run.shape[0], back)
                s_run = s_run.at[s_back].set(s1, mode="drop")
                conv_run = conv_run.at[back].set(tail1, mode="drop")
            with jax.named_scope("mlp"):
                h = mlp(lp, c, h + y)
            return (h, s_run, conv_run), None

        h = embed_lookup(params, toks, c.dtype).astype(jnp.float32)
        s_all, conv_all = list(s_all), list(conv_all)
        i = j = 0
        for kind, count in segments(c):
            if kind == "mamba":
                (h, s_all[i], conv_all[i]), _ = jax.lax.scan(
                    mamba_layer, (h, s_all[i], conv_all[i]), (params["mamba"][i], jnp.arange(count)))
                i += 1
                continue
            lp = params["attn"][j]
            with jax.named_scope("attn"):
                q, k, v = _project_qkv(lp, c, rms_norm(h, lp["mixer_norm"], c.rms_norm_eps))
                hist = chunk_history_partial(
                    c, q, pages, j * num_blocks + tabs, history_len, n_tiles, pos, scale,
                    tile_blocks, block_size, c.dtype)
                part = _merge_partials(hist, _chunk_self_partial(c, q, k, v, pos, scale))
                k_all = jax.lax.dynamic_update_slice(k_all, k[None], (j, top, 0, 0, 0))
                v_all = jax.lax.dynamic_update_slice(v_all, v[None], (j, top, 0, 0, 0))
                if handed:
                    # the rows above, in this group or an earlier one, folded from the partial of no keys
                    # (merging with it is exact): the loop's carry is kept apart from ``part``, so what
                    # stands above compiles as it does without the loop and rows alone in their lanes
                    # keep their bits
                    part = _merge_partials(part, chunk_rows_above_partial(
                        c, q, k_all[j], v_all[j], positions, lanes, top, n_back, scale, no_keys))
                num, _, den = part
                attn = jnp.where(
                    (den > 0.0).transpose(0, 2, 1)[..., None],
                    num / jnp.maximum(den, 1e-30).transpose(0, 2, 1)[..., None], 0.0)
                h = h + _dot(attn.reshape(n, t, c.q_dim), lp["wo"])
            with jax.named_scope("mlp"):
                h = mlp(lp, c, h)
            j += 1
        h = rms_norm(h, params["final_norm"], c.rms_norm_eps)
        return (jax.lax.dynamic_update_slice_in_dim(h_all, h, top, 0), k_all, v_all,
                tuple(s_all), tuple(conv_all))

    # the dispatch's fresh keys and values, both attention layers': a group's rows go in where they stand
    none_yet = jnp.zeros((len(params["attn"]), rows, t, c.num_kv_heads, c.head_dim), c.dtype)
    carry = (jnp.zeros((rows, t, c.hidden_size), jnp.float32), none_yet, none_yet,
             tuple(s.reshape(-1, *s.shape[2:]) for s in state["s"]),
             tuple(tail.reshape(-1, tail.shape[2]) for tail in state["conv"]))
    if rows == n:  # one group: no loop, and computed whether or not a row holds a token
        n_groups = jnp.int32(1)
        h, k, v, s_all, conv_all = group(jnp.int32(0), carry)
    else:
        _trace_the_chunk_kernel(c, n, t, handed)
        last = jnp.max(jnp.where(live, jnp.arange(rows) + 1, 0))
        n_groups = (last + n - 1) // n
        h, k, v, s_all, conv_all = jax.lax.fori_loop(0, n_groups, group, carry)
    cache = {"k": write_kv_to_pool(kv_cache["k"], k, positions, block_tables),
             "v": write_kv_to_pool(kv_cache["v"], v, positions, block_tables)}
    new_state = {"s": tuple(s.reshape(was.shape) for s, was in zip(s_all, state["s"])),
                 "conv": tuple(tail.reshape(was.shape) for tail, was in zip(conv_all, state["conv"]))}

    n_mamba = sum(_runs(c))
    handovers = jnp.sum(goes_on) if handed else jnp.int32(0)
    # a live row that goes on from none is a lane's first of a GROUP: where its state goes in and out
    counters = jnp.stack([
        n_mamba * n_groups, n_mamba * jnp.sum((positions >= 0) & (lanes < slots)[:, None]),
        n_mamba * (jnp.sum(live) - handovers), jnp.sum((positions[:, 0] == 0) & (lanes < slots)),
        handovers, n_groups * n])
    return h, cache, new_state, counters.astype(jnp.int32)


def _trace_the_chunk_kernel(c: JambaConfig, n: int, t: int, handed: bool) -> None:
    """Has ``selective_scan`` traced for a group of ``n`` rows of ``t`` tokens,
    and nothing else: it is jitted, so JAX keeps its trace (the kernel's body:
    two thirds of what tracing this module's chunk program costs) by the shapes
    it is called with, and the loops' three runs then find it made. Made inside
    the loop over the groups and the loop over a run's layers the same trace
    costs three times as much Python on the chip's host (0.97 s against 0.31,
    and three times that again beside a start-up's other threads), which a
    start-up pays once a chunk program, warm compile cache or not: 10 s of 48
    (PERF.md 6, PR 67). Called where the program is traced, it adds no equation
    to it."""
    d, k = c.d_inner, c.mamba_d_state

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    above = (jax.ShapeDtypeStruct((n,), bool),) if handed else ()
    jax.eval_shape(
        lambda a_log, s, delta, x, b, cc, valid, *above: _scan_tokens(
            {"a_log": a_log}, s, delta, x, b, cc, valid, *above),
        f32(k, d), f32(n, k, d), f32(n, t, d), f32(n, t, d), f32(n, t, k), f32(n, t, k),
        jax.ShapeDtypeStruct((n, t), bool), *above)


def _convolve(lp: Params, c: JambaConfig, x: jax.Array, valid: jax.Array, tail: jax.Array,
              above=None):
    """The mixer's causal depthwise convolution of ``x`` ``[B, T, D]`` behind the
    rows' tail ``[B, (K - 1) * D]`` (tap K - 1 is the token itself, tap 0 the
    oldest input), its bias and its silu: (the mixer's ``x`` ``[B, T, D]``, the
    new tail: the row's last ``K - 1`` valid inputs). A chunk's row that goes on
    from the row ``above`` it (``[B]`` bool), a FULL row of the same sequence,
    starts behind that row's last ``K - 1`` inputs and not behind ``tail``: they
    are the in-projection of that row's own tokens, so the rows are still
    convolved all at once. One token (a decode step):
    the taps are the tail's ``K - 1`` slices of ``D`` lanes and the token, and the
    new tail is the old one moved up by a token where the lane decodes, so the
    tail stays ``[B, (K - 1) * D]`` from the slot state and back. (As ``[B, K - 1,
    D]``, a chunk's form, its 3 rows lie on 4 sublanes: at one token the chip's
    compiler relaid it there and back around a gather of 192 rows, 52 us a layer
    and step of 445, as much as the state's pass: PERF.md 6, PR 44.) The same
    products, summed in the same order, in both forms."""
    bsz, t, d = x.shape
    kk = c.mamba_d_conv
    if t == 1:
        taps = [tail[:, j * d:(j + 1) * d] for j in range(kk - 1)] + [x[:, 0]]
        out = jax.nn.silu(sum(taps[j] * lp["conv_w"][j] for j in range(kk)) + lp["conv_b"])
        return out[:, None], jnp.where(valid, jnp.concatenate(taps[1:], axis=1), tail)
    if above is not None:
        if t < kk - 1:
            raise ValueError(f"a row of {t} tokens holds no tail of {kk - 1}")
        ends = x[:, t - (kk - 1):].reshape(bsz, -1)  # what each row, if full, leaves the row under it
        tail = jnp.where(above[:, None], jnp.concatenate([tail[:1], ends[:-1]]), tail)
    seq = jnp.concatenate([tail.reshape(bsz, kk - 1, d), x], axis=1)  # [B, K-1+T, D]
    out = jax.nn.silu(sum(seq[:, j:j + t] * lp["conv_w"][j] for j in range(kk)) + lp["conv_b"])
    tail_at = valid.sum(axis=1)[:, None] + jnp.arange(kk - 1)[None, :]  # the K-1 inputs before position n
    return out, jnp.take_along_axis(seq, tail_at[:, :, None], axis=1).reshape(bsz, -1)


def decode(
    params: Params, config: JambaConfig, tokens: jax.Array, positions: jax.Array,
    kv_cache: KVCache, block_tables: jax.Array, state: SlotState, steps: int, max_pos: int,
    sample, carry,
):
    """``steps`` tokens of every slot (``tokens``, ``positions`` ``[S]``;
    position < 0 = the slot does not decode, and its state stays as it is; a
    lane that passes ``max_pos`` stops there).

    A step reads and writes every slot's state once: a run of Mamba layers is
    a ``lax.scan`` that CARRIES the run's state, and a layer's step kernel
    updates its layer of it in place (the state as the loop's ``xs`` and ``ys``
    reads a layer twice: one fusion for the new state, one for the sum over N;
    PERF.md 6, PR 44); the convolution's tails are its ``xs`` and ``ys``. The
    ``steps`` (a handful) are unrolled, so that one step leaves the state where
    the next takes it and no buffer is copied. (Under a ``lax.scan`` over the
    steps the chip's compiler copies the state whole onto the loop's carry
    every step, in either form of the layer loop: PERF.md 6, PR 41.) The attention
    layers are the dense tier of the Llama decode program: the pool is
    read-only inside the dispatch, its live (lane, tile) pairs gathered once
    (``with_live_history``), a step's K and V go to a window buffer and the
    pool takes the window after the steps in one scatter a pool array
    (``flush_window``). ``sample(logits [S, V], positions, carry, k) -> (next
    tokens [S], carry, outputs)`` is the engine's. Returns (tokens, positions,
    carry, the stacked outputs, pool, state, counters ``[len(COUNTERS)]``)."""
    c = config
    segs = segments(c)
    base = positions
    n_slots = tokens.shape[0]
    window = jnp.zeros((n_slots, steps, c.num_kv_heads, c.head_dim), c.dtype)
    n_attn = layer_kinds(c).count("attn")

    def mamba_layer(carry, xs):
        # one function for every step and width, the lanes that decode in its carry: a run of
        # layers is traced and lowered once a run, and not once a run, step and width
        h, s_run, valid = carry
        lp, tail, layer = xs
        with jax.named_scope("mamba"):
            y, (s_run, _), tail = mamba_mixer(
                lp, c, rms_norm(h, lp["mixer_norm"], c.rms_norm_eps), valid, (s_run, layer), tail)
        with jax.named_scope("mlp"):
            h = mlp(lp, c, h + y)
        return (h, s_run, valid), tail

    def run(history):
        live = history[1]

        def step(loop, k):
            toks, pos, carry, s_all, conv_all, wk, wv = loop
            valid = (pos >= 0)[:, None]

            in_window = (jnp.arange(steps)[None, :] <= k) & (base[:, None] >= 0)  # [S, W]
            s_all, conv_all, wk, wv = list(s_all), list(conv_all), list(wk), list(wv)
            h = embed_lookup(params, toks, c.dtype).astype(jnp.float32)[:, None]  # [S, 1, E]
            i = j = 0
            for kind, _ in segs:
                if kind == "mamba":
                    (h, s_all[i], _), conv_all[i] = jax.lax.scan(
                        mamba_layer, (h, s_all[i], valid),
                        (params["mamba"][i], conv_all[i], jnp.arange(s_all[i].shape[0])))
                    i += 1
                    continue
                lp = params["attn"][j]
                with jax.named_scope("attn"):
                    q, kk, vv = _project_qkv(lp, c, rms_norm(h, lp["mixer_norm"], c.rms_norm_eps))
                    wk[j] = jax.lax.dynamic_update_slice(wk[j], kk, (0, k, 0, 0))
                    wv[j] = jax.lax.dynamic_update_slice(wv[j], vv, (0, k, 0, 0))
                    attn = _live_window_attention(
                        c, q, live, live.k[j], live.v[j], wk[j], wv[j], in_window, None)
                    h = h + _dot(attn.reshape(n_slots, 1, c.q_dim), lp["wo"])
                with jax.named_scope("mlp"):
                    h = mlp(lp, c, h)
                j += 1
            h = rms_norm(h, params["final_norm"], c.rms_norm_eps)
            nxt, carry, out = sample(lm_head(params, c, h)[:, 0], pos, carry, k)
            new_pos = jnp.where((pos >= 0) & (pos < max_pos), pos + 1, -1)
            return (nxt, new_pos, carry, tuple(s_all), tuple(conv_all), tuple(wk), tuple(wv)), out

        def unrolled(loop, _):
            outs = []
            for k in range(steps):
                loop, out = step(loop, jnp.int32(k))
                outs.append(out)
            return loop, jax.tree.map(lambda *a: jnp.stack(a), *outs)

        return unrolled

    # the steps update the state in place, so they take it as ``carried`` (with_live_history), and
    # with it room for what a step's ``sample`` gives, stacked over the steps
    out = jax.eval_shape(lambda: sample(
        jnp.zeros((n_slots, c.vocab_size), jnp.float32), positions, carry, jnp.int32(0))[2])
    loop = (tokens, positions, carry, state["s"], state["conv"], (window,) * n_attn, (window,) * n_attn)
    (toks, pos, carry, s_all, conv_all, wk, wv), out = with_live_history(
        kv_cache, block_tables, base, run, out_dtype=c.dtype,
        carried=(loop, jax.tree.map(lambda a: jnp.zeros((steps, *a.shape), a.dtype), out)))
    cache = flush_window(kv_cache, block_tables, base, jnp.stack(wk), jnp.stack(wv), max_pos)
    counters = jnp.zeros((len(COUNTERS),), jnp.int32).at[0].set(steps * sum(_runs(c)))
    return toks, pos, carry, out, cache, {"s": s_all, "conv": conv_all}, counters
