"""Xing4.0 decoder (``model_type: xing4_0``): FOUR residual streams mixed by
manifold-constrained hyper-connections around every sublayer
(``ops/mhc.py``), latent attention (MLA) in every layer rotated with YaRN's
frequencies, dense feed-forwards in the first layers and sigmoid-routed
experts (selected under a bias, ``noaux_tc``) beside one shared expert after
them, and ONE multi-token-prediction module behind the last layer.

The mixer, the expert layer, the page pool (one member ``latent``, NOTHING per
slot) and the contract with the engine are ``models/openpangu.py``'s, taken
from there as they are: the module brings its own step programs
(``forward_chunk``, ``draft_chunk``, ``decode``, ``COUNTERS``:
``models.module_for``), the engine hands them ``state = None``, and a prefix
hit, ``verify``, preemption, the host tier and a transfer are open to it, and
a lane may fill several rows of one chunk dispatch (``LANE_TAKES_ROWS`` is
``models/openpangu.py``'s own statement, imported with the functions it rests
on: ``_paged`` and ``_in_groups`` are called here as they are, and the maps of
the residual path are a token's own). What is this module's own is the
residual path. Between sublayers a token's state is ``hc_mult`` streams (a tuple of ``[B, T, E]`` arrays: ``ops/mhc.py`` says
why no ``[n, E]``-minor array); they start as copies of the embedding and end
as their sum, so what the engine sees (``hidden [R, C, E]`` after the final
norm, or raw for :func:`draft_chunk`) is ONE stream, as any module's.

A sublayer ``F`` (``MLA(N_in(.))``, then ``FF(N_post(.))``; pre-norm, two
norms a layer) on the streams ``X``: ``H_pre, H_post, H_res =
mhc_maps(X)``; ``u = Σ_j H_pre[j] X_j``; ``y = F(u)``; ``X_i <- Σ_j H_res[i,
j] X_j + H_post[i] y``, each sublayer with its own ``φ``, ``b``, ``α`` (the
leaves ``attn_hc`` and ``mlp_hc`` of a layer). The arithmetic is
``ops/latent.py``'s (float32 activations in three bfloat16 parts against
bfloat16 weights): a router picks 4 of 64, and the maps decide how every later
layer is fed.

YaRN (``rope_scaling.type: yarn``, the DeepSeek-V3 form): the rotated parts of
``q`` and the one shared ``k_r`` turn with a STATIC blend of ``rope_theta``'s
frequencies and those a ``factor``-th as fast (:func:`yarn_inv_freq`), cosine
and sine times ``mscale / mscale_all_dim``'s ratio (1 at the published keys,
and only 1 is run), and the scores' scale is ``qk_head_dim^-0.5 x
yarn_mscale(factor, mscale_all_dim)²``.

Multi-token prediction (the DeepSeek-V3 form, as ``models/openpangu.py``
has it): ``u_i = W_eh [N_e(Emb(t_{i+1})) ; N_h(x_i)]`` with ``x_i`` the
collapsed raw output, ``hc_mult`` copies of ``u_i``, one expert layer with its
own two mHC sets and its own latent pages, collapse by sum, ``N_mtp``, the
main head.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.models import openpangu as base
from dynamo_tpu.models.llama import embed_lookup, rms_norm
from dynamo_tpu.models.openpangu import (  # noqa: F401  (the module's contract: models.module_for)
    LANE_TAKES_ROWS, MOE_COUNTERS, chunk_history_tiles, decode_history_tiles, feed_forward, final_norm,
    is_expert_layer, lm_head, make_kv_cache, mixer, param_shardings,
)
from dynamo_tpu.ops import mhc
from dynamo_tpu.ops.latent import (
    attend_absorbed_live, live_latents, live_positions_attended, recent_latents, write_latent,
)

Params = Dict[str, Any]
KVCache = Dict[str, jax.Array]  # {"latent": [L (+ 1 where the engine drafts), N, bs, W]} float32

# sums the step programs return, in this order (engine: /debug/engine): openPangu's, then the
# residual path's: maps computed (a sublayer of a group of rows or of a decode step) and the token
# rows those calls mixed
COUNTERS = (*base.COUNTERS, "mhc_mix_calls", "mhc_rows_mixed")


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original_positions: int,
                  beta_fast: float, beta_slow: float) -> Tuple[float, ...]:
    """YaRN's ``dim / 2`` rotary frequencies: ``f_i = theta^(-2i / dim)`` where
    a pair turns more than ``beta_fast`` times over the original context, ``f_i
    / factor`` where fewer than ``beta_slow`` times, a linear ramp between."""
    def turns_at(turns: float) -> float:  # the pair that turns ``turns`` times over the original context
        return dim * math.log(original_positions / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    out = []
    for i in range(dim // 2):
        f = theta ** (-2.0 * i / dim)
        ramp = min(max((i - low) / max(high - low, 0.001), 0.0), 1.0)
        out.append(f / factor * ramp + f * (1.0 - ramp))
    return tuple(out)


@dataclass(frozen=True)
class Xing4Config(base.OpenPanguConfig):
    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216
    num_layers: int = 40
    num_heads: int = 32
    q_lora_rank: int = 768
    rope_theta: float = 10000.0
    first_k_dense: int = 2
    moe_intermediate_size: int = 1024
    num_experts: int = 64
    num_experts_published: int = 64
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 2.0
    rms_norm_eps: float = 1e-6
    # the residual path
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)
    # YaRN (None: rotate by rope_theta alone)
    yarn_factor: Optional[float] = 64.0
    yarn_original_positions: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale_all_dim: float = 1.0

    @property
    def score_scale(self) -> float:
        scale = self.qk_head_dim ** -0.5
        if self.yarn_factor is None:
            return scale
        return scale * yarn_mscale(self.yarn_factor, self.yarn_mscale_all_dim) ** 2

    @property
    def rope_inv_freq(self):
        if self.yarn_factor is None:
            return None
        return yarn_inv_freq(self.qk_rope_head_dim, self.rope_theta, self.yarn_factor,
                             self.yarn_original_positions, self.yarn_beta_fast, self.yarn_beta_slow)

    @property
    def hc_maps(self) -> int:
        """Values of one sublayer's three maps: ``2n + n²``."""
        return 2 * self.hc_mult + self.hc_mult ** 2


# -- parameters ---------------------------------------------------------------

def _init_hc(key, c: Xing4Config) -> Params:
    """One sublayer's mHC set, seeded so that the maps are alive: ``φ ~ N(0, 1
    / nC)`` (``x̂ φ`` of unit variance), ``α`` = 1, ``b_pre`` = ``b_post`` = 0,
    ``b_res`` = 2 I: ``H_res`` is neither the identity nor uniform and ``H_pre``,
    ``H_post`` vary between tokens, so a program that skipped or froze the maps
    is another model. ``φ`` is held transposed (``ops/mhc.py``), in the
    configuration's dtype as ``b`` and ``α`` are."""
    n, wide = c.hc_mult, c.hc_mult * c.hidden_size
    b = jnp.concatenate([jnp.zeros((2 * n,), jnp.float32), 2.0 * jnp.eye(n, dtype=jnp.float32).reshape(-1)])
    return {"phi": base._dense(key, (c.hc_maps, wide), wide, c.dtype), "b": b.astype(c.dtype),
            "alpha": jnp.ones((3,), c.dtype)}


def _init_layer(key, c: Xing4Config, experts: bool) -> Params:
    """openPangu's layer without the sandwich's two post-norms, with the two
    sublayers' mHC sets and, in an expert layer, the router's selection bias
    (small seeded values: it moves the choice and never a weight)."""
    lp = base._init_layer(key, c, experts)
    del lp["post_attn_norm"], lp["post_mlp_norm"]
    lp["attn_hc"] = _init_hc(jax.random.fold_in(key, 100), c)
    lp["mlp_hc"] = _init_hc(jax.random.fold_in(key, 101), c)
    if experts:
        lp["e_bias"] = 0.1 * jax.random.normal(jax.random.fold_in(key, 102), (c.num_experts_published,), jnp.float32)
    return lp


def init_params(rng: jax.Array, config: Xing4Config) -> Params:
    """openPangu's tree (embedding, layers, head, the prediction module) of this module's layers."""
    return base.init_params(rng, config, _init_layer)


# -- a layer ------------------------------------------------------------------

def _around(hp: Params, c: Xing4Config, streams: mhc.Streams, sublayer):
    """The residual path around one sublayer: ``sublayer(u) -> (y, aux)`` sees
    the streams mixed in and its output is mixed out. Returns (streams, aux)."""
    with jax.named_scope("mhc"):
        h_pre, h_post, h_res = mhc.mhc_maps(
            streams, hp["phi"], hp["b"], hp["alpha"], c.hc_sinkhorn_iters, c.hc_eps,
            c.rms_norm_eps, c.hc_clamp)
        u = mhc.mix_in(streams, h_pre)
    y, aux = sublayer(u)
    with jax.named_scope("mhc"):
        return mhc.mix_out(streams, h_res, h_post, y), aux


def _layer(lp: Params, c: Xing4Config, streams: mhc.Streams, positions: jax.Array, width: int, attend):
    """One decoder layer over the streams (``hc_mult`` arrays ``[B, T, E]``) at
    ``positions`` ``[B, T]`` (< 0: padding), ``attend`` ``models/openpangu.py:
    mixer``'s. Returns (streams, the expert counters)."""
    eps = c.rms_norm_eps
    streams, _ = _around(lp["attn_hc"], c, streams, lambda u: (
        mixer(lp, c, rms_norm(u, lp["in_norm"], eps), positions, width, attend), None))
    return _around(lp["mlp_hc"], c, streams, lambda u: feed_forward(
        lp, c, rms_norm(u, lp["pre_mlp_norm"], eps), positions >= 0))


def _mhc_counts(layers: int, positions: jax.Array) -> jax.Array:
    """``mhc_mix_calls``, ``mhc_rows_mixed`` of ``layers`` layers (two
    sublayers each) over rows at ``positions`` (< 0: padding, not counted)."""
    return jnp.stack([jnp.int32(2 * layers), 2 * layers * (positions >= 0).sum()]).astype(jnp.int32)


def _embedded(params: Params, c: Xing4Config, tokens: jax.Array) -> mhc.Streams:
    return mhc.spread(embed_lookup(params, tokens, c.dtype).astype(jnp.float32), c.hc_mult)


def _own_sums(mla: jax.Array, mtp: int, layers: int, positions: jax.Array) -> jax.Array:
    """``COUNTERS`` behind the expert layer's: latent attention's three,
    ``mtp_layer_calls``, the residual path's two."""
    return jnp.concatenate([mla, jnp.full((1,), mtp, jnp.int32), _mhc_counts(layers, positions)])


# -- the step programs (models/openpangu.py's, over the streams) ---------------

def forward_chunk(
    params: Params, config: Xing4Config, tokens: jax.Array, positions: jax.Array,
    kv_cache: KVCache, block_tables: jax.Array, state: None, lanes: jax.Array,
    raw: bool = False,
):
    """``models/openpangu.py:forward_chunk``'s contract: a ``[R, C]`` block of
    tokens, a row a lane or successive pieces of a lane's prompt in consecutive
    rows (``lanes`` is read nowhere), valid tokens a prefix of each row, a row
    starting at any position. Returns (hidden ``[R, C, E]``: the streams' sum
    after the final norm, or before it where ``raw``; the pool with the rows'
    latents written; ``state`` as it came: None; the counters
    ``[len(COUNTERS)]``)."""
    c = config

    def rows_fn(pool, tokens, positions, block_tables):
        box = [pool]
        n_tiles, attended = base._tiles_read(positions, pool, block_tables)
        streams = _embedded(params, c, tokens)
        counters = jnp.zeros((MOE_COUNTERS,), jnp.int32)
        for i, lp in enumerate(params["layers"]):
            streams, stats = _layer(lp, c, streams, positions, pool.shape[-1],
                                    base._paged(c, box, i, positions, block_tables, n_tiles))
            counters = counters + stats
        x = mhc.collapse(streams)
        h = x if raw else final_norm(params, c, x)
        own = base._mla_counts(c.num_layers, positions, attended)
        return h, box[0], jnp.concatenate([counters, _own_sums(own, 0, c.num_layers, positions)])

    h, pool, sums = base._in_groups(rows_fn, kv_cache["latent"], (tokens, positions, block_tables),
                                    tokens.shape[1], len(COUNTERS))
    return h, {"latent": pool}, state, sums


def draft_chunk(
    params: Params, config: Xing4Config, hidden: jax.Array, next_tokens: jax.Array,
    positions: jax.Array, kv_cache: KVCache, block_tables: jax.Array,
):
    """The prediction module over the positions a dispatch computed
    (``models/openpangu.py:draft_chunk``'s contract): ``hidden`` ``[R, C, E]``
    the main stack's RAW collapsed output, ``next_tokens`` the token that
    follows each. Returns (hidden after ``N_mtp``; the pool; the counters)."""
    c = config
    layer = c.num_layers  # the module's pages lie behind the decoder's
    if kv_cache["latent"].shape[0] <= layer:
        raise ValueError("the pool holds no pages for the prediction module (make_kv_cache(drafting=True))")

    def rows_fn(pool, hidden, next_tokens, positions, block_tables):
        box = [pool]
        n_tiles, attended = base._tiles_read(positions, pool, block_tables)
        with jax.named_scope("mtp"):
            u = base._mtp_input(params, c, hidden, next_tokens)
            streams, stats = _layer(params["mtp"]["layer"], c, mhc.spread(u, c.hc_mult), positions,
                                    pool.shape[-1],
                                    base._paged(c, box, layer, positions, block_tables, n_tiles))
            h = rms_norm(mhc.collapse(streams), params["mtp"]["norm"], c.rms_norm_eps)
        own = base._mla_counts(1, positions, attended)
        return h, box[0], jnp.concatenate([stats, _own_sums(own, 1, 1, positions)])

    h, pool, sums = base._in_groups(rows_fn, kv_cache["latent"],
                                    (hidden, next_tokens, positions, block_tables), hidden.shape[1],
                                    len(COUNTERS))
    return h, {"latent": pool}, sums


def decode(
    params: Params, config: Xing4Config, tokens: jax.Array, positions: jax.Array,
    kv_cache: KVCache, block_tables: jax.Array, state: None, steps: int, max_pos: int,
    sample, carry, draft: bool = False,
):
    """``steps`` tokens of every slot (``models/openpangu.py:decode``'s
    contract and form: every layer's history gathered ONCE a dispatch, a
    step's latent written to a small buffer a layer, the lane's tiles that
    hold history and the buffer attended, the pool takes the buffers after the
    loop). Returns (tokens, positions, carry, the stacked outputs, pool,
    ``state`` as it came, counters ``[len(COUNTERS)]``) and, where ``draft``,
    the module's first choice for the token AFTER the last one sampled,
    ``[S]`` int32."""
    c = config
    pool = kv_cache["latent"]
    n_hist = c.num_layers + bool(draft)
    if pool.shape[0] < n_hist:
        raise ValueError("the pool holds no pages for the prediction module (make_kv_cache(drafting=True))")
    live = live_latents(pool, n_hist, block_tables, positions)
    attended = live_positions_attended(live, steps)
    # read ONCE a dispatch, whoever decodes: every lane's whole table, by the gather
    gathered = n_hist * block_tables.size * pool.shape[2]

    def step(loop, k):
        toks, pos, carry, recent, counters, drafts = loop
        recent = list(recent)
        pos2 = pos[:, None]

        def buffered(j):
            def attend(lp, q, latent):
                ends, dims = base._absorbed(lp, c)
                out, recent[j] = attend_absorbed_live(q, *ends, live, j, recent[j], latent, k, pos >= 0, *dims)
                return out
            return attend

        streams = _embedded(params, c, toks[:, None])
        for i, lp in enumerate(params["layers"]):
            streams, stats = _layer(lp, c, streams, pos2, pool.shape[-1], buffered(i))
            counters = counters.at[:MOE_COUNTERS].add(stats)
        x = mhc.collapse(streams)
        nxt, carry, out = sample(lm_head(params, c, final_norm(params, c, x))[:, 0], pos, carry, k)
        if draft:
            with jax.named_scope("mtp"):
                u = base._mtp_input(params, c, x, nxt[:, None])
                streams, stats = _layer(params["mtp"]["layer"], c, mhc.spread(u, c.hc_mult), pos2,
                                        pool.shape[-1], buffered(c.num_layers))
                y = rms_norm(mhc.collapse(streams), params["mtp"]["norm"], c.rms_norm_eps)
                guess = jnp.argmax(lm_head(params, c, y)[:, 0], axis=-1).astype(jnp.int32)
            drafts = jnp.where(pos >= 0, guess, drafts)
            counters = counters.at[:MOE_COUNTERS].add(stats)
        counters = counters.at[MOE_COUNTERS:].add(_own_sums(
            base._mla_counts(n_hist, pos2, attended), int(draft), n_hist, pos2))
        new_pos = jnp.where((pos >= 0) & (pos < max_pos), pos + 1, -1)
        return (nxt, new_pos, carry, tuple(recent), counters, drafts), (out, pos)

    (toks, pos, carry, recent, counters, drafts), (out, at) = jax.lax.scan(
        step,
        (tokens, positions, carry, recent_latents(pool, n_hist, tokens.shape[0], steps),
         jnp.zeros((len(COUNTERS),), jnp.int32).at[COUNTERS.index("mla_history_positions_read")].set(gathered),
         jnp.zeros_like(tokens)),
        jnp.arange(steps))
    for j, lat in enumerate(recent):  # [S, steps, W], written at `at` [steps, S]
        pool = write_latent(pool, j, lat, at.T, block_tables)
    done = (toks, pos, carry, out, {"latent": pool}, state, counters)
    return (*done, drafts) if draft else done
