"""Llama-family decoder in pure-functional JAX with paged KV cache.

Design choices (TPU-first):
- **Stacked layers + lax.scan**: all L layers' weights are stacked on a leading
  axis and the decoder scans over them — one compiled layer body regardless of
  depth, fast compiles even for 80-layer 70B.
- **Paged KV in HBM**: the cache is a page pool `[L, N, bs, KVH, D]`; the model
  writes new K/V into pages then attends through block tables (ops/attention.py),
  so prefill, decode, and prefix-hit prefill are ONE code path with static shapes.
- **bfloat16 matmuls on the MXU**, float32 norms/softmax/logits.
- **Logical sharding axes** on every param (parallel/mesh.py) — Megatron-style
  TP over heads/MLP, vocab-sharded embeddings; XLA inserts the ICI collectives.

Capability parity: the reference serves this family via vLLM workers
(SURVEY.md §2.9-2.10); here the model is framework-native.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]
KVCache = Dict[str, jax.Array]  # {"k": [L,N,bs,KVH,D], "v": ...}


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    tie_embeddings: bool = False
    qkv_bias: bool = False  # qwen2-family attention biases
    # sparse MoE MLP (mixtral family): > 1 activates ops/moe.py in every
    # serving path's MLP block; 0/1 = dense MLP
    num_experts: int = 0
    num_experts_per_tok: int = 2
    expert_capacity_factor: float = 2.0  # serving: generous, rare drops
    dtype: Any = jnp.bfloat16

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim


LLAMA_PRESETS: Dict[str, LlamaConfig] = {
    # test-size model: tiny but structurally identical (GQA, untied head)
    "tiny": LlamaConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, rope_theta=10000.0,
    ),
    "llama3.2-1b": LlamaConfig(
        vocab_size=128256, hidden_size=2048, intermediate_size=8192, num_layers=16,
        num_heads=32, num_kv_heads=8, head_dim=64, tie_embeddings=True,
    ),
    "llama3-8b": LlamaConfig(),
    "llama3-70b": LlamaConfig(
        hidden_size=8192, intermediate_size=28672, num_layers=80,
        num_heads=64, num_kv_heads=8, head_dim=128,
    ),
    # qwen2 family: same decoder with attention biases + its own dims
    "qwen2.5-7b": LlamaConfig(
        vocab_size=152064, hidden_size=3584, intermediate_size=18944,
        num_layers=28, num_heads=28, num_kv_heads=4, head_dim=128,
        rope_theta=1000000.0, rms_norm_eps=1e-6, qkv_bias=True,
    ),
    "qwen2.5-1.5b": LlamaConfig(
        vocab_size=151936, hidden_size=1536, intermediate_size=8960,
        num_layers=28, num_heads=12, num_kv_heads=2, head_dim=128,
        rope_theta=1000000.0, rms_norm_eps=1e-6, qkv_bias=True,
        tie_embeddings=True,
    ),
    # mixtral family: llama attention + sparse MoE MLP (expert parallel)
    "tiny-moe": LlamaConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, rope_theta=10000.0,
        num_experts=4, num_experts_per_tok=2,
    ),
    "mixtral-8x7b": LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
        rope_theta=1000000.0, num_experts=8, num_experts_per_tok=2,
    ),
}


# -- params ------------------------------------------------------------------

def init_params(rng: jax.Array, config: LlamaConfig) -> Params:
    """Random init with fan-in scaling; layer weights stacked on axis 0."""
    c = config
    keys = jax.random.split(rng, 8)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(c.dtype)

    L, E, F = c.num_layers, c.hidden_size, c.intermediate_size
    if c.num_experts > 1:  # sparse MoE MLP: per-expert FFN + router
        X = c.num_experts
        mlp_weights = {
            "moe_router": dense(keys[5], (L, E, X), E).astype(jnp.float32),
            "w_gate": dense(keys[6], (L, X, E, F), E),
            "w_up": dense(keys[7], (L, X, E, F), E),
            "w_down": dense(jax.random.fold_in(rng, 42), (L, X, F, E), F),
        }
    else:
        mlp_weights = {
            "w_gate": dense(keys[5], (L, E, F), E),
            "w_up": dense(keys[6], (L, E, F), E),
            "w_down": dense(keys[7], (L, F, E), F),
        }
    params: Params = {
        "embed": dense(keys[0], (c.vocab_size, E), E),
        "final_norm": jnp.ones((E,), jnp.float32),
        "layers": {
            "attn_norm": jnp.ones((L, E), jnp.float32),
            "wq": dense(keys[1], (L, E, c.q_dim), E),
            "wk": dense(keys[2], (L, E, c.kv_dim), E),
            "wv": dense(keys[3], (L, E, c.kv_dim), E),
            "wo": dense(keys[4], (L, c.q_dim, E), c.q_dim),
            "mlp_norm": jnp.ones((L, E), jnp.float32),
            **mlp_weights,
        },
    }
    if c.qkv_bias:
        params["layers"]["bq"] = jnp.zeros((L, c.q_dim), jnp.float32)
        params["layers"]["bk"] = jnp.zeros((L, c.kv_dim), jnp.float32)
        params["layers"]["bv"] = jnp.zeros((L, c.kv_dim), jnp.float32)
    if not c.tie_embeddings:
        params["lm_head"] = dense(jax.random.fold_in(rng, 99), (E, c.vocab_size), E)
    return params


def param_logical_axes(config: LlamaConfig) -> Params:
    """Logical sharding axes per param leaf (names resolved by parallel/mesh.py)."""
    axes: Params = {
        "embed": ("vocab", "embed"),
        "final_norm": ("embed",),
        # leading axis = stacked layers → pipeline stages when pp > 1
        "layers": {
            "attn_norm": ("layers", "embed"),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "mlp_norm": ("layers", "embed"),
            **(
                {
                    # MoE: experts shard over ep, FFN width over tp
                    "moe_router": ("layers", "embed", None),
                    "w_gate": ("layers", "experts", "embed", "mlp"),
                    "w_up": ("layers", "experts", "embed", "mlp"),
                    "w_down": ("layers", "experts", "mlp", "embed"),
                }
                if config.num_experts > 1
                else {
                    "w_gate": ("layers", "embed", "mlp"),
                    "w_up": ("layers", "embed", "mlp"),
                    "w_down": ("layers", "mlp", "embed"),
                }
            ),
        },
    }
    if config.qkv_bias:
        axes["layers"]["bq"] = ("layers", "heads")
        axes["layers"]["bk"] = ("layers", "kv_heads")
        axes["layers"]["bv"] = ("layers", "kv_heads")
    if not config.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def param_shardings(config: LlamaConfig, mesh) -> Params:
    """NamedSharding pytree matching init_params' structure. A configuration
    that is no ``LlamaConfig`` is handed on to its own module's function
    (``benchmark/reference_child.py`` imports THIS one by name for every
    model: ROADMAP B11 takes the opening out again)."""
    from dynamo_tpu.parallel.mesh import logical_to_sharding

    if not isinstance(config, LlamaConfig):
        from dynamo_tpu.models import module_for

        return module_for(config).param_shardings(config, mesh)

    return jax.tree.map(
        lambda ax: logical_to_sharding(mesh, *ax),
        param_logical_axes(config),
        is_leaf=lambda x: isinstance(x, tuple),
    )


def make_kv_cache(
    config: LlamaConfig, num_blocks: int, block_size: int, dtype: Any = None,
    quantized: bool = False,
) -> KVCache:
    """Allocate the paged KV pool: [layers, blocks, block_size, kv_heads, head_dim].

    ``quantized=True`` builds the int8 page layout: pages store int8 values
    and the dict carries per-block scale tables ``k_scale``/``v_scale``
    ([L, num_blocks, block_size] float32 — one absmax scale per token row
    per layer, grouped by physical block so scales travel WITH their pages
    through prefix reuse, the host tier, and the disagg transfer plane).
    Per-token granularity is what makes incremental decode writes exact:
    each new token quantizes independently, so a partially-written block
    never needs re-scaling. Overhead is 4 bytes per (layer, token) vs
    ``2*kv_heads*head_dim`` page bytes — < 2% at every preset."""
    c = config
    shape = (c.num_layers, num_blocks, block_size, c.num_kv_heads, c.head_dim)
    if quantized:
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(shape[:3], jnp.float32),
            "v_scale": jnp.zeros(shape[:3], jnp.float32),
        }
    dt = dtype or c.dtype
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def kv_cache_quantized(kv_cache: KVCache) -> bool:
    """Is this pool the int8 page layout? (Static at trace time — the key
    set of the cache dict decides which code path compiles.)"""
    return "k_scale" in kv_cache


def quantize_kv(k: jax.Array, v: jax.Array):
    """Per-token absmax int8 quantization of fresh K/V ([..., KVH, D] →
    int8 values + float32 scales over the last two axes). The scale floor
    keeps all-zero rows (padding lanes) exact: 0/eps quantizes to 0."""
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    ks = jnp.maximum(jnp.max(jnp.abs(kf), axis=(-2, -1)), 1e-12) / 127.0
    vs = jnp.maximum(jnp.max(jnp.abs(vf), axis=(-2, -1)), 1e-12) / 127.0
    kq = jnp.clip(jnp.round(kf / ks[..., None, None]), -127, 127).astype(jnp.int8)
    vq = jnp.clip(jnp.round(vf / vs[..., None, None]), -127, 127).astype(jnp.int8)
    return kq, vq, ks, vs


def dequantize_kv(kq: jax.Array, scale: jax.Array, dtype: Any) -> jax.Array:
    """int8 pages + per-token scales → compute-dtype values. The scale
    multiply runs in f32 (it carries the quantization precision) and drops
    to the compute dtype afterwards — same contract as :func:`matw`."""
    return (kq.astype(jnp.float32) * scale[..., None, None]).astype(dtype)


# -- int8 weight-only quantization -------------------------------------------

def matw(x: jax.Array, w) -> jax.Array:
    """``x @ w`` where ``w`` is a plain array or an int8 pair {"q", "s"}.

    Weight-only per-output-channel absmax quantization: the int8 tensor is
    converted inline and the dot's operand load fuses the convert, so the
    HBM read halves (weights ARE the decode roofline — a bf16 1B model
    streams 2.5 GB/step). Scales stay in float32 and multiply the output."""
    if isinstance(w, dict):
        y = x @ w["q"].astype(x.dtype)
        # scales multiply in f32 (they carry the quantization precision;
        # rounding them to bf16 first would compound the int8 error), then
        # the product drops back to the activation dtype — XLA fuses the
        # convert/mul/convert chain into the matmul epilogue
        return (y.astype(jnp.float32) * w["s"]).astype(x.dtype)
    return x @ w


def embed_lookup(params: Params, tokens: jax.Array, dtype: Any = jnp.bfloat16) -> jax.Array:
    """Embedding-table gather, transparent to int8 quantization (per-row)."""
    e = params["embed"]
    if isinstance(e, dict):
        rows = jnp.clip(tokens, 0)
        deq = e["q"][rows].astype(jnp.float32) * e["s"][rows][..., None]
        return deq.astype(dtype)
    return e[jnp.clip(tokens, 0)]


def quantize_params_int8(params: Params, config: LlamaConfig) -> Params:
    """Quantize every dense weight matrix to int8 with per-output-channel
    (absmax/127) scales; norms, biases and the MoE router stay as they are.
    The embedding table quantizes per ROW so both its gather use and its
    tied lm-head use (scale per vocab column of ``embed.T``) stay cheap.

    Dense mats contract over the second-to-last axis, both plain stacked
    ([L, in, out]) and MoE expert stacks ([L, X, in, out]) — so one rule
    quantizes every family. Mesh-sharded serving uses this tree with
    :func:`quantized_param_shardings`."""

    def quant(w: jax.Array, contract_axis: int) -> dict:
        wf = w.astype(jnp.float32)
        s = jnp.max(jnp.abs(wf), axis=contract_axis) / 127.0  # per out-channel
        s = jnp.maximum(s, 1e-12)
        q = jnp.round(wf / jnp.expand_dims(s, contract_axis))
        return {"q": jnp.clip(q, -127, 127).astype(jnp.int8), "s": s}

    out = dict(params)
    out["embed"] = quant(params["embed"], 1)  # per-row: [V, E] → s [V]
    if "lm_head" in params:
        out["lm_head"] = quant(params["lm_head"], 0)
    lp = dict(params["layers"])
    for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        if name in lp:
            lp[name] = quant(lp[name], lp[name].ndim - 2)
    out["layers"] = lp
    return out


def quantized_logical_axes(config: LlamaConfig) -> Params:
    """Logical sharding axes for :func:`quantize_params_int8`'s tree: ``q``
    shards exactly like its parent weight; ``s`` (per-out-channel scales)
    keeps every parent axis except the contracted one. This is what lets
    int8 decode run on a dp×tp×ep mesh — the 70B north-star config — with
    each shard holding its own slice of both tensors."""
    axes = param_logical_axes(config)

    def q_axes(ax, contract_idx):
        return {
            "q": ax,
            "s": tuple(a for i, a in enumerate(ax) if i != contract_idx),
        }

    axes["embed"] = q_axes(axes["embed"], 1)
    if "lm_head" in axes:
        axes["lm_head"] = q_axes(axes["lm_head"], 0)
    for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        if name in axes["layers"]:
            ax = axes["layers"][name]
            axes["layers"][name] = q_axes(ax, len(ax) - 2)
    return axes


def quantized_param_shardings(config: LlamaConfig, mesh) -> Params:
    """NamedSharding pytree matching quantize_params_int8's structure."""
    from dynamo_tpu.parallel.mesh import logical_to_sharding

    return jax.tree.map(
        lambda ax: logical_to_sharding(mesh, *ax),
        quantized_logical_axes(config),
        is_leaf=lambda x: isinstance(x, tuple),
    )


# -- math --------------------------------------------------------------------

def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * weight).astype(x.dtype)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float, inv_freq=None) -> jax.Array:
    """Rotary embedding; x: [B, T, H, D], positions: [B, T]. ``inv_freq``
    (``D / 2`` values, a model's scaled table: YaRN's blend of ``theta``'s
    frequencies) takes the place of ``theta``'s own where given."""
    d = x.shape[-1]
    if inv_freq is None:
        freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)  # [D/2]
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    angles = jnp.clip(positions, 0).astype(jnp.float32)[..., None] * freqs  # [B,T,D/2]
    cos = jnp.cos(angles)[:, :, None, :]  # [B,T,1,D/2]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return rotated.astype(x.dtype)


def project_qkv(
    lp: Params, c: LlamaConfig, hidden: jax.Array, positions: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Shared attention-input block: pre-norm, Q/K/V projections (+ qwen2
    biases), head reshape, rope. One implementation for every layer body
    (decode window, prefill chunk, sp chunk, pipeline stage) so the paths
    cannot drift."""
    b, t = positions.shape
    x = rms_norm(hidden, lp["attn_norm"], c.rms_norm_eps)
    q, k, v = matw(x, lp["wq"]), matw(x, lp["wk"]), matw(x, lp["wv"])
    if c.qkv_bias:
        q = q + lp["bq"].astype(q.dtype)
        k = k + lp["bk"].astype(k.dtype)
        v = v + lp["bv"].astype(v.dtype)
    q = q.reshape(b, t, c.num_heads, c.head_dim)
    k = k.reshape(b, t, c.num_kv_heads, c.head_dim)
    v = v.reshape(b, t, c.num_kv_heads, c.head_dim)
    q = apply_rope(q, positions, c.rope_theta)
    k = apply_rope(k, positions, c.rope_theta)
    return q, k, v


def mlp_block(
    lp: Params, c: LlamaConfig, hidden: jax.Array, positions: jax.Array
) -> jax.Array:
    """Shared MLP block (post-norm + FFN + residual): dense silu-gate, or
    the sparse MoE FFN (ops/moe.py, experts over the ep mesh axis) when the
    config declares experts — every serving path gets MoE for free.
    ``positions`` (< 0 = padding) masks padding tokens out of MoE routing
    so they cannot consume expert capacity ahead of real tokens."""
    x = rms_norm(hidden, lp["mlp_norm"], c.rms_norm_eps)
    if c.num_experts > 1:
        from dynamo_tpu.ops.moe import MoeConfig, moe_mlp

        mcfg = MoeConfig(
            hidden_size=c.hidden_size,
            intermediate_size=c.intermediate_size,
            num_experts=c.num_experts,
            top_k=c.num_experts_per_tok,
            capacity_factor=c.expert_capacity_factor,
        )
        moe_params = {
            "router": lp["moe_router"],
            "w_gate": lp["w_gate"],
            "w_up": lp["w_up"],
            "w_down": lp["w_down"],
        }
        out, _aux = moe_mlp(moe_params, mcfg, x, token_valid=positions >= 0)
        return hidden + out.astype(hidden.dtype)
    gate = jax.nn.silu(matw(x, lp["w_gate"]).astype(jnp.float32)).astype(x.dtype)
    return hidden + matw(gate * matw(x, lp["w_up"]), lp["w_down"])


# -- forward -----------------------------------------------------------------

def decoder_layer(
    lp: Params,  # one layer's params (leading layer axis removed)
    config: LlamaConfig,
    hidden: jax.Array,  # [B, T, E]
    positions: jax.Array,  # [B, T]; < 0 = padding
    k_page: jax.Array,  # this layer's page pool [N, bs, KVH, D]
    v_page: jax.Array,
    block_tables: jax.Array,  # [B, max_blocks]
    *,
    soft_cap: Optional[float] = None,
    use_pallas: Optional[bool] = None,
    mesh=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decoder layer: returns (hidden, k_page, v_page).

    Shared by the single-program scan in :func:`forward` and the
    pipeline-parallel stage loop (parallel/pipeline.py)."""
    from dynamo_tpu.ops.attention import paged_attention, write_kv_to_pages

    c = config
    b, t = positions.shape

    q, k, v = project_qkv(lp, c, hidden, positions)
    k_page, v_page = write_kv_to_pages(k_page, v_page, k, v, positions, block_tables)
    attn = paged_attention(
        q, k_page, v_page, block_tables, positions, soft_cap=soft_cap,
        use_pallas=use_pallas, mesh=mesh,
    )
    hidden = hidden + matw(attn.reshape(b, t, c.q_dim), lp["wo"])
    return mlp_block(lp, c, hidden, positions), k_page, v_page


def lm_head(params: Params, config: LlamaConfig, h: jax.Array) -> jax.Array:
    """Project final hidden states to vocabulary logits (float32)."""
    head = params["embed"] if config.tie_embeddings else params["lm_head"]
    if isinstance(head, dict):
        q, s = head["q"], head["s"]
        if config.tie_embeddings:
            # embed is quantized per ROW ([V] scales) = per vocab column of
            # embed.T, so the scale applies to the logit axis either way
            return (h @ q.T.astype(h.dtype)).astype(jnp.float32) * s[None, :]
        return (h @ q.astype(h.dtype)).astype(jnp.float32) * s[None, :]
    if config.tie_embeddings:
        head = head.T
    return (h @ head).astype(jnp.float32)


def _window_attention(
    c: LlamaConfig,
    q: jax.Array,  # [B, 1, H, D] (rope applied)
    gk: jax.Array,  # [B, Smax, KVH, D] dense history (pre-gathered pages)
    gv: jax.Array,
    base: jax.Array,  # [B] history holds positions < base; -1 = padding lane
    wk: jax.Array,  # [B, W, KVH, D] window K (rope applied)
    wv: jax.Array,
    wslot: jax.Array,  # scalar: current window slot (q's own position)
    soft_cap: Optional[float],
) -> jax.Array:
    """Attention over (dense history, decode window) as two flash partials,
    the history at every table's full width: all ``Smax`` positions of every
    lane are scored and the dead ones masked. The form a mesh engine keeps
    (with the pool sharded a KV head a shard the live form read a step 10.06
    ms against this one's 7.22 under tp=4: PERF.md 6, PR 57); one device
    serves :func:`_live_window_attention`, which reads what is live.

    The history is gathered from the paged pool ONCE per decode dispatch (the
    pool is immutable inside a dispatch): a per-step page gather is the
    dominant decode cost on TPU — XLA lowers big dynamic gathers to
    serialized page slices (~17 ms of a 17 ms step measured on v5e) — while
    attending a dense buffer is a pair of einsums. Fresh K/V live in the
    per-lane window buffer, flushed to pages once per dispatch by
    :func:`flush_window`.

    The two segments are NOT concatenated: at serving scale the concat
    materializes a history-sized copy per layer per step (~700 MB/step of
    pure HBM traffic at 32 lanes × 2k ctx on a 1B model — measured ~1.4
    ms/step of the ~7 ms step on v5e). Instead each segment computes an
    unnormalized softmax partial and the two are merged flash-decoding
    style, reading the history exactly once."""
    b, _, h_, d = q.shape
    kvh = c.num_kv_heads
    g = h_ // kvh
    smax = gk.shape[1]
    qg = q.reshape(b, kvh, g, d)

    # history partial
    scores = jnp.einsum(
        "bngd,bsnd->bngs", qg, gk, preferred_element_type=jnp.float32
    ) * (d ** -0.5)
    if soft_cap is not None:
        scores = jnp.tanh(scores / soft_cap) * soft_cap
    pool_valid = jnp.arange(smax)[None, :] < base[:, None]  # [B, Smax]
    scores = jnp.where(pool_valid[:, None, None, :], scores, -jnp.inf)
    m_p = jnp.maximum(scores.max(axis=-1), -1e30)  # [B, KVH, G]
    p = jnp.exp(scores - m_p[..., None])
    l_p = p.sum(axis=-1)
    num_p = jnp.einsum(
        "bngs,bsnd->bngd", p.astype(gv.dtype), gv
    ).astype(jnp.float32)

    # window partial + flash combine
    num_w, m_w, l_w = _window_only_attention(c, q, base, wk, wv, wslot, soft_cap)
    m_p = m_p.reshape(b, h_)
    l_p = l_p.reshape(b, h_)
    num_p = num_p.reshape(b, h_, d)
    m_t = jnp.maximum(m_p, m_w)
    a_p = jnp.exp(m_p - m_t)
    a_w = jnp.exp(m_w - m_t)
    denom = a_p * l_p + a_w * l_w
    num = num_p * a_p[..., None] + num_w * a_w[..., None]
    out = num / jnp.maximum(denom, 1e-30)[..., None]
    out = jnp.where((denom > 0.0)[..., None], out, 0.0)
    return out.reshape(b, 1, h_, d).astype(q.dtype)


def _pool_pages(kv_cache: KVCache) -> KVCache:
    """The pool's arrays as ``[L * N, bs, ...]`` views: page p of layer l is row
    ``l * N + p``. The step programs read the pool through ONE such row index;
    a slice by layer, or an index on the block axis alone
    (``pool[:, block_tables]``), makes the TPU compiler copy pool-sized buffers
    before it gathers. Take the views OUTSIDE a loop that gathers from them:
    reshaped inside, the pool rides the loop in a layout of the compiler's
    choosing, behind a whole-pool copy."""
    return {name: a.reshape(-1, *a.shape[2:]) for name, a in kv_cache.items()}


def gather_history(
    kv_cache: KVCache, block_tables: jax.Array, out_dtype: Any = None
) -> Tuple[jax.Array, jax.Array]:
    """Gather every lane's pages into dense [L, B, Smax, KVH, D] buffers —
    once per decode dispatch, so the in-scan attention never gathers. EVERY
    page of every table, whatever the lanes hold: 4,096 pages a layer at 32
    lanes x 2,048 positions, of which a serving batch's lanes read a fifth
    (PERF.md 6, PR 35). The mesh engines' form; one device gathers the live
    pairs alone (:func:`with_live_history`).

    An int8 pool dequantizes here (pages × their per-token scale tables into
    ``out_dtype``): the HBM read of the gather — the decode-roofline half
    that int8 KV halves — moves int8 bytes; the dequantized dense buffer is
    the transient working set the in-scan einsums already needed."""
    from dynamo_tpu.ops.attention import gather_pages

    l, n = kv_cache["k"].shape[:2]
    pages = _pool_pages(kv_cache)
    quantized = kv_cache_quantized(kv_cache)
    dt = out_dtype or jnp.bfloat16

    def layer_history(_, layer):
        rows = layer * n + block_tables
        hk, hv = gather_pages(pages["k"], rows), gather_pages(pages["v"], rows)
        if quantized:
            hk = dequantize_kv(hk, gather_pages(pages["k_scale"], rows), dt)
            hv = dequantize_kv(hv, gather_pages(pages["v_scale"], rows), dt)
        return None, (hk, hv)

    # a loop over layers, not one gather of every layer's pages: the pool views
    # ride the loop as they are, where the one gather would have the compiler
    # copy the whole pool into the layout the attention wants of the history
    return jax.lax.scan(layer_history, None, jnp.arange(l))[1]


def _window_partial(
    c: LlamaConfig,
    q: jax.Array,  # [B, 1, H, D] (rope applied)
    wk: jax.Array,  # [B, W, KVH, D]
    wv: jax.Array,
    mask: jax.Array,  # [B, W] bool: the window slots attended
    soft_cap: Optional[float],
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Flash partial over the decode window, by KV head as the products leave
    it: UNNORMALIZED numerator [B, KVH, G, D] f32, row max and denominator
    [B, KVH, G] f32."""
    b, _, h_, d = q.shape
    kvh = c.num_kv_heads
    qg = q.reshape(b, kvh, h_ // kvh, d)
    scores = jnp.einsum(
        "bngd,bwnd->bngw", qg, wk, preferred_element_type=jnp.float32
    ) * (d ** -0.5)
    if soft_cap is not None:
        scores = jnp.tanh(scores / soft_cap) * soft_cap
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    m = jnp.maximum(scores.max(axis=-1), -1e30)  # [B, KVH, G]
    p = jnp.exp(scores - m[..., None])
    l = p.sum(axis=-1)
    num = jnp.einsum("bngw,bwnd->bngd", p.astype(wv.dtype), wv).astype(jnp.float32)
    return num, m, l


def _window_only_attention(
    c: LlamaConfig,
    q: jax.Array,  # [B, 1, H, D] (rope applied)
    base: jax.Array,  # [B]
    wk: jax.Array,  # [B, W, KVH, D]
    wv: jax.Array,
    wslot: jax.Array,
    soft_cap: Optional[float],
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Flash-style attention over just the decode window: returns the
    UNNORMALIZED numerator [B, H, D] f32 plus row max / denominator
    ([B, H] f32), ready to merge with a pool-attention partial."""
    b, _, h_, d = q.shape
    mask = (jnp.arange(wk.shape[1])[None, :] <= wslot) & (base[:, None] >= 0)
    num, m, l = _window_partial(c, q, wk, wv, mask, soft_cap)
    return (
        num.reshape(b, h_, d),
        m.reshape(b, h_),
        l.reshape(b, h_),
    )


def _paged_window_attention(
    c: LlamaConfig,
    q: jax.Array,  # [B, 1, H, D] (rope applied)
    k_page: jax.Array,  # [NB, bs, KVH, D] this layer's pool (read-only)
    v_page: jax.Array,
    block_tables: jax.Array,  # [B, MB]
    base: jax.Array,  # [B] pool holds positions < base; -1 = padding lane
    wk: jax.Array,  # [B, W, KVH, D]
    wv: jax.Array,
    wslot: jax.Array,
    soft_cap: Optional[float],
    mesh,
    interpret: bool,
) -> jax.Array:
    """Kernel-tier decode-window attention: the Pallas flash kernel computes
    the pool partial (streaming pages HBM→VMEM, never materializing a
    gathered context) and returns its softmax stats; the in-hand window
    partial is merged with the standard flash-decoding combine. The pool
    stays read-only inside the dispatch — the kernel tier gets the same
    no-per-step-scatter decode structure as the jnp path."""
    from dynamo_tpu.ops.attention import paged_decode

    o_p, m_p, l_p = paged_decode(
        q[:, 0], k_page, v_page, block_tables, jnp.maximum(base, 0),
        mesh=mesh, interpret=interpret, return_stats=True,
    )
    num_w, m_w, l_w = _window_only_attention(c, q, base, wk, wv, wslot, soft_cap)

    m_p = jnp.maximum(m_p, -1e30)
    m_t = jnp.maximum(m_p, m_w)  # [B, H]
    a_p = jnp.exp(m_p - m_t) * l_p
    a_w = jnp.exp(m_w - m_t)
    denom = a_p + a_w * l_w
    num = (
        o_p.astype(jnp.float32) * a_p[..., None]
        + num_w * a_w[..., None]
    )
    out = num / jnp.maximum(denom, 1e-30)[..., None]
    valid = (denom > 0.0)[..., None]
    return jnp.where(valid, out, 0.0).astype(q.dtype)[:, None]  # [B, 1, H, D]


def forward_window(
    params: Params,
    config: LlamaConfig,
    tokens: jax.Array,  # [B] one token per lane
    positions: jax.Array,  # [B] absolute positions; < 0 = padding
    history,  # ("dense", hk, hv) [L,B,Smax,KVH,D] ×2 (gather_history), or
              # ("live", LiveHistory) (with_live_history), or
              # ("paged", kv_cache, block_tables, mesh, interpret)
    base: jax.Array,  # [B] history context length per lane (positions < base)
    window_k: jax.Array,  # [L, B, W, KVH, D]
    window_v: jax.Array,
    wslot: jax.Array,  # scalar: window slot for this step (= step index)
    *,
    soft_cap: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step over immutable history + window-buffered fresh K/V.

    Returns (logits [B, vocab] f32, window_k, window_v). The pool is
    READ-ONLY during a decode dispatch; the engine scans this over
    ``decode_steps`` and flushes the window into the pool once per dispatch
    (:func:`flush_window`) — keeping the per-step loop free of pool
    scatters, which cost more than the step's entire matmul work on TPU.

    History modes:
    - ``dense``: pages pre-gathered once per dispatch (:func:`gather_history`)
      so the in-scan attention is a pair of einsums (jnp tier — per-step page
      gathers lower to serialized page slices and dominate the step). Every
      table's full width, gathered and streamed: the mesh engines' form.
    - ``live``: the same tier reading what is live. The (lane, tile) pairs
      that hold history are gathered into the first slots of the buffer and
      every step attends those slots, at a width :func:`with_live_history`
      picked for the whole dispatch: a step streams a third of what ``dense``
      does at a serving batch's lengths. One device's form.
    - ``paged``: the Pallas flash kernel streams pages HBM→VMEM per step and
      returns softmax stats; the window partial is merged flash-decoding
      style (kernel tier — no dense materialization, wins at long context).
    """
    c = config
    mode = history[0]
    h = embed_lookup(params, tokens, c.dtype)[:, None]  # [B, 1, E]
    pos2 = positions[:, None]  # [B, 1]
    if mode == "dense":
        _, hist_k, hist_v = history
        xs_extra = (hist_k, hist_v)
    elif mode == "live":
        _, live = history
        xs_extra = (live.k, live.v)
        # the same in every layer of the step, so taken once
        in_window = (
            jnp.arange(window_k.shape[2])[None, :] <= wslot
        ) & (base[:, None] >= 0)  # [B, W]
    else:
        _, kv_cache, block_tables, mesh, interpret = history
        xs_extra = (kv_cache["k"], kv_cache["v"])

    def layer_body(carry, xs):
        (lp, hk, hv, wk, wv) = xs
        hidden = carry
        b = hidden.shape[0]

        q, k, v = project_qkv(lp, c, hidden, pos2)
        wk = jax.lax.dynamic_update_slice(wk, k, (0, wslot, 0, 0))
        wv = jax.lax.dynamic_update_slice(wv, v, (0, wslot, 0, 0))
        if mode == "dense":
            attn = _window_attention(
                c, q, hk, hv, base, wk, wv, wslot, soft_cap
            )
        elif mode == "live":
            attn = _live_window_attention(
                c, q, live, hk, hv, wk, wv, in_window, soft_cap
            )
        else:
            attn = _paged_window_attention(
                c, q, hk, hv, block_tables, base, wk, wv, wslot, soft_cap,
                mesh, interpret,
            )
        hidden = hidden + matw(attn.reshape(b, 1, c.q_dim), lp["wo"])
        return mlp_block(lp, c, hidden, pos2), (wk, wv)

    h, (new_wk, new_wv) = jax.lax.scan(
        layer_body, h,
        (params["layers"],) + xs_extra + (window_k, window_v),
    )
    h = rms_norm(h, params["final_norm"], c.rms_norm_eps)
    return lm_head(params, c, h)[:, 0], new_wk, new_wv


def _history_partial(
    c: LlamaConfig,
    q: jax.Array,  # [B, T, H, D] (rope applied)
    gk: jax.Array,  # [B, Smax, KVH, D] gathered pool pages
    gv: jax.Array,
    chunk_start: jax.Array,  # [B] history = positions < chunk_start
    q_positions: jax.Array,  # [B, T]; < 0 = padding
    scale: float,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Flash partial of chunk queries against pre-chunk paged history:
    (unnormalized numerator [B,T,H,D] f32, row max [B,H,T], denom [B,H,T])."""
    b, t, h, d = q.shape
    kvh = gk.shape[2]
    g = h // kvh
    smax = gk.shape[1]
    qg = q.reshape(b, t, kvh, g, d)
    scores = jnp.einsum(
        "btngd,bsnd->bngts", qg, gk, preferred_element_type=jnp.float32
    ) * scale  # [B, KVH, G, T, S]
    kv_pos = jnp.arange(smax)[None, :]
    mask = (kv_pos < chunk_start[:, None])[:, None, None, None, :]
    mask = mask & (q_positions >= 0)[:, None, None, :, None]
    scores = jnp.where(mask, scores, -jnp.inf)
    m = jnp.maximum(scores.max(axis=-1), -1e30)  # [B, KVH, G, T]
    p = jnp.exp(scores - m[..., None])
    l = p.sum(axis=-1)
    num = jnp.einsum("bngts,bsnd->btngd", p, gv.astype(jnp.float32))
    return (
        num.reshape(b, t, h, d),
        m.reshape(b, h, t),
        l.reshape(b, h, t),
    )


def _chunk_self_partial(
    c: LlamaConfig,
    q: jax.Array,  # [B, T, H, D] (rope applied)
    k: jax.Array,  # [B, T, KVH, D] this chunk's fresh keys (rope applied)
    v: jax.Array,
    positions: jax.Array,  # [B, T]; < 0 = padding
    scale: float,
    window: Optional[int] = None,  # a window layer's: a query sees this many positions, its own the last
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Flash partial of chunk queries against the chunk's OWN keys (causal
    by position): (numerator [B,T,H,D] f32, max [B,H,T], denom [B,H,T])."""
    b, t, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, t, kvh, g, d)
    scores = jnp.einsum(
        "btngd,bsnd->bngts", qg, k, preferred_element_type=jnp.float32
    ) * scale  # [B, KVH, G, T, T]
    causal = positions[:, None, :] <= positions[:, :, None]  # kv_pos <= q_pos
    if window is not None:
        causal &= positions[:, None, :] > positions[:, :, None] - window
    valid = (positions >= 0)[:, :, None] & (positions >= 0)[:, None, :]
    mask = (causal & valid)[:, None, None, :, :]
    scores = jnp.where(mask, scores, -jnp.inf)
    m = jnp.maximum(scores.max(axis=-1), -1e30)
    p = jnp.exp(scores - m[..., None])
    num = jnp.einsum("bngts,bsnd->btngd", p, v.astype(jnp.float32))
    return (
        num.reshape(b, t, h, d),
        m.reshape(b, h, t),
        p.sum(axis=-1).reshape(b, h, t),
    )


# positions a trip of forward_chunk's history loop reads (a whole number of
# pages: `history_tile` rounds it to the pool's block size). Settled on the
# v5e at Qwen2.5-1.5B's serving shapes: 128 reads as 256 does, 512 slower at
# every length (PERF.md 6, PR 30)
HISTORY_TILE = 256


def history_tile(block_size: int, table_blocks: int) -> int:
    """Positions in one tile of the chunk program's history loop: whole
    pages, and no more of them than a block table has."""
    return min(max(HISTORY_TILE // block_size, 1), table_blocks) * block_size


def history_tiles_full(block_size: int, table_blocks: int) -> int:
    """Tiles that cover a whole block table (the last may hang over it)."""
    return -(-table_blocks * block_size // history_tile(block_size, table_blocks))


# A lane may fill several rows of one chunk dispatch with successive pieces of
# its prompt (the engine's `_chunk_build` says when): attention through pages
# is all that carries a sequence's past here, and the fresh keys of a lane's
# earlier rows are in the program's hands (`chunk_sibling_partial`). Every
# module speaks for itself (`models.module_for`): one whose layers hand state
# from token to token beside the pages says this once it also hands that state
# from row to row, and keeps one row a lane until then
# (docs/kv_cache_manager.md).
LANE_TAKES_ROWS = True


def lane_first_positions(positions, lanes):
    """[B]: the lowest first position among the rows of each row's lane, which
    is where the lane's pool history ends in a dispatch that holds several
    pieces of its prompt (a row alone in its lane: its own first position; a
    padding row, < 0, its own). In arithmetic and without a ``where``: for a
    traced array and a numpy one alike, as :func:`chunk_history_tiles` is."""
    first = positions[:, 0]
    sibling = (lanes[:, None] == lanes[None, :]) & (first[None, :] >= 0)
    return (sibling * first[None, :] + ~sibling * first[:, None]).min(axis=1)


def chunk_history_tiles(positions, block_size: int, table_blocks: int, lanes=None):
    """Trips of :func:`forward_chunk`'s history loop for ``positions`` [B, C]:
    the tiles that hold the longest history of the dispatch. A lane's history
    is what lies below its first query (``positions[:, 0]``; a padding lane,
    < 0, has none; with ``lanes`` [B] given, below the first query of the
    lane's FIRST row: :func:`lane_first_positions`), and no lane's reaches
    past its block table. Written for a traced array (the program's own trip
    count) and for a numpy one (the host's count of what the program will
    read) alike."""
    tile = history_tile(block_size, table_blocks)
    first = positions[:, 0] if lanes is None else lane_first_positions(positions, lanes)
    longest = first.max().clip(0, table_blocks * block_size)
    return (longest + tile - 1) // tile


# -- the decode program's live history ----------------------------------------
#
# A lane's block table is as wide as ``max_model_len`` and what it holds is a
# fraction of that, so the decode program cuts every table into tiles of
# :func:`history_tile` positions and takes the (lane, tile) pairs that hold
# history FIRST, in slots 0, 1, 2, ... of its dense buffer, a lane's tiles in
# their order and the lanes in theirs. It gathers and attends the slots as far
# as the last pair that is live, rounded up to one of a few widths
# (:func:`history_widths`), and no further. The width is picked ONCE a
# dispatch, by a ``lax.switch`` around the gather and every step
# (:func:`with_live_history`), so that each branch is the dense program at a
# static width: one product a layer and step, not a loop of thin trips (a tile
# of one lane is 128 KB of keys: microseconds of loop overhead to stream a
# fraction of one), and no branch inside the layer loop, where the compiler
# answers one by moving the loop's state out of fast memory and back around
# it in every trip (PERF.md 6, PR 35).

def history_widths(slots: int) -> Tuple[int, ...]:
    """Widths the decode program compiles at, in (lane, tile) slots: half of
    them, and all. Each width is a copy of the gather and of the step loop in
    every decode program, traced and lowered at start-up, where ``setup_s``
    is judged (the second width costs it 1.8 s), and a faster program spends
    the tracer's budget of events: with a quarter as a third width the cell's
    first token came 10 % sooner again, set-up another 0.5 s later and the
    trace export within 25 s of its limit (PERF.md 6, PR 35), which is what
    keeps them two."""
    return tuple(sorted({max(slots // 2, 1), slots}))


def decode_history_tiles(base, block_size: int, table_blocks: int):
    """(lane, tile) slots the decode program gathers and attends for ``base``
    [B] (a lane's history is the positions < base; a lane that does not
    decode has -1 and none): the narrowest of :func:`history_widths` that
    holds every pair with history in it. Written for a traced array (the
    program's own width) and for a numpy one (the host's count of what the
    program will read) alike."""
    tile = history_tile(block_size, table_blocks)
    widths = history_widths(
        base.shape[0] * history_tiles_full(block_size, table_blocks)
    )
    live = ((base.clip(0, table_blocks * block_size) + tile - 1) // tile).sum()
    return widths[0] + sum(
        (wider - width) * (live > width)
        for width, wider in zip(widths, widths[1:])
    )


class LiveHistory(NamedTuple):
    """The first n slots of a decode dispatch's history: slot s holds one tile
    of one lane. (slot, head) is ONE axis of the buffers and of the products
    that read them: split after a slice, the compiler copies the slice out
    before it reads it; as five axes, it relays both buffers whole between
    the loop that writes them and the loop that reads them."""

    k: jax.Array  # [L, n * KVH, tile, D]
    v: jax.Array
    own: jax.Array  # [B, n] bool: slot s is one of lane b's tiles
    valid: jax.Array  # [n * KVH, tile] bool: the position is history


def with_live_history(
    kv_cache: KVCache,
    block_tables: jax.Array,  # [B, MB]
    base: jax.Array,  # [B] history = positions < base; -1 = padding lane
    steps,  # history -> the dispatch's steps over it (any pytree)
    out_dtype: Any = None, carried=None,  # or steps(history)(*carried) -> carried': the last lines
):
    """``steps(("live", LiveHistory))`` over the (lane, tile) pairs of the
    pool that hold history: gathered once, at the width
    :func:`decode_history_tiles` gives, which is the width every step
    attends.

    The dense buffer of :func:`gather_history` holds every lane's whole
    table; a page gather costs by the page, not the byte (PERF.md 5), the
    steps stream the buffer in every layer, and four fifths of a serving
    batch's tables hold nothing a lane will read. Here a branch of width n
    gathers the pages of n slots a layer (one gather, as the full-width form
    has) and its steps read those. An int8 pool dequantizes in the gather,
    scales gathered for the same pairs."""
    from dynamo_tpu.ops.attention import gather_pages

    l, n_blocks, bs, kvh, d = kv_cache["k"].shape
    b, table_blocks = block_tables.shape
    tile = history_tile(bs, table_blocks)
    tile_blocks = tile // bs
    tiles = history_tiles_full(bs, table_blocks)
    slots = b * tiles
    widths = history_widths(slots)
    quantized = kv_cache_quantized(kv_cache)
    dt = out_dtype or jnp.bfloat16

    held = jnp.clip(base, 0, table_blocks * bs)  # [B] positions in the pool
    live = jnp.arange(tiles)[None, :] * tile < held[:, None]  # [B, tiles]
    # slot -> pair: the live pairs first, in their own (lane, tile) order
    order = jnp.argsort(~live.reshape(-1), stable=True)
    lane, first = order // tiles, order % tiles * tile
    length = jnp.clip(held[lane] - first, 0, tile)  # [P] of a slot, history
    # whole tiles: the columns added point at page 0 and lie past every history
    tables = jnp.pad(
        block_tables, ((0, 0), (0, tiles * tile_blocks - table_blocks))
    ).reshape(slots, tile_blocks)[order]
    pages = _pool_pages(kv_cache)

    def at_width(n):
        def layer_history(_, layer):
            rows = layer * n_blocks + tables[:n]
            out = []
            for name in ("k", "v"):
                got = gather_pages(pages[name], rows)  # [n, tile, KVH, D]
                if quantized:
                    got = dequantize_kv(
                        got, gather_pages(pages[name + "_scale"], rows), dt
                    )
                out.append(
                    got.astype(dt).transpose(0, 2, 1, 3).reshape(n * kvh, tile, d)
                )
            return None, tuple(out)

        # a loop over layers with the pool views taken outside it, as
        # gather_history's, and for its reasons
        hk, hv = jax.lax.scan(layer_history, None, jnp.arange(l))[1]
        return steps(("live", LiveHistory(
            k=hk, v=hv,
            own=lane[None, :n] == jnp.arange(b)[:, None],
            valid=jnp.arange(tile)[None, :] < jnp.repeat(length[:n], kvh)[:, None],
        )))

    read = decode_history_tiles(base, bs, table_blocks)
    return jax.lax.switch(
        sum((read > n).astype(jnp.int32) for n in widths[:-1]),
        [partial(at_width, n) for n in widths],
    ) if carried is None else _each_width_in_turn(read, widths, at_width, carried)


def _each_width_in_turn(read, widths, at_width, carried):
    """``with_live_history`` for steps that UPDATE what they are handed
    (``carried``, a tuple that holds per-slot state; ``steps(history)`` gives
    the function that takes it and gives it back advanced): one conditional a
    width in turn, a width that is not the dispatch's handing ``carried`` on
    as it is. The chip's compiler orders a conditional's branches, the first
    before the second, to let them share buffers; so in every branch but the
    last an operand of the conditional is live past the branch, and an update
    of it in place is made on a copy: of a run's whole state, a layer and step
    (PERF.md 6, PR 44). Here every width's steps are the last branch of their
    own conditional. (Written under the function and not in it: a Pallas
    kernel's cache key holds its callers' lines and columns, and two models'
    kernels are called from the lines above.)"""
    for n in widths:  # ``read`` is one of them
        carried = jax.lax.cond(
            read == n, lambda *c, n=n: at_width(n)(*c), lambda *c: c, *carried)
    return carried


def _live_window_attention(
    c: LlamaConfig,
    q: jax.Array,  # [B, 1, H, D] (rope applied)
    hist: LiveHistory,
    gk: jax.Array,  # [n * KVH, tile, D] this layer of hist.k
    gv: jax.Array,
    wk: jax.Array,  # [B, W, KVH, D] window K (rope applied)
    wv: jax.Array,
    in_window: jax.Array,  # [B, W] bool: the window slots this step attends
    soft_cap: Optional[float],
) -> jax.Array:
    """:func:`_window_attention` over the slots of a :class:`LiveHistory`:
    the same keys attended, as far as they are live.

    Every slot scores its lane's query against its tile's keys; a lane's
    softmax runs over all of its slots (one maximum a lane, as the dense form
    has; a slot past the live ones weighs nothing), and a lane's numerator is
    the sum of its slots'. Queries go out to the slots and numerators come
    back to the lanes as products with ``own``: a gather of some hundred rows
    a layer and step costs more than the attention it feeds. The window
    partial and the merge with it are the dense form's."""
    b, _, h_, d = q.shape
    kvh = c.num_kv_heads
    g = h_ // kvh
    n = hist.own.shape[1]
    own4 = hist.own[:, :, None, None]
    qg = q.reshape(b, kvh, g, d)

    # exact in any dtype: a slot has one owner
    qs = jnp.einsum(
        "bs,bngd->sngd", hist.own.astype(q.dtype), qg
    ).reshape(n * kvh, g, d)
    scores = jnp.einsum(
        "xgd,xpd->xgp", qs, gk, preferred_element_type=jnp.float32
    ) * (d ** -0.5)
    if soft_cap is not None:
        scores = jnp.tanh(scores / soft_cap) * soft_cap
    scores = jnp.where(hist.valid[:, None, :], scores, -jnp.inf)
    m_s = scores.max(axis=-1).reshape(n, kvh, g)
    m_p = jnp.maximum(
        jnp.where(own4, m_s[None], -jnp.inf).max(axis=1), -1e30
    )  # [B, KVH, G]
    m_own = jnp.where(own4, m_p[:, None], 0.0).sum(axis=0)  # [n, KVH, G]
    p = jnp.exp(scores - m_own.reshape(n * kvh, g, 1))
    l_s = p.sum(axis=-1).reshape(n, kvh, g)
    l_p = jnp.where(own4, l_s[None], 0.0).sum(axis=1)
    num_s = jnp.einsum(
        "xgp,xpd->xgd", p.astype(gv.dtype), gv,
        preferred_element_type=jnp.float32,
    ).reshape(n, kvh, g, d)
    num_p = jnp.einsum(
        "bs,sngd->bngd", hist.own.astype(jnp.float32), num_s,
        precision=jax.lax.Precision.HIGHEST,
    )

    # window partial + flash combine: _window_attention's mathematics on
    # [B, KVH, G] as the products leave it. (Split into heads, as there, every
    # one of the six statistics is a copy and a reshape of its own a layer
    # and step, and the tracer has a budget of operations: PERF.md 7.)
    num_w, m_w, l_w = _window_partial(c, q, wk, wv, in_window, soft_cap)
    m_t = jnp.maximum(m_p, m_w)
    a_p = jnp.exp(m_p - m_t)
    a_w = jnp.exp(m_w - m_t)
    denom = a_p * l_p + a_w * l_w
    num = num_p * a_p[..., None] + num_w * a_w[..., None]
    out = num / jnp.maximum(denom, 1e-30)[..., None]
    out = jnp.where((denom > 0.0)[..., None], out, 0.0)
    return out.reshape(b, 1, h_, d).astype(q.dtype)


def _merge_partials(p, q):
    """Flash merge of two attention partials over disjoint keys, each
    (numerator [B,T,H,D] f32, row max [B,H,T], denominator [B,H,T])."""
    (num_p, m_p, l_p), (num_q, m_q, l_q) = p, q
    m = jnp.maximum(m_p, m_q)
    a_p = jnp.exp(m_p - m)
    a_q = jnp.exp(m_q - m)
    num = (
        num_p * a_p.transpose(0, 2, 1)[..., None]
        + num_q * a_q.transpose(0, 2, 1)[..., None]
    )
    return num, m, a_p * l_p + a_q * l_q


def chunk_history_partial(
    c: LlamaConfig,
    q: jax.Array,  # [B, T, H, D] one layer's chunk queries
    pages: KVCache,  # the pool as `_pool_pages` views
    rows: jax.Array,  # [B, tiles * tile_blocks] the layer's rows of those views
    history_len: jax.Array,  # [B] positions a lane has in the pool
    n_tiles,  # trips: `chunk_history_tiles`
    positions: jax.Array,  # [B, T]; < 0 = padding
    scale: float,
    tile_blocks: int,
    block_size: int,
    dtype: Any,  # what an int8 pool's pages dequantize into
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Flash partial of one layer's chunk queries against the history in the
    pool, a tile of :func:`history_tile` positions a trip and ``n_tiles``
    trips, each tile's partial folded into a running one by the flash merge.
    No trip leaves the empty partial, which the merge with the chunk's own
    turns into that alone. The layer loop of :func:`forward_chunk` calls it,
    and any module whose attention layers read the same page layout."""
    from dynamo_tpu.ops.attention import gather_pages

    b, t = positions.shape
    quantized = kv_cache_quantized(pages)

    def tile(i, acc):
        """Fold tile ``i``'s partial into ``acc``: its pages hold
        positions ``i * tile`` onwards."""
        cols = jax.lax.dynamic_slice_in_dim(
            rows, i * tile_blocks, tile_blocks, axis=1
        )
        gk = gather_pages(pages["k"], cols)
        gv = gather_pages(pages["v"], cols)
        if quantized:
            # dequant on the GATHERED lanes only (O(context), never
            # O(pool)); gather_pages is trailing-dim agnostic so the
            # [L * N, bs] scale tables gather like [B, S] vectors
            gks = gather_pages(pages["k_scale"], cols)
            gvs = gather_pages(pages["v_scale"], cols)
            gk = dequantize_kv(gk, gks, dtype)
            gv = dequantize_kv(gv, gvs, dtype)
        start = i * tile_blocks * block_size
        return _merge_partials(acc, _history_partial(
            c, q, gk, gv, history_len - start, positions, scale
        ))

    empty = (
        jnp.zeros((b, t, c.num_heads, c.head_dim), jnp.float32),
        jnp.full((b, c.num_heads, t), -1e30, jnp.float32),
        jnp.zeros((b, c.num_heads, t), jnp.float32),
    )
    return jax.lax.fori_loop(0, n_tiles, tile, empty)


def chunk_sibling_partial(
    c: LlamaConfig,
    q: jax.Array,  # [B, T, H, D] one layer's chunk queries (rope applied)
    k: jax.Array,  # [B, T, KVH, D] the chunk's fresh keys (rope applied)
    v: jax.Array,
    positions: jax.Array,  # [B, T]; < 0 = padding
    lanes: jax.Array,  # [B] the lane of each row
    n_back,  # trips: `sibling_rows_back`
    scale: float,
    acc: Tuple[jax.Array, jax.Array, jax.Array],
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``acc`` with the flash partial of each row's queries against the fresh
    keys of the EARLIER rows of its lane folded in: a lane that fills several
    rows of a dispatch with successive pieces of its prompt attends, from a
    later piece, keys that are in hand and not yet in the pool. Trip ``i``
    meets every row with the row ``i + 1`` above it, where that is a row of
    the same lane, as the row's own partial meets it with itself (causal by
    position, so an earlier piece is attended whole); ``n_back`` trips reach
    the farthest pair of the dispatch, and none leaves ``acc`` as it was.
    Scores are ``[B, T, T]`` a trip, never ``[B, B]`` row pairs at once."""
    b, t, h, d = q.shape
    kvh = k.shape[2]
    qg = q.reshape(b, t, kvh, h // kvh, d)
    rows = jnp.arange(b)
    # rows r - back .. r - back + B - 1 of a doubled array: row r meets r - back
    k2, v2 = jnp.concatenate([k, k]), jnp.concatenate([v, v])
    pos2, lanes2 = jnp.concatenate([positions, positions]), jnp.concatenate([lanes, lanes])

    def above(x2, back):
        return jax.lax.dynamic_slice_in_dim(x2, b - back, b, axis=0)

    def trip(i, acc):
        back = i + 1
        kv_pos = above(pos2, back)  # [B, T]
        met = (above(lanes2, back) == lanes) & (rows >= back)  # [B]
        mask = (
            met[:, None, None] & (kv_pos >= 0)[:, None, :]
            & (kv_pos[:, None, :] <= positions[:, :, None])
        )[:, None, None, :, :]
        scores = jnp.einsum(
            "btngd,bsnd->bngts", qg, above(k2, back),
            preferred_element_type=jnp.float32,
        ) * scale  # [B, KVH, G, T, T]
        scores = jnp.where(mask, scores, -jnp.inf)
        m = jnp.maximum(scores.max(axis=-1), -1e30)
        p = jnp.exp(scores - m[..., None])
        num = jnp.einsum(
            "bngts,bsnd->btngd", p, above(v2, back).astype(jnp.float32)
        )
        return _merge_partials(acc, (
            num.reshape(b, t, h, d),
            m.reshape(b, h, t),
            p.sum(axis=-1).reshape(b, h, t),
        ))

    return jax.lax.fori_loop(0, n_back, trip, acc)


def sibling_rows_back(positions, lanes):
    """Trips of :func:`chunk_sibling_partial` for a dispatch: the farthest
    that a row lies below an earlier row of its lane (0: every lane has one
    row; the engine lays a lane's pieces in consecutive rows, so the rows a
    lane took less one)."""
    real = positions[:, 0] >= 0
    rows = jnp.arange(lanes.shape[0])
    pair = (lanes[:, None] == lanes[None, :]) & real[:, None] & real[None, :]
    return jnp.where(pair, rows[:, None] - rows[None, :], 0).max()


class ChunkLayout(NamedTuple):
    """A chunk dispatch in which a lane may fill several rows (under the full
    width), as a chunk program that takes its rows some at a time tells each
    group of them (``models/lfm2.py``, ``models/qwen3_next.py``)."""

    positions: jax.Array  # [N, C] of every row of the dispatch
    lanes: jax.Array  # [N]
    takes: jax.Array  # [N] a real row whose lane is that of the real row above it
    starts: jax.Array  # [N] where a row's pool history ends: `lane_first_positions`
    n_back: jax.Array  # the sibling loop's trips: `sibling_rows_back`

    def rows(self, at, n):
        """Of rows ``at`` .. ``at + n - 1``: (where each one's pool history
        ends, ``[n, 1]`` as a column of positions; which take, ``[n]``)."""
        return (jax.lax.dynamic_slice_in_dim(self.starts, at, n)[:, None],
                jax.lax.dynamic_slice_in_dim(self.takes, at, n))


def chunk_layout(positions, lanes, slots: int) -> ChunkLayout:
    """The layout of a dispatch of ``positions`` ``[N, C]`` whose rows' lanes
    are ``lanes`` ``[N]`` (``slots`` and above: a padding row)."""
    live = (lanes < slots) & (positions[:, 0] >= 0)
    return ChunkLayout(
        positions=positions, lanes=lanes,
        takes=jnp.concatenate([
            jnp.zeros((1,), bool), (lanes[1:] == lanes[:-1]) & live[1:] & live[:-1]]),
        starts=lane_first_positions(positions, lanes),
        n_back=sibling_rows_back(positions, lanes))


def chunk_rows_above_partial(
    c: LlamaConfig,
    q: jax.Array,  # [B, T, H, D] the queries of rows `at` .. `at + B - 1` of the dispatch
    k: jax.Array,  # [N, T, KVH, D] the dispatch's fresh keys; rows past `at + B` are not read
    v: jax.Array,
    positions: jax.Array,  # [N, T] of every row of the dispatch; < 0 = padding
    lanes: jax.Array,  # [N] the lane of each row of the dispatch
    at,  # the dispatch's row that is the first of the B
    n_back,  # trips: `sibling_rows_back` of the dispatch
    scale: float,
    acc: Tuple[jax.Array, jax.Array, jax.Array],
    window: Optional[int] = None,  # as `_chunk_self_partial`'s
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`chunk_sibling_partial` for a GROUP of a dispatch's rows, for a
    chunk program that takes its rows some at a time: ``acc`` with the flash
    partial of each of the B rows' queries against the fresh keys of the
    EARLIER rows of its lane folded in, whether those lie in the group or
    above it (a lane's rows may straddle two groups). Trip ``i`` meets row
    ``at + r`` with row ``at + r - (i + 1)`` of the dispatch, where there is
    one and it is a row of the same lane; the same ``[B, T, T]`` scores a
    trip, and with ``at = 0`` and ``N = B`` the same pairs."""
    b, t, h, d = q.shape
    kvh = k.shape[2]
    qg = q.reshape(b, t, kvh, h // kvh, d)
    rows = at + jnp.arange(b)
    q_positions = jax.lax.dynamic_slice_in_dim(positions, at, b, axis=0)
    q_lanes = jax.lax.dynamic_slice_in_dim(lanes, at, b, axis=0)

    # B rows in front for the rows that would lie above row 0 (`rows >= back`
    # says they meet nothing): row j of the dispatch is row B + j of these
    k2, v2, pos2, lanes2 = (
        jnp.concatenate([jnp.zeros((b, *x.shape[1:]), x.dtype), x])
        for x in (k, v, positions, lanes)
    )

    def above(x2, back):
        return jax.lax.dynamic_slice_in_dim(x2, at + b - back, b, axis=0)

    def trip(i, acc):
        back = i + 1
        kv_pos = above(pos2, back)  # [B, T]
        met = (above(lanes2, back) == q_lanes) & (rows >= back)  # [B]
        mask = (
            met[:, None, None] & (kv_pos >= 0)[:, None, :]
            & (kv_pos[:, None, :] <= q_positions[:, :, None])
        )
        if window is not None:
            mask &= kv_pos[:, None, :] > q_positions[:, :, None] - window
        mask = mask[:, None, None, :, :]
        scores = jnp.einsum(
            "btngd,bsnd->bngts", qg, above(k2, back),
            preferred_element_type=jnp.float32,
        ) * scale  # [B, KVH, G, T, T]
        scores = jnp.where(mask, scores, -jnp.inf)
        m = jnp.maximum(scores.max(axis=-1), -1e30)
        p = jnp.exp(scores - m[..., None])
        num = jnp.einsum(
            "bngts,bsnd->btngd", p, above(v2, back).astype(jnp.float32)
        )
        return _merge_partials(acc, (
            num.reshape(b, t, h, d),
            m.reshape(b, h, t),
            p.sum(axis=-1).reshape(b, h, t),
        ))

    return jax.lax.fori_loop(0, n_back, trip, acc)


def forward_chunk(
    params: Params,
    config: LlamaConfig,
    tokens: jax.Array,  # [B, C] int32
    positions: jax.Array,  # [B, C]; < 0 = padding
    kv_cache: KVCache,
    block_tables: jax.Array,  # [B, MB]
    *,
    hidden_only: bool = False,
    with_history: bool = True,
    lanes: Optional[jax.Array] = None,  # [B] the rows' lanes: a lane may take several
) -> Tuple[jax.Array, KVCache]:
    """Prefill-chunk forward with the history/fresh attention split — the
    same contract as :func:`forward`, restructured for the TPU scheduler.

    :func:`forward` scatters the chunk's K/V into pages and then gathers
    them back for attention, chaining scatter → gather → einsum on every
    layer's critical path. Here attention = flash-merge of a pool-history
    partial (pages < each lane's chunk start — by construction everything
    already flushed) with an in-chunk causal partial over the fresh K/V in
    hand. The pool is READ-ONLY inside the layer loop and never sliced by
    layer (the history gather indexes the whole pool, layer included); the
    layers' fresh K/V leave the loop stacked and ONE in-place scatter writes
    them after it (:func:`write_kv_to_pool`), so the dispatch costs what its
    lanes touch, whatever the pool's size.

    The history is read a tile of :func:`history_tile` positions at a time,
    and only as many tiles as the longest history of THIS dispatch fills
    (:func:`chunk_history_tiles`, a traced scalar): the block tables are as
    wide as ``max_model_len``, the scores over them f32, and what the live
    sequences hold is a fraction of that. Each tile's partial folds into a
    running one by the flash merge; no trip leaves the empty partial, which
    the merge with the chunk's own turns into that alone.

    ``with_history=False`` compiles out the pool gather + history partial
    entirely — the caller guarantees every lane starts at position 0 (a
    fresh admission wave's first chunk, THE TTFT-critical dispatch).

    ``lanes`` given, a lane may fill SEVERAL rows with successive pieces of
    its prompt, an earlier piece in an earlier row: a row's pool history then
    ends where the lane's first row of the dispatch starts
    (:func:`lane_first_positions`), and one more partial a layer attends the
    fresh keys of the lane's earlier rows (:func:`chunk_sibling_partial`).
    The pool is still written once, after the loop, by position and table:
    two rows of a lane write different positions of the same pages. ``None``
    is the program as it was, one row a lane, to the character."""
    from dynamo_tpu.ops.attention import write_kv_to_pool

    c = config
    scale = c.head_dim ** -0.5
    h = embed_lookup(params, tokens, c.dtype)  # [B, C, E]
    b, t = positions.shape
    quantized = kv_cache_quantized(kv_cache)
    num_blocks, block_size = kv_cache["k"].shape[1:3]
    table_blocks = block_tables.shape[1]
    pages = _pool_pages(kv_cache)
    tile_blocks = history_tile(block_size, table_blocks) // block_size
    # what a lane has in the pool: below its first query, within its table
    # (with lanes: below the first query of the lane's FIRST row; the rows
    # between are siblings, their keys in hand)
    first = positions[:, 0] if lanes is None else lane_first_positions(positions, lanes)
    history_len = jnp.clip(first, 0, table_blocks * block_size)  # [B]
    n_tiles = chunk_history_tiles(positions, block_size, table_blocks, lanes)
    if lanes is not None:
        n_back = sibling_rows_back(positions, lanes)
    # whole tiles: the columns added point at page 0 and lie past every history
    max_tiles = history_tiles_full(block_size, table_blocks)
    tables = jnp.pad(
        block_tables, ((0, 0), (0, max_tiles * tile_blocks - table_blocks))
    )

    def layer_body(hidden, xs):
        lp, layer = xs

        q, k, v = project_qkv(lp, c, hidden, positions)
        part = _chunk_self_partial(c, q, k, v, positions, scale)
        if with_history:
            hist = chunk_history_partial(
                c, q, pages, layer * num_blocks + tables, history_len,
                n_tiles, positions, scale, tile_blocks, block_size,
                hidden.dtype,
            )
            part = _merge_partials(hist, part)
        if lanes is not None:
            part = chunk_sibling_partial(
                c, q, k, v, positions, lanes, n_back, scale, part
            )
        num, _, den = part
        attn = jnp.where(
            (den > 0.0).transpose(0, 2, 1)[..., None],
            num / jnp.maximum(den, 1e-30).transpose(0, 2, 1)[..., None],
            0.0,
        ).astype(hidden.dtype)

        hidden = hidden + matw(attn.reshape(b, t, c.q_dim), lp["wo"])
        # the fresh K/V quantize per token on their way to the pages; the
        # in-chunk causal partial above attended the exact values in hand
        fresh = quantize_kv(k, v) if quantized else (k, v)
        return mlp_block(lp, c, hidden, positions), fresh

    h, fresh = jax.lax.scan(
        layer_body, h, (params["layers"], jnp.arange(c.num_layers))
    )
    cache = {
        name: write_kv_to_pool(kv_cache[name], new, positions, block_tables)
        for name, new in zip(("k", "v", "k_scale", "v_scale"), fresh)
    }
    h = rms_norm(h, params["final_norm"], c.rms_norm_eps)
    if hidden_only:
        return h, cache
    return lm_head(params, c, h), cache


def forward_chunk_sp(
    params: Params,
    config: LlamaConfig,
    tokens: jax.Array,  # [B, C] int32
    positions: jax.Array,  # [B, C]; < 0 = padding
    kv_cache: KVCache,
    block_tables: jax.Array,  # [B, MB]
    mesh,
    *,
    hidden_only: bool = False,
) -> Tuple[jax.Array, KVCache]:
    """Sequence-parallel prefill chunk: same contract as :func:`forward`.

    The chunk's sequence axis is sharded over the ``sp`` mesh axis; within-
    chunk causal attention runs as ring attention (K/V shards rotate over
    ICI, parallel/ring_attention.py) and pre-chunk history is a flash
    partial against the paged pool, merged flash-decoding style. This is
    what makes sp a SERVING axis rather than a tested-but-unused module:
    long prompts prefill with their activations and attention split across
    the ring. (The reference has no sequence parallelism at all —
    SURVEY.md §2.12 — this is a TPU-native extension.)
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dynamo_tpu.ops.attention import gather_pages, write_kv_to_pages
    from dynamo_tpu.parallel.mesh import AXIS_SP
    from dynamo_tpu.parallel.ring_attention import ring_attention

    c = config
    d = c.head_dim
    scale = d ** -0.5
    h = embed_lookup(params, tokens, c.dtype)  # [B, C, E]
    h = jax.lax.with_sharding_constraint(
        h, NamedSharding(mesh, P(None, AXIS_SP, None))
    )
    chunk_start = jnp.where(positions[:, 0] >= 0, positions[:, 0], 0)  # [B]

    def layer_body(carry, xs):
        lp, k_page, v_page = xs
        hidden = carry
        b, t = positions.shape

        q, k, v = project_qkv(lp, c, hidden, positions)
        k_page, v_page = write_kv_to_pages(
            k_page, v_page, k, v, positions, block_tables
        )

        # in-chunk causal part: ring over sp (positions drive causality)
        num_r, m_r, l_r = ring_attention(
            q, k, v, positions, positions, mesh, scale=scale,
            return_stats=True,
        )
        # pre-chunk history from the pool (masked to < chunk_start, so the
        # scatter above can never double-count the chunk's own tokens)
        gk = gather_pages(k_page, block_tables)
        gv = gather_pages(v_page, block_tables)
        num_h, m_h, l_h = _history_partial(
            c, q, gk, gv, chunk_start, positions, scale
        )

        m_t = jnp.maximum(m_r, m_h)  # [B, H, T]
        a_r = jnp.exp(m_r - m_t)
        a_h = jnp.exp(m_h - m_t)
        den = a_r * l_r + a_h * l_h
        num = (
            num_r.astype(jnp.float32) * a_r.transpose(0, 2, 1)[..., None]
            + num_h * a_h.transpose(0, 2, 1)[..., None]
        )
        attn = jnp.where(
            (den > 0.0).transpose(0, 2, 1)[..., None],
            num / jnp.maximum(den, 1e-30).transpose(0, 2, 1)[..., None],
            0.0,
        ).astype(hidden.dtype)

        hidden = hidden + matw(attn.reshape(b, t, c.q_dim), lp["wo"])
        return mlp_block(lp, c, hidden, positions), (k_page, v_page)

    h, (new_k, new_v) = jax.lax.scan(
        layer_body, h, (params["layers"], kv_cache["k"], kv_cache["v"])
    )
    h = rms_norm(h, params["final_norm"], c.rms_norm_eps)
    cache = {"k": new_k, "v": new_v}
    if hidden_only:
        return h, cache
    return lm_head(params, c, h), cache


def flush_window(
    kv_cache: KVCache,
    block_tables: jax.Array,  # [B, MB]
    base: jax.Array,  # [B] first position written by this dispatch
    window_k: jax.Array,  # [L, B, W, KVH, D]
    window_v: jax.Array,
    max_pos: int,
) -> KVCache:
    """Scatter a decode dispatch's window buffer into the paged pool — ONE
    in-place scatter per pool array per dispatch, every layer at once
    (:func:`write_kv_to_pool`). Lanes that were padding (base < 0) or ran
    past ``max_pos`` mid-dispatch get position −1, which the scatter drops.
    An int8 pool quantizes the window once (per-token scales); values and
    scale tables take the same scatter."""
    from dynamo_tpu.ops.attention import write_kv_to_pool

    w = window_k.shape[2]
    fpos = base[:, None] + jnp.arange(w)[None, :]  # [B, W]
    valid = (base[:, None] >= 0) & (fpos <= max_pos)
    fpos = jnp.where(valid, fpos, -1)
    fresh = (window_k, window_v)
    if kv_cache_quantized(kv_cache):
        fresh = quantize_kv(window_k, window_v)
    return {
        name: write_kv_to_pool(kv_cache[name], new, fpos, block_tables)
        for name, new in zip(("k", "v", "k_scale", "v_scale"), fresh)
    }


def forward(
    params: Params,
    config: LlamaConfig,
    tokens: jax.Array,  # [B, T] int32; padding rows/cols use position < 0
    positions: jax.Array,  # [B, T] absolute positions; < 0 = padding
    kv_cache: KVCache,  # paged pool, updated functionally
    block_tables: jax.Array,  # [B, max_blocks]
    *,
    soft_cap: Optional[float] = None,
    use_pallas: Optional[bool] = None,  # None = auto (DYN_TPU_ATTENTION + platform)
    mesh=None,  # set when the cache is sharded: kernels run under shard_map
    hidden_only: bool = False,  # skip the LM head, return [B, T, E] hidden
) -> Tuple[jax.Array, KVCache]:
    """One forward step (prefill if T>1, decode if T==1).

    Writes new K/V into the paged cache, attends through block tables, returns
    (logits [B, T, vocab] float32, updated cache). Single code path for
    prefill/decode/prefix-hit keeps everything static-shaped under jit.

    ``hidden_only`` returns the final-norm hidden states instead of logits so
    callers that sample at one position per row (the engine's prefill chunk)
    can gather first and apply :func:`lm_head` to [B, E] — skipping T-1 of T
    LM-head columns and the [B, T, vocab] float32 materialization.
    """
    c = config
    h = embed_lookup(params, tokens, c.dtype)  # [B, T, E]

    def layer_body(carry, xs):
        lp, k_page, v_page = xs  # layer params + this layer's page pool
        hidden, k_page, v_page = decoder_layer(
            lp, c, carry, positions, k_page, v_page, block_tables,
            soft_cap=soft_cap, use_pallas=use_pallas, mesh=mesh,
        )
        return hidden, (k_page, v_page)

    h, (new_k, new_v) = jax.lax.scan(
        layer_body, h, (params["layers"], kv_cache["k"], kv_cache["v"])
    )

    h = rms_norm(h, params["final_norm"], c.rms_norm_eps)
    cache = {"k": new_k, "v": new_v}
    if hidden_only:
        return h, cache
    return lm_head(params, c, h), cache
