"""Paged-KV attention and KV page scatter.

The KV cache is a pool of fixed-size pages ("blocks") in HBM:
``[num_blocks, block_size, num_kv_heads, head_dim]``. A request owns a
*block table* — the list of physical page ids backing its logical context —
so sequences grow without reallocation and prefix-shared pages can be reused
by many requests (the TPU equivalent of the reference's paged/prefix KV,
SURVEY.md §2.10).

All shapes are static under jit: block tables are padded to a fixed
max-blocks-per-seq, batch is padded to fixed slot count, masks do the rest.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.runtime.envknobs import env_str


@lru_cache(maxsize=1)
def _platform_is_tpu() -> bool:
    # no exception handling: a backend that fails to initialise must fail
    # the caller, not silently select the dense tier
    return jax.devices()[0].platform == "tpu"


def _select_pallas(head_dim: int) -> bool:
    """One fresh-read policy for the T==1 attention implementation of the
    plain ``forward`` path.

    DYN_TPU_ATTENTION=pallas|jnp forces the choice; auto uses the Pallas
    decode kernel on TPU whenever the head dim is lane-aligned (D % 128 ==
    0 — Mosaic DMA slices must align to the 128-lane tiling). Which kernel
    schedule runs is :func:`decode_schedule`'s choice. Env vars are read at
    trace time, so tests and operators can flip them live. Callers with a
    cache sharded over a mesh pass ``mesh=`` so the kernel runs under
    shard_map (Mosaic kernels have no GSPMD partitioning rule; shard_map
    sidesteps auto-partitioning).
    """
    mode = env_str("DYN_TPU_ATTENTION", "auto")
    if mode == "pallas":
        return True
    if mode == "jnp":
        return False
    return _platform_is_tpu() and head_dim % 128 == 0


def _v2_supported(head_dim: int, num_kv_heads: int, itemsize: int = 2) -> bool:
    """Single home for the Mosaic DMA-slice alignment constraints of the
    per-lane v2 schedule, as the v5e compiler states them (asked ahead of
    time, tests/test_aot_compile_tpu.py): its page slices
    ``[bs, KVH, D]`` must align to the 128-lane tiling in D, and — for
    packed (sub-32-bit) dtypes — to the sublane tiling in KVH, which is
    min(8, next_pow2(KVH)) rows: KVH of 2, 4 or a multiple of 8 compiles;
    1, 3, 5, 6, 12 are refused ("Slice shape along dimension 2 must be
    aligned to tiling"). ``num_kv_heads`` is the count the kernel SEES —
    per tp shard under shard_map, so every tp layout that leaves one KV
    head per shard (qwen2.5-7b tp=4, 70B tp=8) is excluded here."""
    if head_dim % 128:
        return False
    if itemsize >= 4:
        return True
    tile = min(8, max(2, 1 << (num_kv_heads - 1).bit_length()))
    return num_kv_heads % tile == 0


def _v4_supported(num_kv_heads: int, head_dim: int) -> bool:
    """The lane-batched v4 kernel fuses (kvh, d) into ONE lane dimension
    (its pages move as [bs, kvh*d] slabs), so its alignment constraint is
    on the fused width — d=64 GQA models (llama-1b: 8×64=512) qualify even
    though the per-lane v2 schedule's d%128 rule excludes them."""
    return (num_kv_heads * head_dim) % 128 == 0


def decode_schedule(
    n_lanes: int, block_size: int, num_kv_heads: int, head_dim: int,
    itemsize: int, max_blocks: int, sharded: bool = False,
) -> Tuple[str, Optional[int]]:
    """Which Pallas decode schedule serves these shapes: ``("v4", pages_
    per_chunk)``, ``("v2", None)`` or ``("v1", None)``. The choice follows
    from shapes alone. ``num_kv_heads`` is per tp shard when ``sharded``
    (the kernel runs per shard under shard_map, where only the per-lane
    schedules are used). v1 — one page per grid step, BlockSpec-pipelined —
    has no DMA-slice alignment constraint and is the schedule of last
    resort."""
    if not sharded and _v4_supported(num_kv_heads, head_dim):
        from dynamo_tpu.ops.pallas.paged_attention import v4_plan

        plan = v4_plan(
            n_lanes, block_size, num_kv_heads, head_dim, itemsize, max_blocks
        )
        if plan is not None:
            return "v4", plan
    if _v2_supported(head_dim, num_kv_heads, itemsize):
        return "v2", None
    return "v1", None


def paged_decode(
    q: jax.Array,  # [S, H, D]
    k_cache: jax.Array,  # [N, bs, KVH, D]
    v_cache: jax.Array,
    block_tables: jax.Array,  # [S, MB]
    lengths: jax.Array,  # [S]; 0 = padding lane
    *,
    mesh=None,
    scale: Optional[float] = None,
    interpret: bool = False,
    return_stats: bool = False,
):
    """The Pallas decode kernel under the schedule :func:`decode_schedule`
    picks (per tp shard under shard_map when ``mesh`` is given)."""
    from dynamo_tpu.ops.pallas import paged_attention as pk

    kw = dict(scale=scale, interpret=interpret, return_stats=return_stats)
    if mesh is not None:
        return pk.paged_attention_decode_sharded(
            q, k_cache, v_cache, block_tables, lengths, mesh=mesh, **kw
        )
    _, bs, kvh, d = k_cache.shape
    name, plan = decode_schedule(
        q.shape[0], bs, kvh, d, k_cache.dtype.itemsize, block_tables.shape[1]
    )
    if name == "v4":
        # lane-batched single-program schedule: one loop drives every
        # lane's DMA+compute (the per-lane grid's fixed cost / n_lanes)
        return pk.paged_attention_decode_v4(
            q, k_cache, v_cache, block_tables, lengths,
            pages_per_chunk=plan, **kw
        )
    fn = pk.paged_attention_decode_v2 if name == "v2" else pk.paged_attention_decode
    return fn(q, k_cache, v_cache, block_tables, lengths, **kw)


def decode_uses_pallas(
    head_dim: int,
    mesh,
    num_heads: int,
    num_kv_heads: int,
    dense_history_bytes: int = 0,
    dense_history_budget: Optional[int] = None,
) -> bool:
    """Should the engine's decode dispatch read history through the Pallas
    kernel (paged, streams live pages HBM→VMEM) instead of the dense
    pre-gathered buffer (jnp einsums over [L, S, Smax])?

    Both tiers are window-buffered (no per-step pool writes). Measured on
    v5e: the dense tier wins whenever its buffer is affordable — a once-per-
    dispatch gather plus contiguous reads beat per-step paged DMA by ~1.4×
    at 2k context. The kernel tier wins when the dense buffer is NOT
    affordable: it reads only live pages (dense always reads the full
    padded [S, max_model_len] history and duplicates prefix-shared pages
    per lane), so the policy is a memory budget, not a speed heuristic:

    - ``DYN_TPU_ATTENTION=jnp``    → dense, always.
    - ``DYN_TPU_ATTENTION=pallas`` → kernel, always (if usable).
    - auto → kernel iff the dense history buffer would exceed
      ``dense_history_budget`` bytes (the engine passes its config's
      ``dense_history_max_bytes``) — e.g. a 70B tp8 slice at 8k context ×
      32 lanes needs a ~10 GB/chip dense buffer; the kernel serves that
      regime with zero extra HBM.

    Usability: TPU platform, and on a sharded mesh the head axes must split
    evenly over tp (shard_map divisibility). The kernel schedule is
    :func:`decode_schedule`'s choice.
    """
    mode = env_str("DYN_TPU_ATTENTION", "auto")
    if mode == "jnp":
        return False
    if mesh is not None and not _tp_divisible(mesh, num_heads, num_kv_heads):
        return False  # shard_map divisibility: kernel can't run at all
    if mode == "pallas":
        # honor the force even off-TPU — interpret mode is how CPU tests
        # cover the kernel-tier decode path
        return True
    if not _platform_is_tpu():
        return False
    return (
        dense_history_budget is not None
        and dense_history_bytes > dense_history_budget
    )


def _tp_divisible(mesh, h: int, kvh: int) -> bool:
    """Can the head axes split evenly over the mesh's tp axis? (shard_map
    requires exact divisibility, unlike GSPMD's padded auto-partitioning.)"""
    from dynamo_tpu.parallel.mesh import AXIS_TP

    if AXIS_TP not in mesh.axis_names:
        return True
    tp = mesh.shape[AXIS_TP]
    return h % tp == 0 and kvh % tp == 0


def _page_rows(
    positions: jax.Array,  # [B, T] absolute position in sequence; < 0 = padding
    block_tables: jax.Array,  # [B, max_blocks] physical page ids
    num_blocks: int,
    block_size: int,
) -> jax.Array:
    """Row of each position in a ``[num_blocks * block_size, ...]`` view of one
    layer's pages ([B, T] int32). Padding and out-of-table positions get the
    out-of-range row ``num_blocks * block_size``, which a ``mode="drop"``
    scatter drops; without it XLA's clamping would silently write into the
    wrong physical page."""
    max_blocks = block_tables.shape[1]
    logical_block = positions // block_size
    phys = jnp.take_along_axis(
        block_tables, jnp.clip(logical_block, 0, max_blocks - 1), axis=1
    )
    valid = (positions >= 0) & (logical_block < max_blocks)
    return jnp.where(
        valid, phys * block_size + positions % block_size,
        num_blocks * block_size,
    )


def write_kv_to_pages(
    k_cache: jax.Array,  # [num_blocks, block_size, KVH, D]
    v_cache: jax.Array,
    k_new: jax.Array,  # [B, T, KVH, D]
    v_new: jax.Array,
    positions: jax.Array,  # [B, T] absolute position in sequence; < 0 = padding
    block_tables: jax.Array,  # [B, max_blocks] physical page ids
) -> Tuple[jax.Array, jax.Array]:
    """Scatter new K/V vectors into ONE layer's pages; padding positions are
    dropped."""
    num_blocks, block_size = k_cache.shape[:2]
    rows = _page_rows(positions, block_tables, num_blocks, block_size).reshape(-1)

    def put(cache, new):
        flat = cache.reshape(num_blocks * block_size, *cache.shape[2:])
        flat = flat.at[rows].set(
            new.reshape(rows.shape[0], *new.shape[2:]), mode="drop"
        )
        return flat.reshape(cache.shape)

    return put(k_cache, k_new), put(v_cache, v_new)


def write_kv_to_pool(
    pool: jax.Array,  # [L, num_blocks, block_size, ...] one array of the pool
    new: jax.Array,  # [L, B, T, ...] every layer's fresh rows
    positions: jax.Array,  # [B, T] absolute position in sequence; < 0 = padding
    block_tables: jax.Array,  # [B, max_blocks] physical page ids
) -> jax.Array:
    """Scatter every layer's fresh rows into the whole pool at once; padding
    and out-of-table positions are dropped as in :func:`write_kv_to_pages`.
    Trailing-dim agnostic: K/V pages and the int8 pool's ``[L, N, bs]`` scale
    tables take the same call.

    The ONE flat index over ``[L * num_blocks * block_size, ...]`` is the
    point: on a donated pool XLA compiles it to a bare in-place scatter, so a
    step program's cost follows the rows it writes and not the pool's size. An
    index per axis (``pool.at[:, rows]``) makes the TPU compiler copy the
    whole pool into another layout and back
    (tests/test_aot_compile_tpu.py holds the compiled program to this)."""
    n_layers, num_blocks, block_size = pool.shape[:3]
    per_layer = num_blocks * block_size
    rows = _page_rows(positions, block_tables, num_blocks, block_size).reshape(-1)
    idx = jnp.where(
        rows < per_layer,
        jnp.arange(n_layers)[:, None] * per_layer + rows,
        n_layers * per_layer,
    ).reshape(-1)
    flat = pool.reshape(n_layers * per_layer, *pool.shape[3:])
    flat = flat.at[idx].set(
        new.reshape(idx.shape[0], *new.shape[3:]), mode="drop"
    )
    return flat.reshape(pool.shape)


def gather_pages(
    cache: jax.Array,  # [num_blocks, block_size, KVH, D]
    block_tables: jax.Array,  # [B, max_blocks]
) -> jax.Array:
    """Gather a request's pages into contiguous [B, max_blocks*block_size, KVH, D]."""
    pages = cache[block_tables]  # [B, MB, bs, KVH, D]
    b, mb, bs = pages.shape[0], pages.shape[1], pages.shape[2]
    return pages.reshape(b, mb * bs, *pages.shape[3:])


def paged_attention(
    q: jax.Array,  # [B, T, H, D]
    k_cache: jax.Array,  # [num_blocks, block_size, KVH, D]
    v_cache: jax.Array,
    block_tables: jax.Array,  # [B, max_blocks]
    q_positions: jax.Array,  # [B, T] absolute positions of queries; < 0 = padding
    *,
    scale: Optional[float] = None,
    soft_cap: Optional[float] = None,
    use_pallas: Optional[bool] = None,
    mesh=None,
) -> jax.Array:
    """Causal attention of ``q`` against the paged context (reference impl).

    The context for batch row b is the logical sequence laid out by its block
    table; query at absolute position p attends to context positions <= p
    (causal, inclusive of the just-written own position). Assumes new K/V were
    already scattered into the cache, which unifies prefill (T>1), decode (T=1)
    and prefix-cache-hit prefill (positions offset past the cached prefix).

    Pure-jnp fallback; the Pallas TPU kernel (ops/pallas/paged_attention.py)
    implements the same contract without materializing the gathered context.
    """
    b, t, h, d = q.shape
    kvh = k_cache.shape[2]
    if scale is None:
        scale = d ** -0.5

    if use_pallas is None:
        use_pallas = _select_pallas(d)
    if use_pallas and mesh is not None and not _tp_divisible(mesh, h, kvh):
        # shard_map needs the head axes to split evenly over tp; an uneven
        # mesh (e.g. tp=16 over KVH=8) keeps the GSPMD-partitioned jnp path
        use_pallas = False
    if t == 1 and soft_cap is None and use_pallas:
        lengths = jnp.maximum(q_positions[:, 0] + 1, 0)  # padding (pos<0) → 0
        out = paged_decode(
            q[:, 0], k_cache, v_cache, block_tables, lengths, mesh=mesh,
            scale=scale, interpret=jax.devices()[0].platform == "cpu",
        )
        return out[:, None]

    k = gather_pages(k_cache, block_tables)  # [B, S, KVH, D]
    v = gather_pages(v_cache, block_tables)
    s = k.shape[1]

    # GQA without materializing repeated K/V: group query heads per kv head
    g = h // kvh
    qg = q.reshape(b, t, kvh, g, d)
    scores = jnp.einsum("btngd,bsnd->bngts", qg, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    if soft_cap is not None:
        scores = jnp.tanh(scores / soft_cap) * soft_cap

    kv_pos = jnp.arange(s)[None, None, :]  # logical context positions
    causal = kv_pos <= q_positions[:, :, None]  # [B, T, S]
    valid_q = (q_positions >= 0)[:, :, None]
    mask = (causal & valid_q)[:, None, None, :, :]  # [B, 1, 1, T, S]

    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    # rows with no valid keys (padding queries) produce NaN → zero them
    probs = jnp.where(mask.any(axis=-1, keepdims=True), probs, 0.0)
    out = jnp.einsum("bngts,bsnd->btngd", probs.astype(v.dtype), v)
    return out.reshape(b, t, h, d).astype(q.dtype)
