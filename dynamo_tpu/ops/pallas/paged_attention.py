"""Pallas TPU paged-attention decode kernel.

Computes flash-style attention of one query token per lane against that
lane's paged KV context, streaming pages from HBM into VMEM with the block
table driving the DMA schedule — the physical page id is read from a
scalar-prefetched block table inside each BlockSpec ``index_map``, so the
kernel never materializes a gathered context (the round-1 jnp fallback
gathered + GQA-repeated the full padded context every step).

TPU counterpart of the reference's CUDA KV kernel tier
(``lib/llm/src/kernels/block_copy.cu:41-758`` moves paged KV; its engines'
paged attention lives in vLLM). Contract matches ``ops/attention.py``'s
``paged_attention`` for T==1; parity is tested in interpret mode on CPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _decode_kernel(
    # scalar prefetch
    tables_ref,  # [S, MB] int32 physical page per (lane, logical block)
    lengths_ref,  # [S] int32 context length (0 = padding lane)
    # blocks
    q_ref,  # [1, H, D]
    k_ref,  # [1, bs, KVH, D] — the page selected by index_map
    v_ref,  # [1, bs, KVH, D]
    o_ref,  # [1, H, D]
    *rest,  # with_stats: ms_ref [1,H], ls_ref [1,H] outputs, then scratch;
            # else just scratch: m_ref [H,1], l_ref [H,1], acc_ref [H,D]
    scale: float,
    kvh: int,
    with_stats: bool = False,
):
    if with_stats:
        ms_ref, ls_ref, m_ref, l_ref, acc_ref = rest
    else:
        ms_ref = ls_ref = None
        m_ref, l_ref, acc_ref = rest
    s = pl.program_id(0)
    j = pl.program_id(1)
    bs = k_ref.shape[1]
    h, d = q_ref.shape[1], q_ref.shape[2]
    g = h // kvh

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    length = lengths_ref[s]
    base = j * bs

    @pl.when(base < length)
    def _():
        q = q_ref[0].reshape(kvh, g, d).astype(jnp.float32)  # [KVH, G, D]
        k = k_ref[0].transpose(1, 0, 2).astype(jnp.float32)  # [KVH, bs, D]
        v = v_ref[0].transpose(1, 0, 2).astype(jnp.float32)  # [KVH, bs, D]

        scores = jax.lax.dot_general(  # [KVH, G, bs]
            q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        ) * scale
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (kvh, g, bs), 2)
        scores = jnp.where(pos < length, scores, -jnp.inf)

        flat = scores.reshape(h, bs)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, flat.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(flat - m_new[:, None])  # [H, bs]

        l_ref[:, 0] = l_ref[:, 0] * alpha + p.sum(axis=1)
        m_ref[:, 0] = m_new
        pv = jax.lax.dot_general(  # [KVH, G, D]
            p.reshape(kvh, g, bs), v,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * alpha[:, None] + pv.reshape(h, d)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        l = l_ref[:, 0]
        denom = jnp.where(l > 0.0, l, 1.0)  # padding lanes produce zeros
        o_ref[0] = (acc_ref[:] / denom[:, None]).astype(o_ref.dtype)
        if with_stats:
            # clamp -inf (no live keys) to a finite sentinel: downstream
            # merges exponentiate (m - m_total) and -inf - -inf would NaN
            ms_ref[0, 0] = jnp.maximum(m_ref[:, 0], -1e30)
            ls_ref[0, 0] = l


def _decode_kernel_v2(
    # scalar prefetch
    tables_ref,  # [S, MB]
    lengths_ref,  # [S]
    # blocks
    q_ref,  # [1, H, D] (VMEM, this lane)
    k_hbm,  # [N, bs, KVH, D] (stays in HBM; paged DMA below)
    v_hbm,
    o_ref,  # [1, H, D]
    *rest,  # with_stats: ms_ref [1,H], ls_ref [1,H] outputs, then scratch;
            # else just scratch: k_buf, v_buf [2,P,bs,KVH,D] VMEM, sem
    scale: float,
    kvh: int,
    pages_per_chunk: int,
    with_stats: bool = False,
):
    if with_stats:
        ms_ref, ls_ref, k_buf, v_buf, sem = rest
    else:
        ms_ref = ls_ref = None
        k_buf, v_buf, sem = rest
    s = pl.program_id(0)
    P = pages_per_chunk
    bs = k_hbm.shape[1]
    h, d = q_ref.shape[1], q_ref.shape[2]
    g = h // kvh
    length = lengths_ref[s]
    n_pages = lax.div(length + bs - 1, bs)
    n_chunks = lax.div(length + bs * P - 1, bs * P)

    # trailing in-chunk slots re-fetch the lane's LAST LIVE page: table
    # entries past the live context are never read (they may be arbitrary
    # padding), and the buffers always hold finite data — skipping the DMA
    # instead would leave uninitialized scratch whose NaNs survive masking
    # through the 0·NaN value contraction
    last_live = jnp.maximum(n_pages - 1, 0)

    def chunk_consecutive(chunk):
        """Are this chunk's P live pages physically consecutive? Fresh
        allocations pop ascending ids off the free list, so in steady
        serving most tables are runs — one chunk then moves as ONE
        P·bs-token DMA (~128 KB at d=128) instead of 2P page-sized copies
        (~8 KB each, pure latency). Recomputed identically at start and
        wait so the two always agree on which semaphores were used."""
        first = tables_ref[s, jnp.minimum(chunk * P, last_live)]
        # the whole chunk must be live: a partial tail re-fetches last_live
        # for its padding slots, which a run DMA can't express
        ok = (chunk + 1) * P - 1 <= last_live
        for i in range(1, P):
            idx = jnp.minimum(chunk * P + i, last_live)
            # clamped reads on a non-live chunk compare garbage, but `ok`
            # is already False then — the AND keeps it False
            ok = jnp.logical_and(ok, tables_ref[s, idx] == first + i)
        return ok, first

    def page_dma(slot, chunk, i, which):
        pid = tables_ref[s, jnp.minimum(chunk * P + i, last_live)]
        src, dst = (k_hbm, k_buf) if which == 0 else (v_hbm, v_buf)
        return pltpu.make_async_copy(
            src.at[pid], dst.at[slot, i], sem.at[slot, i, which]
        )

    def run_dma(slot, first, which):
        src, dst = (k_hbm, k_buf) if which == 0 else (v_hbm, v_buf)
        return pltpu.make_async_copy(
            src.at[pl.ds(first, P)], dst.at[slot], sem.at[slot, 0, which]
        )

    def start_chunk(slot, chunk):
        consec, first = chunk_consecutive(chunk)

        @pl.when(consec)
        def _():
            run_dma(slot, first, 0).start()
            run_dma(slot, first, 1).start()

        @pl.when(jnp.logical_not(consec))
        def _():
            for i in range(P):  # static unroll: P page-granular copies
                page_dma(slot, chunk, i, 0).start()
                page_dma(slot, chunk, i, 1).start()

    def wait_chunk(slot, chunk):
        consec, first = chunk_consecutive(chunk)

        @pl.when(consec)
        def _():
            run_dma(slot, first, 0).wait()
            run_dma(slot, first, 1).wait()

        @pl.when(jnp.logical_not(consec))
        def _():
            for i in range(P):
                page_dma(slot, chunk, i, 0).wait()
                page_dma(slot, chunk, i, 1).wait()

    @pl.when(n_chunks > 0)
    def _():
        start_chunk(0, 0)

    # q joins the cache dtype: K/V stream uncast into the MXU (casting THEM
    # is what blew the scoped-VMEM budget), and q is tiny — this also keeps
    # the engine's cache_dtype-differs-from-model-dtype configs compiling
    # (Mosaic has no mixed-operand matmul)
    q = q_ref[0].reshape(kvh, g, d).astype(k_buf.dtype)  # [KVH, G, D]

    def chunk_body(chunk, carry):
        m, l, acc = carry  # [H], [H], [H, D] f32
        slot = lax.rem(chunk, 2)

        @pl.when(chunk + 1 < n_chunks)
        def _():
            start_chunk(lax.rem(chunk + 1, 2), chunk + 1)

        wait_chunk(slot, chunk)
        k = k_buf[slot].reshape(P * bs, kvh, d)  # [T, KVH, D]
        v = v_buf[slot].reshape(P * bs, kvh, d)
        # cache dtype straight into the MXU (f32 accumulate via
        # preferred_element_type); f32 copies here double VMEM pressure
        kt = k.transpose(1, 0, 2)  # [KVH, T, D]
        vt = v.transpose(1, 0, 2)

        scores = lax.dot_general(  # [KVH, G, T]
            q, kt, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale
        pos = chunk * (P * bs) + lax.broadcasted_iota(jnp.int32, (kvh, g, P * bs), 2)
        scores = jnp.where(pos < length, scores, -jnp.inf)
        flat = scores.reshape(h, P * bs)

        m_new = jnp.maximum(m, flat.max(axis=1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(flat - m_new[:, None])
        l = l * alpha + p.sum(axis=1)
        pv = lax.dot_general(  # [KVH, G, D]
            p.reshape(kvh, g, P * bs).astype(vt.dtype), vt,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc = acc * alpha[:, None] + pv.reshape(h, d)
        return m_new, l, acc

    m0 = jnp.full((h,), -1e30, jnp.float32)
    l0 = jnp.zeros((h,), jnp.float32)
    acc0 = jnp.zeros((h, d), jnp.float32)
    m, l, acc = lax.fori_loop(0, n_chunks, chunk_body, (m0, l0, acc0))
    denom = jnp.where(l > 0.0, l, 1.0)  # padding lanes produce zeros
    o_ref[0] = (acc / denom[:, None]).astype(o_ref.dtype)
    if with_stats:
        ms_ref[0, 0] = m
        ls_ref[0, 0] = l


@functools.partial(
    jax.jit, static_argnames=("scale", "pages_per_chunk", "interpret", "return_stats")
)
def paged_attention_decode_v2(
    q: jax.Array,  # [S, H, D]
    k_cache: jax.Array,  # [N, bs, KVH, D]
    v_cache: jax.Array,
    block_tables: jax.Array,  # [S, MB] int32
    lengths: jax.Array,  # [S] int32; 0 = padding lane
    *,
    scale: Optional[float] = None,
    pages_per_chunk: int = 16,
    interpret: bool = False,
    return_stats: bool = False,
):
    """Flash decode over paged KV, multi-page double-buffered schedule.

    The KV pool stays in HBM; each grid step (one lane) streams its pages
    through two VMEM buffers with page-granular async copies, computing
    ``pages_per_chunk * block_size`` keys per inner iteration — the MXU
    sees big tiles and the next chunk's DMA overlaps compute, unlike the
    one-page-per-grid-step v1 schedule. Loop bound is the lane's true
    length, so short lanes neither fetch nor compute their padding.
    """
    s, h, d = q.shape
    _, bs, kvh, _ = k_cache.shape
    if scale is None:
        scale = d ** -0.5
    # clamp the double buffers to the scoped-VMEM budget. The in-kernel
    # transposes/casts cost roughly another buffer's worth of stack, so the
    # buffers themselves get at most 4 MB of the 16 MB scoped limit.
    P = min(pages_per_chunk, block_tables.shape[1])
    per_p = 2 * 2 * bs * kvh * d * k_cache.dtype.itemsize
    while P > 1 and P * per_p > (4 << 20):
        P //= 2

    out_specs = [pl.BlockSpec((1, h, d), lambda si, *_: (si, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((s, h, d), q.dtype)]
    if return_stats:
        out_specs += [pl.BlockSpec((1, 1, h), lambda si, *_: (si, 0, 0))] * 2
        out_shape += [jax.ShapeDtypeStruct((s, 1, h), jnp.float32)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s,),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda si, *_: (si, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.HBM),  # whole pool, stays HBM
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        out_specs=out_specs if return_stats else out_specs[0],
        scratch_shapes=[
            pltpu.VMEM((2, P, bs, kvh, d), k_cache.dtype),
            pltpu.VMEM((2, P, bs, kvh, d), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, P, 2)),
        ],
    )
    kernel = functools.partial(
        _decode_kernel_v2, scale=scale, kvh=kvh, pages_per_chunk=P,
        with_stats=return_stats,
    )
    res = pl.pallas_call(
        kernel,
        out_shape=out_shape if return_stats else out_shape[0],
        grid_spec=grid_spec,
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), q, k_cache, v_cache)
    if return_stats:
        out, m, l = res
        return out, m[:, 0], l[:, 0]
    return res


def v4_plan(
    n_lanes: int, bs: int, kvh: int, d: int, itemsize: int, mb: int,
    vmem_budget: Optional[int] = None,
) -> Optional[int]:
    """Largest pages_per_chunk whose lane-batched double buffers fit the
    VMEM budget, or None when even the smallest chunk doesn't (huge lane
    counts: fall back to the per-lane v2 schedule).

    The chip's scoped-VMEM limit is 16 MB, shared between the double
    buffers and the kernel's stack temporaries; the stack grows with the
    lane count (per-lane q/acc/score rows — measured ~9 MB at 64 lanes,
    ~4 MB at 8), so the buffer budget is 16 MB minus an affine
    lane-scaled margin that sits ABOVE both measured points (a constant
    would overshoot small-lane shapes or undershoot mid-lane ones)."""
    if vmem_budget is None:
        margin = max(6 << 20, (4 << 20) + n_lanes * 100 * 1024)
        vmem_budget = (16 << 20) - margin
    for p in (16, 8, 4, 2, 1):
        if p > mb:
            continue
        if 2 * 2 * n_lanes * p * bs * kvh * d * itemsize <= vmem_budget:
            return p
    return None


def _decode_kernel_v4(
    # scalar prefetch
    tables_ref,  # [S, MB]
    lengths_ref,  # [S]
    # blocks
    q_ref,  # [S, H, D] (VMEM — every lane)
    k_hbm,  # [N, bs, KVH*D] — kv-head and head-dim fused into the lane dim
    v_hbm,
    o_ref,  # [S, H, D]
    *rest,
    scale: float,
    kvh: int,
    pages_per_chunk: int,
    n_lanes: int,
    with_stats: bool = False,
):
    """Lane-batched single-program schedule: ONE fori_loop over context
    chunks drives every lane's DMA + compute together. vs the per-lane grid
    of v2/v3 this divides the fixed per-iteration cost (DMA bookkeeping,
    loop control, flash rescale) by the lane count and feeds the MXU a
    batched [S·KVH] stack of small matmuls per chunk — the regime where the
    kernel must compete with one big dense einsum.

    The cache arrives with (kvh, d) FUSED into one lane dimension: a page
    is [bs, kvh*d], so one DMA moves every head's slice of a page and the
    per-head operand inside the kernel is a STATIC LANE SLICE
    (``[..., n*d:(n+1)*d]``) — the one indexing pattern Mosaic lowers
    without relayout. Any unfused layout either puts kvh in the sublane dim
    (padded 2→8: 4× VMEM inflation) or needs a middle-dim gather (full
    buffer relayout on every read); both blow the 16 MB scoped-VMEM budget
    at serving shapes."""
    if with_stats:
        ms_ref, ls_ref, k_buf, v_buf, sem = rest
    else:
        ms_ref = ls_ref = None
        k_buf, v_buf, sem = rest
    S = n_lanes
    P = pages_per_chunk
    bs = k_hbm.shape[1]
    h, d = q_ref.shape[1], q_ref.shape[2]
    g = h // kvh
    T = P * bs  # context tokens per chunk

    # scalar-prefetch refs live in SMEM: only scalar loads — keep the
    # reduction scalar (Mosaic rejects 1-D→3-D vector reshapes, so the
    # mask-side broadcast below goes scalar→3-D directly, never via a
    # stacked [S] vector)
    max_len = lengths_ref[0]
    for i in range(1, S):
        max_len = jnp.maximum(max_len, lengths_ref[i])
    n_chunks = lax.div(max_len + T - 1, T)

    def lane_last_live(s):
        n_pages = lax.div(lengths_ref[s] + bs - 1, bs)
        return jnp.maximum(n_pages - 1, 0)

    def lane_consecutive(s, chunk):
        last = lane_last_live(s)
        first = tables_ref[s, jnp.minimum(chunk * P, last)]
        ok = (chunk + 1) * P - 1 <= last
        for i in range(1, P):
            idx = jnp.minimum(chunk * P + i, last)
            ok = jnp.logical_and(ok, tables_ref[s, idx] == first + i)
        return ok, first

    # one semaphore per (slot, lane, k/v), SHARED by that lane's page
    # copies: each copy increments it once and each wait decrements once,
    # so counts balance. A per-page semaphore array ([2, S, P, 2]) blows
    # the chip's sflag space (2 KB) at serving lane counts.
    def run_dma(slot, s, first, which):
        src, dst = (k_hbm, k_buf) if which == 0 else (v_hbm, v_buf)
        return pltpu.make_async_copy(
            src.at[pl.ds(first, P)], dst.at[slot, s], sem.at[slot, s, which]
        )

    def page_dma(slot, s, chunk, i, which):
        last = lane_last_live(s)
        pid = tables_ref[s, jnp.minimum(chunk * P + i, last)]
        src, dst = (k_hbm, k_buf) if which == 0 else (v_hbm, v_buf)
        return pltpu.make_async_copy(
            src.at[pid], dst.at[slot, s, i], sem.at[slot, s, which]
        )

    def lane_fetches(s, chunk):
        """Lanes whose context ended before this chunk skip their DMAs
        entirely — with ragged lengths (the serving norm: n_chunks is the
        BATCH max) a finished lane would otherwise re-stream its last page
        once per remaining chunk, pure wasted HBM bandwidth. Chunks 0 and 1
        always fetch so BOTH double-buffer slots hold finite data (compute
        masks the values off, but 0·NaN from uninitialized scratch would
        survive the mask through the value contraction)."""
        return jnp.logical_or(chunk <= 1, chunk * (P * bs) < lengths_ref[s])

    def start_chunk(slot, chunk):
        for s in range(S):  # static unroll over lanes
            consec, first = lane_consecutive(s, chunk)
            fetch = lane_fetches(s, chunk)

            @pl.when(jnp.logical_and(fetch, consec))
            def _(s=s, first=first):
                run_dma(slot, s, first, 0).start()
                run_dma(slot, s, first, 1).start()

            @pl.when(jnp.logical_and(fetch, jnp.logical_not(consec)))
            def _(s=s, chunk=chunk):
                for i in range(P):
                    page_dma(slot, s, chunk, i, 0).start()
                    page_dma(slot, s, chunk, i, 1).start()

    def wait_chunk(slot, chunk):
        for s in range(S):
            consec, first = lane_consecutive(s, chunk)
            fetch = lane_fetches(s, chunk)

            @pl.when(jnp.logical_and(fetch, consec))
            def _(s=s, first=first):
                run_dma(slot, s, first, 0).wait()
                run_dma(slot, s, first, 1).wait()

            @pl.when(jnp.logical_and(fetch, jnp.logical_not(consec)))
            def _(s=s, chunk=chunk):
                for i in range(P):
                    page_dma(slot, s, chunk, i, 0).wait()
                    page_dma(slot, s, chunk, i, 1).wait()

    @pl.when(n_chunks > 0)
    def _():
        start_chunk(0, 0)

    # per-kv-head query slices (kvh is static): Mosaic's tpu.matmul takes
    # ONE batch dim, and per-head slicing avoids vector-layout shape casts.
    # q joins the cache dtype (tiny cast; K/V stream uncast — see v2 note).
    q_all = q_ref[...].astype(k_buf.dtype)  # [S, H, D]
    q_heads = [q_all[:, n * g:(n + 1) * g, :] for n in range(kvh)]  # [S,G,D]

    # per-lane live mask operand, scalar→3-D broadcast per lane (see above)
    len3 = jnp.concatenate(
        [jnp.full((1, g, T), lengths_ref[i], jnp.int32) for i in range(S)], axis=0
    )  # [S, g, T]

    def chunk_body(chunk, carry):
        m, l, acc = carry  # [S,H], [S,H], [S,H,D] f32
        slot = lax.rem(chunk, 2)

        @pl.when(chunk + 1 < n_chunks)
        def _():
            start_chunk(lax.rem(chunk + 1, 2), chunk + 1)

        wait_chunk(slot, chunk)
        # Per-kv-head [S, T, D] operands via static LANE slices of the
        # fused buffer — no relayout, dense (T, D) tiling, MXU dtype.
        pos = chunk * T + lax.broadcasted_iota(jnp.int32, (S, g, T), 2)
        live = pos < len3  # [S, G, T]

        outs = []
        vns = []
        for n in range(kvh):
            kn = jnp.concatenate(
                [k_buf[slot, :, i, :, n * d:(n + 1) * d] for i in range(P)],
                axis=1,
            )  # [S, T, D]
            vns.append(jnp.concatenate(
                [v_buf[slot, :, i, :, n * d:(n + 1) * d] for i in range(P)],
                axis=1,
            ))
            scores = lax.dot_general(  # [S, G, T]
                q_heads[n], kn, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            ) * scale
            outs.append(jnp.where(live, scores, -jnp.inf))
        flat = jnp.concatenate(outs, axis=1)  # [S, H, T] (kvh-major like q)

        m_new = jnp.maximum(m, flat.max(axis=2))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(flat - m_new[:, :, None])
        l = l * alpha + p.sum(axis=2)
        pb = p.astype(k_buf.dtype)  # back to the MXU operand dtype
        pvs = []
        for n in range(kvh):
            pvs.append(lax.dot_general(  # [S, G, D]
                pb[:, n * g:(n + 1) * g, :], vns[n],
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            ))
        pv = jnp.concatenate(pvs, axis=1)  # [S, H, D]
        acc = acc * alpha[:, :, None] + pv
        return m_new, l, acc

    m0 = jnp.full((S, h), -1e30, jnp.float32)
    l0 = jnp.zeros((S, h), jnp.float32)
    acc0 = jnp.zeros((S, h, d), jnp.float32)
    m, l, acc = lax.fori_loop(0, n_chunks, chunk_body, (m0, l0, acc0))
    denom = jnp.where(l > 0.0, l, 1.0)
    o_ref[...] = (acc / denom[:, :, None]).astype(o_ref.dtype)
    if with_stats:
        ms_ref[...] = m[:, None]
        ls_ref[...] = l[:, None]


@functools.partial(
    jax.jit, static_argnames=("scale", "pages_per_chunk", "interpret", "return_stats")
)
def paged_attention_decode_v4(
    q: jax.Array,  # [S, H, D]
    k_cache: jax.Array,  # [N, bs, KVH, D]
    v_cache: jax.Array,
    block_tables: jax.Array,  # [S, MB] int32
    lengths: jax.Array,  # [S] int32; 0 = padding lane
    *,
    scale: Optional[float] = None,
    pages_per_chunk: int = 8,
    interpret: bool = False,
    return_stats: bool = False,
):
    """Lane-batched flash decode over paged KV (see _decode_kernel_v4)."""
    s, h, d = q.shape
    _, bs, kvh, _ = k_cache.shape
    if scale is None:
        scale = d ** -0.5
    # self-clamp to the VMEM budget: the scoped-vmem limit is ~16 MB and the
    # double buffers are the dominant allocation — a caller-passed P that
    # blows it is a compile error on chip, so clamp rather than trust
    plan = v4_plan(s, bs, kvh, d, k_cache.dtype.itemsize, block_tables.shape[1])
    if plan is None:
        raise ValueError(
            "v4 double buffers exceed the VMEM budget at every chunk size; "
            "use paged_attention_decode_v2 (per-lane grid) for this shape"
        )
    P = min(pages_per_chunk, block_tables.shape[1], plan)

    out_shape = [jax.ShapeDtypeStruct((s, h, d), q.dtype)]
    if return_stats:
        out_shape += [jax.ShapeDtypeStruct((s, 1, h), jnp.float32)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        out_specs=(
            [pl.BlockSpec(memory_space=pltpu.VMEM)] * 3
            if return_stats else pl.BlockSpec(memory_space=pltpu.VMEM)
        ),
        scratch_shapes=[
            pltpu.VMEM((2, s, P, bs, kvh * d), k_cache.dtype),
            pltpu.VMEM((2, s, P, bs, kvh * d), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, s, 2)),
        ],
    )
    kernel = functools.partial(
        _decode_kernel_v4, scale=scale, kvh=kvh, pages_per_chunk=P,
        n_lanes=s, with_stats=return_stats,
    )
    n_pages = k_cache.shape[0]
    res = pl.pallas_call(
        kernel,
        out_shape=out_shape if return_stats else out_shape[0],
        grid_spec=grid_spec,
        interpret=interpret,
    )(
        block_tables.astype(jnp.int32), lengths.astype(jnp.int32), q,
        # fuse (kvh, d) into the lane dim: layout-free reshape (contiguous
        # minor dims), one DMA per page covers every head's slice
        k_cache.reshape(n_pages, bs, kvh * d),
        v_cache.reshape(n_pages, bs, kvh * d),
    )
    if return_stats:
        out, m, l = res
        return out, m[:, 0], l[:, 0]
    return res


def paged_attention_decode_sharded(
    q: jax.Array,  # [S, H, D] — H sharded over tp
    k_cache: jax.Array,  # [N, bs, KVH, D] — KVH sharded over tp
    v_cache: jax.Array,
    block_tables: jax.Array,  # [S, MB] int32, replicated
    lengths: jax.Array,  # [S] int32, replicated
    *,
    mesh,
    scale: Optional[float] = None,
    pages_per_chunk: int = 16,
    interpret: bool = False,
    return_stats: bool = False,
):
    """The decode kernel on a sharded KV cache, via ``shard_map`` over tp.

    Mosaic kernels have no GSPMD partitioning rule, so a sharded cache can't
    flow into ``pallas_call`` under plain jit — but the computation is
    embarrassingly parallel over the tp axis: KV heads are the sharded axis
    (parallel/mesh.py ``kv_cache_sharding``), each kv head's query-head group
    is co-located by the Megatron head sharding, and every shard's page-pool
    slice is complete for its heads. ``shard_map`` runs the kernel per-shard
    with zero collectives; the output's head axis comes back sharded exactly
    like q, so the downstream ``attn @ wo`` contraction proceeds as in the
    jnp path. This is what lets the kernel tier run in sharded (70B-path)
    configs instead of falling back to jnp — the reference's kernel tier
    runs in every config (lib/llm/src/kernels/block_copy.cu:41).

    Other mesh axes (dp/pp/sp) see fully-replicated inputs and replicated
    outputs; ``check_vma=False`` because pallas_call can't be rep-checked.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from dynamo_tpu.ops.attention import decode_schedule
    from dynamo_tpu.parallel.mesh import AXIS_TP

    d = q.shape[-1]
    if scale is None:
        scale = d ** -0.5
    tp = AXIS_TP if AXIS_TP in mesh.axis_names else None
    qspec = P(None, tp, None)
    kvspec = P(None, None, tp, None)

    def local(qs, ks, vs, tbl, ln):
        # the alignment rule sees the PER-SHARD kv-head count: one KV head
        # per shard (qwen2.5-7b tp=4, 70B tp=8) is refused by the compiler
        # under v2 and takes the unconstrained v1 schedule
        _, bs, kvh_local, _ = ks.shape
        name, _ = decode_schedule(
            qs.shape[0], bs, kvh_local, d, ks.dtype.itemsize, tbl.shape[1],
            sharded=True,
        )
        if name == "v2":
            return paged_attention_decode_v2(
                qs, ks, vs, tbl, ln, scale=scale,
                pages_per_chunk=pages_per_chunk, interpret=interpret,
                return_stats=return_stats,
            )
        return paged_attention_decode(
            qs, ks, vs, tbl, ln, scale=scale, interpret=interpret,
            return_stats=return_stats,
        )

    # stats are per-head: sharded over tp exactly like q's head axis
    out_specs = (qspec, P(None, tp), P(None, tp)) if return_stats else qspec
    fn = shard_map(
        local, mesh=mesh,
        in_specs=(qspec, kvspec, kvspec, P(None, None), P(None)),
        out_specs=out_specs, check_vma=False,
    )
    return fn(q, k_cache, v_cache, block_tables, lengths)


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "return_stats"))
def paged_attention_decode(
    q: jax.Array,  # [S, H, D] one query token per lane
    k_cache: jax.Array,  # [N, bs, KVH, D]
    v_cache: jax.Array,
    block_tables: jax.Array,  # [S, MB] int32
    lengths: jax.Array,  # [S] int32 context length; 0 = padding lane
    *,
    scale: Optional[float] = None,
    interpret: bool = False,
    return_stats: bool = False,
):
    """Flash decode over paged KV. Returns [S, H, D] in q's dtype; with
    ``return_stats`` also the flash-softmax row max and denominator
    ([S, H] f32 each) for merging with out-of-pool context (the engine's
    decode window)."""
    s, h, d = q.shape
    _, bs, kvh, _ = k_cache.shape
    mb = block_tables.shape[1]
    if scale is None:
        scale = d ** -0.5

    # pages past a lane's live context re-select the previous page index so
    # the pipeline skips the redundant HBM→VMEM copy (compute is masked off)
    def page_index(si, ji, tables, lengths):
        last = jnp.maximum(pl.cdiv(lengths[si], bs) - 1, 0)
        return (tables[si, jnp.minimum(ji, last)], 0, 0, 0)

    out_specs = [pl.BlockSpec((1, h, d), lambda si, ji, *_: (si, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((s, h, d), q.dtype)]
    if return_stats:
        out_specs += [pl.BlockSpec((1, 1, h), lambda si, ji, *_: (si, 0, 0))] * 2
        out_shape += [jax.ShapeDtypeStruct((s, 1, h), jnp.float32)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, mb),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda si, ji, *_: (si, 0, 0)),
            pl.BlockSpec((1, bs, kvh, d), page_index),
            pl.BlockSpec((1, bs, kvh, d), page_index),
        ],
        out_specs=out_specs if return_stats else out_specs[0],
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ],
    )

    kernel = functools.partial(
        _decode_kernel, scale=scale, kvh=kvh, with_stats=return_stats
    )
    res = pl.pallas_call(
        kernel,
        out_shape=out_shape if return_stats else out_shape[0],
        grid_spec=grid_spec,
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), q, k_cache, v_cache)
    if return_stats:
        out, m, l = res
        return out, m[:, 0], l[:, 0]
    return res
