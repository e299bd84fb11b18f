"""On-chip microbenchmark for the Pallas decode kernel vs the dense jnp tier.

A single kernel call is microseconds of device time under about a
millisecond of per-dispatch host cost, so a wall-clock loop around single
dispatches measures the host. The harness here runs N data-chained kernel
invocations inside ONE jit (each iteration's q depends on the previous
output, so nothing can be elided or overlapped away), fetches a scalar to
force completion, and differences two N values to cancel the fixed cost.
Not run on the current machine: kernel times there come from a profiler
trace (on-chip-measurement guide §4); this tool is queued under ROADMAP D4.

Usage: python tools/bench_pallas.py [--ctx 2048,4096,8192,16384] [--lanes 8]
       [--heads 32] [--kv-heads 8] [--head-dim 128] [--json]

Counterpart of the reference's kernel benches (components/benchmarks; the
CUDA kernel tier lib/llm/src/kernels/block_copy.cu is benched in-engine).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _fetch(r):
    jax.block_until_ready(r)
    return float(jnp.asarray(r).ravel()[0].astype(jnp.float32))


def chained_iter_time(build_step, make_args, n_lo=32, reps=4, target_s=1.0):
    """Per-iteration device time of ``build_step`` via N-differencing.

    ``build_step(carry, *args) -> carry`` must make iteration i+1 depend on
    iteration i's output. ``make_args()`` returns (carry0, args).

    Host-clock noise per call is absorbed by making the differenced device
    time ≥ ``target_s`` (~1 s): measure at n_hi=2048 and escalate once to
    16384 if the signal is still under half the target.
    """

    @partial(jax.jit, static_argnames="n")
    def loop(carry, args, n):
        def body(i, c):
            return build_step(c, *args)

        return lax.fori_loop(0, n, body, carry)

    carry0, args = make_args()

    def timed(n, r=reps):
        best = float("inf")
        for _ in range(r):
            t0 = time.perf_counter()
            _fetch(loop(carry0, args, n))
            best = min(best, time.perf_counter() - t0)
        return best

    _fetch(loop(carry0, args, n_lo))  # warm compiles
    _fetch(loop(carry0, args, 2048))
    t_lo = timed(n_lo)
    t_hi = timed(2048)
    if t_hi - t_lo >= target_s / 2:
        return (t_hi - t_lo) / (2048 - n_lo)
    _fetch(loop(carry0, args, 16384))
    return (timed(16384) - t_lo) / (16384 - n_lo)


def bench_shape(S, H, KVH, D, BS, ctx, which):
    """Per-step decode-attention time for one implementation at one shape."""
    NP = max(ctx // BS, 1) * S  # distinct pages per lane: no prefix sharing
    MB = max(ctx // BS, 1)

    def make_args():
        kc = jax.random.normal(jax.random.PRNGKey(0), (NP, BS, KVH, D), jnp.bfloat16)
        vc = jax.random.normal(jax.random.PRNGKey(1), (NP, BS, KVH, D), jnp.bfloat16)
        q0 = jax.random.normal(jax.random.PRNGKey(2), (S, H, D), jnp.bfloat16)
        # permuted tables: steady-state serving is mostly-consecutive, but the
        # bench must not hand the kernel the best case only — interleave lanes
        tbl = jnp.asarray(
            np.arange(NP, dtype=np.int32).reshape(MB, S).T.copy()
        )
        ln = jnp.full((S,), ctx, jnp.int32)
        return q0, (q0, kc, vc, tbl, ln)

    if which == "jnp":
        from dynamo_tpu.ops.attention import paged_attention

        def step(q, q0, kc, vc, tbl, ln):
            out = paged_attention(
                q[:, None], kc, vc, tbl,
                jnp.full((q.shape[0], 1), ctx - 1, jnp.int32),
                use_pallas=False,
            )[:, 0]
            return q0 + out * jnp.bfloat16(1e-8)  # data-chain, value-neutral

    elif which == "v2":
        from dynamo_tpu.ops.pallas.paged_attention import paged_attention_decode_v2

        def step(q, q0, kc, vc, tbl, ln):
            out = paged_attention_decode_v2(q, kc, vc, tbl, ln)
            return q0 + out * jnp.bfloat16(1e-8)

    elif which == "v4":
        from dynamo_tpu.ops.pallas.paged_attention import paged_attention_decode_v4

        def step(q, q0, kc, vc, tbl, ln):
            out = paged_attention_decode_v4(q, kc, vc, tbl, ln)
            return q0 + out * jnp.bfloat16(1e-8)

    else:
        raise ValueError(which)

    return chained_iter_time(step, make_args)


def sweep_row(S, H, KVH, D, BS, ctx, impls):
    """One sweep row: per-impl us + effective GB/s + speedup vs jnp. The
    single home for the kv-byte formula and derived fields — bench.py's
    recorded section and this CLI must report identical numbers."""
    row = {"ctx": ctx, "lanes": S, "heads": H, "kv_heads": KVH, "head_dim": D}
    kv_bytes = S * ctx * KVH * D * 2 * 2  # k+v, bf16
    row["kv_mb"] = round(kv_bytes / 1e6, 1)
    for which in impls:
        try:
            t = bench_shape(S, H, KVH, D, BS, ctx, which)
            row[f"{which}_us"] = round(t * 1e6, 1)
            row[f"{which}_gbs"] = round(kv_bytes / t / 1e9, 1)
        except Exception as e:  # keep the sweep alive on one failure
            row[f"{which}_error"] = f"{type(e).__name__}: {e}"[:200]
    for k in ("v2", "v4"):
        if f"{k}_us" in row and "jnp_us" in row:
            row[f"{k}_speedup"] = round(row["jnp_us"] / row[f"{k}_us"], 3)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ctx", default="2048,4096,8192,16384")
    ap.add_argument("--lanes", type=int, default=8)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--block-size", type=int, default=128)
    ap.add_argument("--impls", default="jnp,v2,v4")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()

    rows = []
    for ctx in (int(c) for c in args.ctx.split(",")):
        row = sweep_row(
            args.lanes, args.heads, args.kv_heads, args.head_dim,
            args.block_size, ctx, args.impls.split(","),
        )
        rows.append(row)
        print(json.dumps(row) if args.json else row, flush=True)
    return rows


if __name__ == "__main__":
    main()
