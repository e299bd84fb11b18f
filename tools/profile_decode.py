"""Decode-step ablation profile on real TPU: localize the roofline gap.

Method notes:
- every measurement chains computations via data dependencies and fences
  with a small ``device_get``. On the current machine ``block_until_ready``
  does block and a host-clock dispatch time agrees with the device trace to
  ~6% (chip_smoke.py, PR 21), so the fence is belt and braces, not a need.
- bandwidth microbenches chain INSIDE one jit (lax.scan), not across
  dispatches, so the per-dispatch host cost does not enter.
- closing over params embeds 2.47 GB of constants in the MLIR (hour-long
  lowering) → every jitted fn takes params as an argument.
"""

from __future__ import annotations

import dataclasses
import faulthandler
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

faulthandler.dump_traceback_later(240, repeat=True, file=sys.stderr)

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
from dynamo_tpu.models.llama import (
    LLAMA_PRESETS,
    forward_window,
    gather_history,
    init_params,
    make_kv_cache,
)

PRESET = os.environ.get("PROF_PRESET", "llama3.2-1b")
SLOTS = int(os.environ.get("PROF_SLOTS", "32"))
K = int(os.environ.get("PROF_DECODE_STEPS", "64"))
CTX = int(os.environ.get("PROF_CTX", "192"))  # mid-decode history length
MAX_LEN = int(os.environ.get("PROF_MAX_LEN", "264"))
N_ITER = int(os.environ.get("PROF_ITERS", "4"))


def log(msg):
    print(f"[{time.strftime('%H:%M:%S')}] {msg}", flush=True)


def fetch(x):
    """Force completion: device_get of a small dependent slice."""
    return jax.device_get(jnp.ravel(x)[:4])


def hbm_bw():
    """Achievable HBM BW: 16 chained 1-GiB copies inside ONE dispatch."""
    x = jnp.zeros((1 << 28,), jnp.float32)  # 1 GiB

    @jax.jit
    def chain(a):
        def body(c, _):
            return c + 1.0, ()
        out, _ = jax.lax.scan(body, a, None, length=16)
        return out

    y = chain(x)
    fetch(y)  # compile + settle
    t0 = time.perf_counter()
    y = chain(y)
    fetch(y)
    dt = (time.perf_counter() - t0) / 16
    return 2 * x.nbytes / dt / 1e9  # rd + wr per step


def main():
    from dynamo_tpu.engine_jax.compile_cache import enable_compile_cache

    enable_compile_cache()
    log("init params...")
    cfg = dataclasses.replace(LLAMA_PRESETS[PRESET], dtype=jnp.bfloat16)
    params = init_params(jax.random.PRNGKey(0), cfg)
    pbytes = sum(int(np.prod(p.shape)) * p.dtype.itemsize for p in jax.tree.leaves(params))
    print(f"model={PRESET} params_bytes={pbytes/1e9:.3f} GB")
    log("hbm bw microbench...")
    bw = hbm_bw()
    print(f"achievable HBM BW (in-jit chain): {bw:.0f} GB/s (nominal 819)")
    ideal_step = pbytes / (bw * 1e9)
    print(f"weight-stream step at achievable BW: {ideal_step*1e3:.2f} ms "
          f"-> {SLOTS/ideal_step:.0f} tok/s")

    ec = EngineConfig(
        max_slots=SLOTS, kv_block_size=16, max_model_len=MAX_LEN,
        decode_steps=K, prefill_chunk=128,
    )
    log("build engine...")
    engine = JaxServingEngine(cfg, params, ec)

    S = SLOTS
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, S), jnp.int32)
    positions = jnp.full((S,), CTX, jnp.int32)
    nblk = (CTX + 16) // 16 + 1
    tables = np.zeros((S, ec.max_blocks_per_seq), np.int32)
    for i in range(S):
        tables[i, :nblk] = np.arange(1 + i * nblk, 1 + (i + 1) * nblk) % (
            ec.resolve_num_blocks() - 1
        ) + 1
    tables = jnp.asarray(tables)
    step_ctr = jnp.asarray(1, jnp.int32)
    ipack = jnp.zeros((2, S), jnp.int32)
    fpack = jnp.asarray(
        np.stack([np.zeros(S), np.ones(S), np.zeros(S), np.zeros(S)]), jnp.float32
    )

    # 1. full decode fn, engine's own (greedy path: no lp/pen/sample)
    fn = engine._decode(False, False, False)
    cache = engine.cache
    counts = engine._dummy_counts

    def call(cache, counts, toks, pos):
        out, t2, p2, cache, counts = fn(
            params, cache, counts, toks, pos, tables, step_ctr, ipack, fpack,
        )
        return out, t2, p2, cache, counts

    log("compile + warm decode fn...")
    out, t2, p2, cache, counts = call(cache, counts, tokens, positions)
    fetch(out)
    log("timing full decode fn...")
    t0 = time.perf_counter()
    for _ in range(N_ITER):
        out, t2, p2, cache, counts = call(cache, counts, t2, p2)
    fetch(out)
    dt = (time.perf_counter() - t0) / N_ITER
    print(f"[1] full decode dispatch k={K}: {dt*1e3:.1f} ms "
          f"({dt/K*1e3:.2f} ms/step, {S*K/dt:.0f} tok/s, "
          f"{ideal_step*K/dt*100:.0f}% of achievable-BW weight roofline)")
    engine.close()
    del engine, cache, counts

    # 2. ablation scans (params passed as args — no giant constants)
    wshape = (cfg.num_layers, S, K, cfg.num_kv_heads, cfg.head_dim)
    cache2 = make_kv_cache(cfg, ec.resolve_num_blocks(), 16)

    @jax.jit
    def fwd_only(params, cache, tokens, positions, tables):
        base = positions
        hist_k, hist_v = gather_history(cache, tables)
        history = ("dense", hist_k, hist_v)
        wk0 = jnp.zeros(wshape, cache["k"].dtype)
        wv0 = jnp.zeros(wshape, cache["v"].dtype)

        def body(carry, k):
            toks, pos, wk, wv = carry
            logits, wk, wv = forward_window(
                params, cfg, toks, pos, history, base, wk, wv, k,
            )
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, pos + 1, wk, wv), nxt

        (toks, pos, wk, wv), outs = jax.lax.scan(
            body, (tokens, positions, wk0, wv0), jnp.arange(K))
        return outs, toks

    log("compile fwd-only scan...")
    outs, toks = fwd_only(params, cache2, tokens, positions, tables)
    fetch(outs)
    log("timing fwd-only scan...")
    t0 = time.perf_counter()
    for _ in range(N_ITER):
        outs, toks = fwd_only(params, cache2, toks, positions, tables)
    fetch(outs)
    dt2 = (time.perf_counter() - t0) / N_ITER
    print(f"[2] fwd+argmax scan (no window flush, no sampling machinery) "
          f"k={K}: {dt2*1e3:.1f} ms ({dt2/K*1e3:.2f} ms/step)")

    # 3. k sweep on the raw scan: exposes fixed per-dispatch cost
    for ksweep in (16, 32):
        wshape_k = (cfg.num_layers, S, ksweep, cfg.num_kv_heads, cfg.head_dim)

        @jax.jit
        def fwd_k(params, cache, tokens, positions, tables, _ks=ksweep, _ws=wshape_k):
            base = positions
            hist_k, hist_v = gather_history(cache, tables)
            history = ("dense", hist_k, hist_v)
            wk0 = jnp.zeros(_ws, cache["k"].dtype)
            wv0 = jnp.zeros(_ws, cache["v"].dtype)

            def body(carry, k):
                toks, pos, wk, wv = carry
                logits, wk, wv = forward_window(
                    params, cfg, toks, pos, history, base, wk, wv, k,
                )
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (nxt, pos + 1, wk, wv), nxt

            (toks, pos, wk, wv), outs = jax.lax.scan(
                body, (tokens, positions, wk0, wv0), jnp.arange(_ks))
            return outs, toks

        outs, toks = fwd_k(params, cache2, tokens, positions, tables)
        fetch(outs)
        t0 = time.perf_counter()
        for _ in range(N_ITER):
            outs, toks = fwd_k(params, cache2, toks, positions, tables)
        fetch(outs)
        dtk = (time.perf_counter() - t0) / N_ITER
        print(f"[3] fwd scan k={ksweep}: {dtk*1e3:.1f} ms ({dtk/ksweep*1e3:.2f} ms/step)")

    # 4. chunk-prefill dispatch: [S, C] fresh prompt, the TTFT critical path
    ec2 = EngineConfig(
        max_slots=SLOTS, kv_block_size=16, max_model_len=MAX_LEN,
        decode_steps=K, prefill_chunk=128,
    )
    log("build engine for chunk timing...")
    engine2 = JaxServingEngine(cfg, params, ec2)
    C = ec2.prefill_chunk
    ptoks = jnp.asarray(rng.integers(0, cfg.vocab_size, (S, C)), jnp.int32)
    ppos = jnp.tile(jnp.arange(C)[None], (S, 1))
    sample_at = jnp.full((S,), C - 1, jnp.int32)
    flops = 2.0 * (pbytes / 2) * S * C  # params(count) ≈ bytes/2 for bf16

    for hist in (True, False):
        cfn = engine2._chunk(False, False, False, hist, S)
        cache3 = engine2.cache
        counts3 = engine2._dummy_counts

        def ccall(cache, counts):
            nxt, cache, counts = cfn(
                params, cache, counts, ptoks, ppos, tables, sample_at,
                jnp.arange(S, dtype=jnp.int32), step_ctr, ipack, fpack,
            )
            return nxt, cache, counts

        nxt, cache3, counts3 = ccall(cache3, counts3)
        fetch(nxt)
        t0 = time.perf_counter()
        for _ in range(N_ITER):
            nxt, cache3, counts3 = ccall(cache3, counts3)
        fetch(nxt)
        # donation: hand the live buffers back to the engine
        engine2.cache = cache3
        engine2._dummy_counts = counts3
        dtc = (time.perf_counter() - t0) / N_ITER
        print(f"[4] chunk prefill dispatch [S={S}, C={C}] history={hist}: "
              f"{dtc*1e3:.1f} ms ({flops/dtc/1e12:.1f} TFLOP/s, "
              f"{flops/dtc/197e12*100:.0f}% MFU)")

    # 5. end-to-end single-request TTFT through the engine (host path incl.)
    import asyncio

    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context

    async def one_ttft():
        req = PreprocessedRequest(
            token_ids=rng.integers(0, cfg.vocab_size, 128).tolist(),
            stop_conditions=StopConditions(max_tokens=2, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0),
        )
        t0 = time.perf_counter()
        async for item in engine2.generate(Context(req)):
            if (item.data or {}).get("token_ids"):
                return time.perf_counter() - t0
        return None

    # warm the serving path once, then measure
    asyncio.run(one_ttft())
    ts = [asyncio.run(one_ttft()) for _ in range(3)]
    print(f"[5] single-request TTFT (prompt 128, engine path): "
          f"{', '.join(f'{t*1e3:.0f}' for t in ts)} ms "
          f"(device chunk alone: {dtc*1e3:.0f} ms)")
    engine2.close()

    log("done")


if __name__ == "__main__":
    main()
