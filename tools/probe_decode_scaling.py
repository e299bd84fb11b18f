"""Decode-step scaling probe: per-step time vs slot count, int8 vs bf16.

Separates the decode step into (weight stream ~ fixed) + (per-lane costs ~
linear) by measuring the engine's own jitted decode fn at S = 32/64/128.
If the non-stream cost is mostly fixed, raising concurrency is the direct
path to the stream-roofline fraction target (the roofline scales with S,
the step cost doesn't). Timing via N-differenced data-chained dispatches
(see tools/bench_pallas.py: the fixed per-dispatch cost cancels).
"""

from __future__ import annotations

import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
from dynamo_tpu.models.llama import LLAMA_PRESETS, init_params

PRESET = os.environ.get("PROBE_PRESET", "llama3.2-1b")
CTX = int(os.environ.get("PROBE_CTX", "192"))
MAX_LEN = int(os.environ.get("PROBE_MAX_LEN", "264"))
SLOTS = [int(s) for s in os.environ.get("PROBE_SLOTS", "16,32,64,128").split(",")]
QUANT = os.environ.get("PROBE_QUANT", "int8")
BS = int(os.environ.get("PROBE_BS", "16"))
K_STEPS = int(os.environ.get("PROBE_K", "16"))


def fetch(x):
    jax.block_until_ready(x)
    return np.asarray(jax.device_get(jnp.ravel(x)[:4]))


def main():
    from dynamo_tpu.engine_jax.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = dataclasses.replace(LLAMA_PRESETS[PRESET], dtype=jnp.bfloat16)
    params = init_params(jax.random.PRNGKey(0), cfg)
    pbytes = sum(
        int(np.prod(p.shape)) * p.dtype.itemsize for p in jax.tree.leaves(params)
    )
    print(f"model={PRESET} bf16_bytes={pbytes/1e9:.3f} GB", flush=True)

    for S in SLOTS:
        ec = EngineConfig(
            max_slots=S, kv_block_size=BS, max_model_len=MAX_LEN,
            decode_steps=K_STEPS, prefill_chunk=128,
            quantize=(QUANT or None),
        )
        eng = JaxServingEngine(cfg, params, ec)
        sbytes = sum(
            int(np.prod(p.shape)) * p.dtype.itemsize
            for p in jax.tree.leaves(eng.params_decode)
        )
        try:
            K = ec.decode_steps
            rng = np.random.default_rng(0)
            tokens = eng._put(
                np.asarray(rng.integers(0, cfg.vocab_size, S), np.int32)
            )
            positions = eng._put(np.full((S,), CTX, np.int32))
            nblk = (CTX + BS) // BS + 1
            tables = np.zeros((S, ec.max_blocks_per_seq), np.int32)
            nb = ec.resolve_num_blocks()
            for i in range(S):
                tables[i, :nblk] = (
                    np.arange(1 + i * nblk, 1 + (i + 1) * nblk) % (nb - 1)
                ) + 1
            step_ctr = eng._put(np.int32(1))
            ipack = eng._put(np.zeros((2, S), np.int32))
            fpack = eng._put(
                np.stack(
                    [np.zeros(S), np.ones(S), np.zeros(S), np.zeros(S)]
                ).astype(np.float32)
            )
            tables_d = eng._put(tables)
            fn = eng._decode(False, False, False)
            cache, counts = eng.cache, eng._dummy_counts

            def run(n):
                nonlocal cache, counts
                t2, p2 = tokens, positions
                out = None
                for _ in range(n):
                    out, t2, p2, cache, counts = fn(
                        eng.params_decode, cache, counts, t2, p2, tables_d,
                        step_ctr, ipack, fpack,
                    )
                return out

            fetch(run(1))  # compile + settle

            def timed(n, reps=3):
                best = float("inf")
                for _ in range(reps):
                    t0 = time.perf_counter()
                    fetch(run(n))
                    best = min(best, time.perf_counter() - t0)
                return best

            n_lo, n_hi = 2, 26  # dispatches (K steps each)
            dt = (timed(n_hi) - timed(n_lo)) / ((n_hi - n_lo) * K)
            tok_s = S / dt
            roof = S * 819e9 / sbytes
            print(
                f"S={S:4d} quant={QUANT or 'bf16'}: {dt*1e3:.2f} ms/step "
                f"{tok_s:,.0f} tok/s  stream={sbytes/dt/1e9:.0f} GB/s "
                f"roofline_frac={tok_s/roof:.3f}",
                flush=True,
            )
        finally:
            eng.close()


if __name__ == "__main__":
    main()
