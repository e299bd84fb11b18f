"""Serving benchmark on real TPU hardware: continuous-batching throughput.

Drives the full JaxServingEngine (paged KV, chunked batched prefill, jitted
multi-step decode, in-jit sampling) with concurrent requests on the flagship
model and reports output tokens/sec/chip, TTFT percentiles, MFU, and the
fraction of the weight-bandwidth decode roofline achieved.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

The reference publishes no absolute numbers (BASELINE.md), so ``vs_baseline``
is roofline-based: the DECODE-PHASE token rate (all lanes prefilled — the
steady state the roofline describes) against the bf16 weight-stream decode
roofline tok/s_max = slots * BW / bytes(bf16 params) — the ceiling an
unquantized engine could ever reach on this chip. The default engine mode is
hybrid int8 (decode streams the int8 weight copy, prefill computes bf16),
which is how it passes large fractions of that ceiling; ``stream_fraction``
reports the same rate against the roofline of the bytes the decode actually
streams, and ``alt_mode`` measures the other weight mode on the same
workload. The reference's GPU engines typically run 0.5-0.7 of their own
(unquantized) rooflines.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import os
import sys
import time

def _tree_bytes(tree) -> int:
    import jax
    import numpy as np

    return sum(
        int(np.prod(p.shape)) * p.dtype.itemsize for p in jax.tree.leaves(tree)
    )


N_REQUESTS = int(os.environ.get("BENCH_REQUESTS", "32"))
PROMPT_LEN = int(os.environ.get("BENCH_PROMPT_LEN", "128"))
GEN_TOKENS = int(os.environ.get("BENCH_GEN_TOKENS", "128"))
MAX_SLOTS = int(os.environ.get("BENCH_SLOTS", "32"))
DECODE_STEPS = int(os.environ.get("BENCH_DECODE_STEPS", "64"))
PRESET = os.environ.get("BENCH_PRESET", "llama3.2-1b")

# Published peaks of one chip, keyed by the device_kind JAX reports (Google
# Cloud documentation, "TPU v5e": 819 GB/s HBM, 197 TFLOP/s bf16). A device
# that is not in the table is an error, not a default.
DEVICE_PEAKS = {"TPU v5 lite": {"hbm_gbps": 819.0, "bf16_tflops": 197.0}}
HBM_GBPS = PEAK_TFLOPS = None  # set by _require_tpu() from DEVICE_PEAKS


def _require_tpu() -> None:
    """bench.py measures a TPU: refuse any other platform (a CPU number must
    never be printed under a device metric's name) and any chip whose peaks
    are not in the table."""
    global HBM_GBPS, PEAK_TFLOPS
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU; JAX found platform {dev.platform!r}"
        )
    if dev.device_kind not in DEVICE_PEAKS:
        raise SystemExit(
            f"no peak rates for device_kind {dev.device_kind!r}; add its "
            f"published peaks to bench.DEVICE_PEAKS"
        )
    HBM_GBPS = DEVICE_PEAKS[dev.device_kind]["hbm_gbps"]
    PEAK_TFLOPS = DEVICE_PEAKS[dev.device_kind]["bf16_tflops"]
# "serve" (default): concurrent-load throughput/TTFT.
# "multiturn": long-prompt conversations re-sent after device-pool pressure —
# measures the host KV tier's TTFT win (reference credits +40%).
MODE = os.environ.get("BENCH_MODE", "serve")
# "int8" (default) = hybrid weight quantization: decode streams the int8
# copy, prefill computes with bf16 (the int8 dequant starves the MXU in the
# FLOPs-bound chunk). "" = bf16 everywhere. The JSON reports the decode rate
# against BOTH rooflines — the bf16 (unquantized-ceiling) one and the int8
# stream's own — explicitly labeled.
QUANTIZE = os.environ.get("BENCH_QUANTIZE", "int8")
# >1: serve over a tp mesh spanning the local chips (real multi-chip runs)
BENCH_TP = int(os.environ.get("BENCH_TP", "1"))


def bench_multiturn() -> None:
    """Multi-turn TTFT with and without the host KV tier.

    Conversations long enough that the device pool can't hold them all are
    revisited after eviction pressure; with the host tier their KV re-enters
    HBM instead of being recomputed. Prints one JSON line with TTFT for both
    configurations."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.models.llama import LLAMA_PRESETS
    from dynamo_tpu.runtime.engine import Context

    cfg = dataclasses.replace(LLAMA_PRESETS[PRESET], dtype=jnp.bfloat16)
    params = _init_params_fast(cfg)
    n_convs = int(os.environ.get("BENCH_CONVS", "8"))
    turn_len = int(os.environ.get("BENCH_TURN_LEN", "512"))
    # pool holds ~2.5 conversations: revisits force eviction
    blocks_per_conv = (turn_len + 64) // 16 + 1
    num_kv_blocks = int(blocks_per_conv * 2.5)

    rng = np.random.default_rng(0)
    convs = [rng.integers(0, cfg.vocab_size, turn_len).tolist() for _ in range(n_convs)]

    async def one(engine, prompt):
        req = PreprocessedRequest(
            token_ids=prompt,
            stop_conditions=StopConditions(max_tokens=8, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0),
        )
        t0 = time.perf_counter()
        ttft = None
        async for item in engine.generate(Context(req)):
            if ttft is None and (item.data or {}).get("token_ids"):
                ttft = time.perf_counter() - t0
        return ttft

    def run_config(host_blocks: int) -> float:
        engine = JaxServingEngine(
            cfg, params,
            EngineConfig(
                max_slots=4, kv_block_size=16, max_model_len=turn_len + 64,
                num_kv_blocks=num_kv_blocks, host_cache_blocks=host_blocks,
            ),
        )
        engine.warmup()

        async def drive():
            # turn 1: prefill every conversation (evicting earlier ones)
            for c in convs:
                await one(engine, c)
            # turn 2: revisit — device tier mostly evicted
            ttfts = []
            for c in convs:
                ttfts.append(await one(engine, c))
            return ttfts

        ttfts = asyncio.run(drive())
        engine.close()
        return sorted(ttfts)[len(ttfts) // 2]

    cold = run_config(0)
    warm = run_config(num_kv_blocks * 8)  # host tier holds everything
    out = {
        "metric": "multiturn_ttft_p50_ms",
        "value": round(warm * 1e3, 1),
        "unit": "ms",
        "vs_baseline": round(cold / warm, 2),  # x-fold TTFT win from host tier
        "mode": "multiturn",
        "model": PRESET,
        "turn_len": turn_len,
        "conversations": n_convs,
        "ttft_p50_no_host_tier_ms": round(cold * 1e3, 1),
        "ttft_p50_host_tier_ms": round(warm * 1e3, 1),
    }
    print(json.dumps(out))



def _init_params_fast(cfg, seed: int = 0):
    """init_params under ONE jit program: one dispatch instead of the eager
    version's ~30, and the persistent compile cache makes the compile itself
    a one-time cost."""
    import jax

    from dynamo_tpu.models.llama import init_params

    return jax.jit(init_params, static_argnums=1)(jax.random.PRNGKey(seed), cfg)

def _release_device_memory():
    """Drop every droppable device buffer between bench sections: each
    section builds its own engine + params, and without this the leftovers
    accumulate until the later sections die with RESOURCE_EXHAUSTED on a
    16 GB chip (the dress-rehearsal failure mode for concurrency/model_8b)."""
    import gc

    import jax

    gc.collect()
    try:
        jax.clear_caches()
    except Exception:
        pass
    gc.collect()


def bench_pallas_kernel() -> dict:
    """On-chip kernel microbench: lane-batched Pallas decode (v4) vs the
    dense jnp tier at the llama-8B serving geometry (S=8, H=32, KVH=8,
    D=128), ctx 2k/4k/8k/16k. Uses the N-differenced chained harness
    (tools/bench_pallas.py). The auto-policy crossover
    (dense under ``dense_history_max_bytes``, kernel above) is grounded in
    these numbers: dense wins while its buffer is VMEM/HBM-affordable, the
    kernel streams at the practical HBM ceiling and reads only live bytes."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools"))
    from bench_pallas import sweep_row

    S, H, KVH, D, BS = 8, 32, 8, 128, 128
    rows = [
        sweep_row(S, H, KVH, D, BS, ctx, ("jnp", "v4"))
        for ctx in (2048, 4096, 8192, 16384)
    ]
    # headline = the longest ctx with a valid measurement (the kernel-tier
    # regime; 8k sits on the crossover, 16k is decisive) — a transient
    # failure of one row must not erase the round's kernel evidence
    head_row = next(
        (r for r in reversed(rows) if "v4_speedup" in r and r["ctx"] >= 8192),
        None,
    )
    return {
        "shape": {"lanes": S, "heads": H, "kv_heads": KVH, "head_dim": D},
        "sweep": rows,
        # kernel-tier rows only (ctx >= 8k): a short-ctx fallback would be
        # the dense-wins regime mislabeled as the kernel headline
        "pallas_speedup": head_row["v4_speedup"] if head_row else None,
        "pallas_speedup_ctx": head_row["ctx"] if head_row else None,
    }


def bench_pallas_d128() -> dict:
    """Kernel-tier proof point on a D=128 model (qwen2.5-1.5b), long context.

    Serves the same workload twice — Pallas paged-decode kernel (forced) vs
    the dense windowed jnp tier — and reports both decode throughputs. This
    runs the Pallas kernel end-to-end through the serving engine in the
    recorded benchmark (VERDICT r2 W1: no recorded bench had ever executed
    the kernel tier). Note the auto policy (EngineConfig
    dense_history_max_bytes, ops/attention.py decode_uses_pallas) picks the
    dense tier at this scale — the kernel's regime is histories too large to
    materialize densely (70B/long-context), which a 16 GB single chip cannot
    hold; the kernel-level crossover is measured by bench_pallas_kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.models.llama import LLAMA_PRESETS
    from dynamo_tpu.runtime.engine import Context

    preset = "qwen2.5-1.5b"
    n_req, prompt_len, gen = 8, 2048, 48
    cfg = dataclasses.replace(LLAMA_PRESETS[preset], dtype=jnp.bfloat16)
    params = _init_params_fast(cfg, seed=1)
    rng = np.random.default_rng(1)
    prompts = [
        rng.integers(0, cfg.vocab_size, prompt_len).tolist() for _ in range(n_req)
    ]

    async def one(engine, prompt):
        req = PreprocessedRequest(
            token_ids=prompt,
            stop_conditions=StopConditions(max_tokens=gen, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0),
        )
        first = None
        n = 0
        async for item in engine.generate(Context(req)):
            got = len((item.data or {}).get("token_ids", []))
            if got and first is None:
                first = time.perf_counter()
            n += got
        return first, n

    def run_config(attention: str):
        os.environ["DYN_TPU_ATTENTION"] = attention
        engine = None
        try:
            engine = JaxServingEngine(
                cfg, params,
                EngineConfig(
                    max_slots=n_req, kv_block_size=16,
                    max_model_len=prompt_len + gen + 16,
                    decode_steps=16, prefill_chunk=256,
                ),
            )
            engine.warmup()

            async def drive():
                t0 = time.perf_counter()
                res = await asyncio.gather(*[one(engine, p) for p in prompts])
                end = time.perf_counter()
                # decode throughput: first token (end of prefill) -> done
                first = min(t for t, _ in res if t is not None)
                toks = sum(n for _, n in res)
                return toks, end - t0, end - first

            toks, total_s, decode_s = asyncio.run(drive())
            return toks / decode_s
        finally:
            if engine is not None:
                engine.close()
            os.environ.pop("DYN_TPU_ATTENTION", None)

    jnp_tok_s = run_config("jnp")
    pallas_tok_s = run_config("pallas")
    return {
        "model": preset,
        "head_dim": 128,
        "prompt_len": prompt_len,
        "requests": n_req,
        "decode_tok_s_pallas": round(pallas_tok_s, 1),
        "decode_tok_s_jnp": round(jnp_tok_s, 1),
        "pallas_speedup": round(pallas_tok_s / jnp_tok_s, 3),
        "auto_policy": "dense under dense_history_max_bytes; kernel above "
                       "(zero extra HBM residency at 70B/long-context scale)",
    }


def _serve_wave(cfg, params, engine_cfg, prompts, gen, warm_len,
                warmup_variants="all"):
    """Shared engine-drive protocol for the sectional benches: build, warm
    (compiles + one small disjoint wave so timed prompts stay cache-cold),
    drive the measured wave, tear down. Returns drive_wave's tuple plus the
    engine's decode stream bytes."""
    import numpy as np

    from dynamo_tpu.engine_jax.engine import JaxServingEngine

    engine = JaxServingEngine(cfg, params, engine_cfg)
    try:
        engine.warmup(variants=warmup_variants)
        rng = np.random.default_rng(99)
        warm = [rng.integers(0, cfg.vocab_size, warm_len).tolist() for _ in range(2)]
        drive_wave(engine, warm, 8)
        out, elapsed, ttfts, decode_tok_s = drive_wave(engine, prompts, gen)
        return out, elapsed, ttfts, decode_tok_s, _tree_bytes(engine.params_decode)
    finally:
        engine.close()


def bench_isl_sweep() -> dict:
    """TTFT/throughput across input sequence lengths (VERDICT r4 item 7):
    the <200 ms TTFT target must hold beyond toy prompts. Prompt lengths
    128/1k/2k/4k on the flagship 1B in the headline int8 mode; requests
    sized so every wave fits the slot count (one admission wave, no
    queueing noise in TTFT). Match: reference benchmark recipes sweep ISL
    (examples/llm/benchmarks/README.md:27-125)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
    from dynamo_tpu.models.llama import LLAMA_PRESETS

    cfg = dataclasses.replace(LLAMA_PRESETS[PRESET], dtype=jnp.bfloat16)
    params = _init_params_fast(cfg)
    rows = []
    rng = np.random.default_rng(7)
    for isl in (128, 1024, 2048, 4096):
        n_req, gen = 8, 48
        prompts = [
            rng.integers(0, cfg.vocab_size, isl).tolist() for _ in range(n_req)
        ]
        out, elapsed, ttfts, decode_tok_s, _ = _serve_wave(
            cfg, params,
            EngineConfig(
                max_slots=n_req, kv_block_size=16,
                max_model_len=isl + gen + 16, decode_steps=16,
                prefill_chunk=256, quantize=QUANTIZE or None,
            ),
            prompts, gen, warm_len=isl,
        )
        rows.append({
            "isl": isl,
            "requests": n_req,
            "ttft_p50_ms": round(ttfts[len(ttfts) // 2] * 1e3, 1),
            "ttft_p95_ms": round(ttfts[int(len(ttfts) * 0.95)] * 1e3, 1),
            "decode_tok_s": round(decode_tok_s, 1),
        })
    return {"model": PRESET, "quantize": QUANTIZE or "bf16", "sweep": rows}


def _host_quantized_params(cfg, seed: int = 0):
    """Build an int8 {q, s} param tree leaf-by-leaf on the HOST (numpy):
    the full bf16 tree of an 8B model (16.06 GB) can never exist in a
    16 GB chip's HBM, and doing it leaf-wise keeps host RSS bounded.

    The bench serves random tokens, so the weights only need the right
    SHAPES and bounded activations — generate the int8 tensors directly
    (uniform in [-127, 127]) with a constant fan-in scale instead of
    quantizing gaussian floats: float RNG + 4 quantization passes over
    32 GB cost ~6 host-minutes; int8 generation is ~20x cheaper and the
    device-side compute/byte profile is identical."""
    import numpy as np

    rng = np.random.default_rng(seed)

    def dense_q(shape, fan_in, contract_axis):
        q = rng.integers(-127, 128, size=shape, dtype=np.int8)
        s_shape = tuple(
            d for i, d in enumerate(shape)
            if i != (contract_axis % len(shape))
        )
        # dequantized magnitude ~ U(-1,1)/sqrt(fan_in): bounded activations
        s = np.full(
            s_shape, 1.0 / (127.0 * np.sqrt(float(fan_in))), np.float32
        )
        return {"q": q, "s": s}

    L, E, F = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    V = cfg.vocab_size
    params = {
        "embed": dense_q((V, E), E, 1),
        "final_norm": np.ones((E,), np.float32),
        "layers": {
            "attn_norm": np.ones((L, E), np.float32),
            "wq": dense_q((L, E, cfg.q_dim), E, 1),
            "wk": dense_q((L, E, cfg.kv_dim), E, 1),
            "wv": dense_q((L, E, cfg.kv_dim), E, 1),
            "wo": dense_q((L, cfg.q_dim, E), cfg.q_dim, 1),
            "mlp_norm": np.ones((L, E), np.float32),
            "w_gate": dense_q((L, E, F), E, 1),
            "w_up": dense_q((L, E, F), E, 1),
            "w_down": dense_q((L, F, E), F, 1),
        },
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_q((E, V), E, 0)
    return params


def bench_model_8b() -> dict:
    """Largest family member that fits one chip: llama3-8b in int8-all
    (both phases read the int8 weights; the bf16 tree would alone exceed
    16 GB HBM). Host-quantized leaf-by-leaf, uploaded once. Reports the
    serving rate + TTFT as the big-single-chip datapoint (VERDICT r4
    item 7)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
    from dynamo_tpu.models.llama import LLAMA_PRESETS

    cfg = dataclasses.replace(LLAMA_PRESETS["llama3-8b"], dtype=jnp.bfloat16)
    host = _host_quantized_params(cfg)
    params = jax.tree.map(jnp.asarray, host)
    del host
    n_req, prompt_len, gen = 8, 128, 32
    rng = np.random.default_rng(3)
    prompts = [
        rng.integers(0, cfg.vocab_size, prompt_len).tolist() for _ in range(n_req)
    ]
    out, elapsed, ttfts, decode_tok_s, stream_bytes = _serve_wave(
        cfg, params,
        EngineConfig(
            max_slots=n_req, kv_block_size=16,
            max_model_len=prompt_len + gen + 16, decode_steps=16,
            prefill_chunk=128, quantize="int8-all",
        ),
        prompts, gen, warm_len=prompt_len,
        # greedy-only warmup: this section serves greedy
        warmup_variants="greedy",
    )
    roof = n_req * HBM_GBPS * 1e9 / stream_bytes
    return {
        "model": "llama3-8b",
        "quantize": "int8-all",
        "requests": n_req,
        "prompt_len": prompt_len,
        "tok_s": round(out / elapsed, 1),
        "decode_tok_s": round(decode_tok_s, 1),
        "ttft_p50_ms": round(ttfts[len(ttfts) // 2] * 1e3, 1),
        "stream_gb": round(stream_bytes / 1e9, 2),
        "roofline_fraction": round(decode_tok_s / roof, 3),
        # ttft/tok_s include whatever first-execution compilation the
        # greedy-only warmup left; the steady-state number is decode_tok_s
        "note": "ttft/tok_s include first-boot compilation; "
                "decode_tok_s is the steady-state rate",
    }


def bench_concurrency() -> dict:
    """Decode rate + stream-roofline fraction vs slot count: the step cost
    is (weight stream ~ fixed) + (per-lane attention ~ linear), so the
    fraction falls as concurrency rises while absolute tok/s climbs —
    this curve is the measured basis for choosing the serving point
    (probes: tools/probe_decode_scaling.py)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
    from dynamo_tpu.models.llama import LLAMA_PRESETS

    cfg = dataclasses.replace(LLAMA_PRESETS[PRESET], dtype=jnp.bfloat16)
    params = _init_params_fast(cfg)
    rng = np.random.default_rng(5)
    rows = []
    for slots in (16, 32, 64):
        prompts = [
            rng.integers(0, cfg.vocab_size, PROMPT_LEN).tolist()
            for _ in range(slots)
        ]
        out, elapsed, ttfts, decode_tok_s, stream_bytes = _serve_wave(
            cfg, params,
            EngineConfig(
                max_slots=slots, kv_block_size=16,
                max_model_len=PROMPT_LEN + 96 + 8, decode_steps=DECODE_STEPS,
                prefill_chunk=min(256, PROMPT_LEN), quantize=QUANTIZE or None,
            ),
            prompts, 96, warm_len=PROMPT_LEN,
        )
        roof = slots * HBM_GBPS * 1e9 / stream_bytes
        rows.append({
            "slots": slots,
            "decode_tok_s": round(decode_tok_s, 1),
            "roofline_fraction": round(decode_tok_s / roof, 3),
            "ttft_p50_ms": round(ttfts[len(ttfts) // 2] * 1e3, 1),
        })
    return {"model": PRESET, "quantize": QUANTIZE or "bf16", "sweep": rows}


def drive_wave(engine, prompts, gen_tokens):
    """Run one concurrent wave; returns (total_out, elapsed, ttfts,
    decode_tok_s) where decode_tok_s is the decode-phase rate (all lanes
    prefilled → done), guarded against a degenerate zero-length phase.

    TTFT and per-token inter-token gaps additionally feed the tracing
    plane's phase histograms (runtime/tracing.py) so the BENCH json can
    report p50/p95/p99 latency shape from the same source operators scrape
    in production. Multi-token items spread their arrival gap evenly — the
    engine emits whole decode chunks, the consumer-visible per-token rate
    is gap/chunk."""
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime import tracing
    from dynamo_tpu.runtime.engine import Context

    trace_on = tracing.enabled()

    async def one(prompt):
        req = PreprocessedRequest(
            token_ids=prompt,
            stop_conditions=StopConditions(max_tokens=gen_tokens, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0),
        )
        t0 = time.perf_counter()
        ttft = first_abs = None
        prev = None
        n = 0
        async for item in engine.generate(Context(req)):
            got = len(((item.data) or {}).get("token_ids", []))
            if got and ttft is None:
                first_abs = time.perf_counter()
                ttft = first_abs - t0
                prev = first_abs
                if trace_on:
                    tracing.observe_phase("ttft", ttft)
            elif got:
                now = time.perf_counter()
                if trace_on and prev is not None:
                    tracing.observe_phase("inter_token", (now - prev) / got)
                prev = now
            n += got
        return ttft, n, first_abs

    async def go():
        t0 = time.perf_counter()
        res = await asyncio.gather(*[one(p) for p in prompts])
        return res, time.perf_counter() - t0, time.perf_counter()

    res, elapsed, end = asyncio.run(go())
    out = sum(n for _, n, _ in res)
    ttfts = sorted(t for t, _, _ in res if t is not None)
    firsts = [f for _, _, f in res if f is not None]
    decode_start = max(firsts) if firsts else end
    decode_toks = out - len(firsts)
    decode_tok_s = decode_toks / (end - decode_start) if end > decode_start else 0.0
    return out, elapsed, ttfts, decode_tok_s


def bench_alt_mode(quantize: str) -> dict:
    """The OTHER weight mode on the same workload (one wave) — the primary
    and this secondary together show what hybrid int8 buys: the decode
    stream halves while prefill keeps the bf16 MXU path."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.models.llama import LLAMA_PRESETS
    from dynamo_tpu.runtime.engine import Context

    cfg = dataclasses.replace(LLAMA_PRESETS[PRESET], dtype=jnp.bfloat16)
    params = _init_params_fast(cfg)
    engine = JaxServingEngine(
        cfg, params,
        EngineConfig(
            max_slots=MAX_SLOTS, kv_block_size=16,
            max_model_len=max(256, PROMPT_LEN + GEN_TOKENS + 8),
            decode_steps=DECODE_STEPS, prefill_chunk=min(256, PROMPT_LEN),
            quantize=quantize or None,
        ),
    )
    try:
        # the DECODE stream reads the quantized copy — that is the roofline
        pbytes = _tree_bytes(engine.params_decode)
        rng = np.random.default_rng(7)
        prompts = [
            rng.integers(0, cfg.vocab_size, PROMPT_LEN).tolist()
            for _ in range(N_REQUESTS)
        ]
        drive_wave(engine, prompts[:2], GEN_TOKENS)  # warm
        out_toks, elapsed, _, decode_tok_s = drive_wave(engine, prompts, GEN_TOKENS)
        roofline = MAX_SLOTS * HBM_GBPS * 1e9 / pbytes
        return {
            "quantize": quantize or "bf16",
            "tok_s_chip": round(out_toks / elapsed, 1),
            "decode_tok_s_chip": round(decode_tok_s, 1),
            "stream_roofline_tok_s": round(roofline, 1),
            "stream_fraction": round(decode_tok_s / roofline, 3),
        }
    finally:
        engine.close()


def _spec_leg(cfg, params, prompts, spec_k: int) -> dict:
    """One speculative-decoding measurement: an engine at the given spec_k
    over the workload; returns decode rate, ITL percentiles (from the
    tracing plane, reset per leg), and the engine's own draft counters."""
    from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
    from dynamo_tpu.runtime import tracing as _tracing

    engine = JaxServingEngine(
        cfg, params,
        EngineConfig(
            max_slots=MAX_SLOTS, kv_block_size=16,
            max_model_len=max(256, PROMPT_LEN + GEN_TOKENS + 8),
            decode_steps=DECODE_STEPS, prefill_chunk=min(256, PROMPT_LEN),
            quantize=QUANTIZE or None, spec_k=spec_k,
        ),
    )
    try:
        engine.warmup()
        drive_wave(engine, prompts[:2], GEN_TOKENS)  # warm
        _tracing.configure()  # ITL percentiles cover only the timed wave
        out_toks, elapsed, _, decode_tok_s = drive_wave(
            engine, prompts, GEN_TOKENS
        )
        snap = engine.metrics_snapshot()
        phases = _tracing.phase_summary()
        itl = phases.get("inter_token", {}) if phases else {}
        drafted = snap.get("spec_drafted_tokens", 0)
        accepted = snap.get("spec_accepted_tokens", 0)
        return {
            "spec_k": spec_k,
            "tok_s": round(out_toks / elapsed, 1),
            "decode_tok_s": round(decode_tok_s, 1),
            "itl_p50_ms": itl.get("p50_ms"),
            "itl_p95_ms": itl.get("p95_ms"),
            "spec_drafted_tokens": drafted,
            "spec_accepted_tokens": accepted,
            "spec_accept_rate": round(accepted / drafted, 4) if drafted else 0.0,
        }
    finally:
        engine.close()


def bench_spec_decode() -> dict:
    """Speculative decoding (r06): drafted-vs-accepted counters plus decode
    tok/s and ITL deltas against the non-speculative baseline, on two
    workloads — repetition-heavy (a short motif tiled through the prompt,
    the shape prompt-lookup drafting exists for: multi-turn quoting, code
    edits, extraction) and adversarial (i.i.d. random prompts, where the
    drafter should go dormant and cost ~nothing). The acceptance gate is
    spec/base decode tok/s ≥ 1.5 on repetition and ≥ 0.95 on adversarial."""
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models.llama import LLAMA_PRESETS

    spec_k = int(os.environ.get("BENCH_SPEC_K", "4"))
    cfg = dataclasses.replace(LLAMA_PRESETS[PRESET], dtype=jnp.bfloat16)
    params = _init_params_fast(cfg)
    rng = np.random.default_rng(11)
    motif = rng.integers(0, cfg.vocab_size, 24).tolist()
    rep_prompts = [
        # per-request offset so waves don't all prefix-hit one another
        (motif[i % len(motif):] + motif * (PROMPT_LEN // len(motif) + 1))[:PROMPT_LEN]
        for i in range(N_REQUESTS)
    ]
    adv_prompts = [
        rng.integers(0, cfg.vocab_size, PROMPT_LEN).tolist()
        for _ in range(N_REQUESTS)
    ]
    out: dict = {"spec_k": spec_k}
    for name, prompts in (("repetition", rep_prompts), ("adversarial", adv_prompts)):
        base = _spec_leg(cfg, params, prompts, 0)
        _release_device_memory()
        spec = _spec_leg(cfg, params, prompts, spec_k)
        _release_device_memory()
        ratio = (
            spec["decode_tok_s"] / base["decode_tok_s"]
            if base["decode_tok_s"] else None
        )
        itl_delta = (
            round(spec["itl_p50_ms"] - base["itl_p50_ms"], 3)
            if spec["itl_p50_ms"] is not None and base["itl_p50_ms"] is not None
            else None
        )
        out[name] = {
            "baseline": base,
            "speculative": spec,
            "decode_speedup": round(ratio, 3) if ratio else None,
            "itl_p50_delta_ms": itl_delta,
        }
    return out


def bench_kv_int8() -> dict:
    """int8-KV vs bf16-KV sweep leg (r06): same workload, same weights, the
    only difference is the page layout — int8 pages + per-token scale
    tables halve the KV half of the decode stream. The win grows with
    context; at short ISL the quantize/dequantize ops can eat the saving."""
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
    from dynamo_tpu.models.llama import LLAMA_PRESETS

    cfg = dataclasses.replace(LLAMA_PRESETS[PRESET], dtype=jnp.bfloat16)
    params = _init_params_fast(cfg)
    rng = np.random.default_rng(13)
    prompt_len = int(os.environ.get("BENCH_KV_PROMPT_LEN", str(max(PROMPT_LEN, 1024))))
    prompts = [
        rng.integers(0, cfg.vocab_size, prompt_len).tolist()
        for _ in range(N_REQUESTS)
    ]
    legs = {}
    for kv_dtype in ("bf16", "int8"):
        engine = JaxServingEngine(
            cfg, params,
            EngineConfig(
                max_slots=MAX_SLOTS, kv_block_size=16,
                max_model_len=max(256, prompt_len + GEN_TOKENS + 8),
                decode_steps=DECODE_STEPS, prefill_chunk=256,
                quantize=QUANTIZE or None, kv_dtype=kv_dtype,
            ),
        )
        try:
            drive_wave(engine, prompts[:2], GEN_TOKENS)  # warm
            out_toks, elapsed, ttfts, decode_tok_s = drive_wave(
                engine, prompts, GEN_TOKENS
            )
            legs[kv_dtype] = {
                "tok_s": round(out_toks / elapsed, 1),
                "decode_tok_s": round(decode_tok_s, 1),
                "ttft_p50_ms": (
                    round(ttfts[len(ttfts) // 2] * 1e3, 1) if ttfts else None
                ),
            }
        finally:
            engine.close()
        _release_device_memory()
    b, q = legs["bf16"]["decode_tok_s"], legs["int8"]["decode_tok_s"]
    return {
        "prompt_len": prompt_len,
        "bf16": legs["bf16"],
        "int8": legs["int8"],
        "decode_speedup": round(q / b, 3) if b else None,
    }


def bench_frontend() -> dict:
    """Frontend hot-path saturation (VERDICT r3 item 8): echo engine at zero
    delay behind the real OpenAI HTTP service, N concurrent SSE streams.

    Reports the frontend-only token ceiling (tok/s through HTTP + SSE +
    protocol encode/decode with no model in the way) and the per-token
    frontend CPU cost — the number that says when the Python frontend
    becomes the bottleneck ahead of the chips it feeds."""
    import aiohttp

    from dynamo_tpu.llm.engines import EchoEngineFull
    from dynamo_tpu.llm.http.service import HttpService, ModelManager

    concurrency = int(os.environ.get("BENCH_FE_CONCURRENCY", "32"))
    words = int(os.environ.get("BENCH_FE_WORDS", "256"))
    rounds = int(os.environ.get("BENCH_FE_ROUNDS", "4"))

    async def go():
        manager = ModelManager()
        manager.add_chat_model("echo", EchoEngineFull(delay_s=0.0))
        svc = HttpService(manager, host="127.0.0.1", port=0)
        port = await svc.start()
        body = {
            "model": "echo", "stream": True,
            "messages": [{"role": "user", "content": "tok " * words}],
        }

        async def one(session):
            n = 0
            async with session.post(
                f"http://127.0.0.1:{port}/v1/chat/completions", json=body
            ) as resp:
                async for line in resp.content:
                    if line.startswith(b"data: ") and b"content" in line:
                        n += 1
            return n

        try:
            async with aiohttp.ClientSession() as session:
                await asyncio.gather(*[one(session) for _ in range(4)])  # warm
                t0 = time.perf_counter()
                c0 = time.process_time()
                total = 0
                for _ in range(rounds):
                    ns = await asyncio.gather(
                        *[one(session) for _ in range(concurrency)]
                    )
                    total += sum(ns)
                wall = time.perf_counter() - t0
                cpu = time.process_time() - c0
        finally:
            await svc.stop()
        return {
            "concurrency": concurrency,
            "tokens": total,
            "frontend_tok_s": round(total / wall, 1),
            "frontend_cpu_us_per_token": round(cpu / max(total, 1) * 1e6, 1),
            "cpu_utilization": round(cpu / wall, 2),
            # r4→r5: the SSE template fast path (llm/http/service.py
            # _SseTemplate) removed the per-token json.dumps tree walk:
            # 40.5k→49.2k tok/s, 24.5→19.7 µs/token. The residue is aiohttp
            # transport machinery (server-only ≈20 µs/token measured with an
            # external client). Pod-scale analysis: one frontend process
            # feeds 6-10 chips at the current per-chip rate; frontends are
            # stateless and horizontally scaled by the operator (HPA), same
            # as the reference's replicated frontends — the binding
            # constraint at pod scale is chips, not frontend CPU.
            "analysis": "sse template fast path; residue is aiohttp "
                        "transport; scale frontends horizontally (~7 "
                        "chips/process)",
        }

    return asyncio.run(go())


def bench_planner_sim() -> dict:
    """SLA-driven planner under the million-user traffic simulator
    (tools/traffic_sim.py, virtual time — milliseconds of wall clock, no
    TPU): the 5× flash-crowd burst scenario with the planner closed-loop,
    plus a frozen-topology control leg quantifying what the loop buys.

    Reports SLO page→clear time, peak/final pool sizes, decision counts,
    and the control leg's failure count — the ROADMAP item 4 acceptance
    ("SLO recovery after a 5x burst with zero failed requests") as a bench
    number the perf trajectory can track."""
    from tools.traffic_sim import run_burst_scenario

    res = asyncio.run(run_burst_scenario())
    ctrl = asyncio.run(run_burst_scenario(planner_enabled=False))
    scale_decisions = [d for d in res.decisions if d["kind"] == "scale"]
    ups = sum(
        1 for d in scale_decisions if d["to_replicas"] > d["from_replicas"]
    )
    return {
        "scenario": "diurnal-base + 5x flash crowd, r05 isl_sweep heavy-tail mix",
        "offered_requests": res.offered_total,
        "failed_requests": res.failed_total,
        "first_page_t_s": res.first_page_t,
        # to_dict maps inf -> "never" (json.dumps would emit Infinity)
        "slo_recovery_s": res.to_dict()["recovery_s"],
        "page_episodes": len(res.episodes),
        "pool_initial": res.pool_initial,
        "pool_peak": res.pool_peak,
        "pool_final": res.pool_final,
        "scale_decisions": len(scale_decisions),
        "scale_up_decisions": ups,
        "control_no_planner": {
            "failed_requests": ctrl.failed_total,
            "slo_recovery_s": ctrl.to_dict()["recovery_s"],
        },
    }


def bench_qos() -> dict:
    """Multi-tenant QoS under a noisy neighbor (tools/qos_sim.py, virtual
    time — no TPU): victim-tenant ITL p95 alone, with an abusive tenant at
    ~10-20x its rate quota under full QoS (rate gate + weighted fair
    queuing + KV budget + prefill duty cycle), and with QoS off (the
    control leg proving the contention is real). The tier-1 acceptance
    (tests/test_qos.py): QoS holds the victim's ITL p95 within 10% of the
    alone baseline with zero victim sheds."""
    from tools.qos_sim import run_scenario

    res = run_scenario()
    return {
        "scenario": "steady short-prompt victim vs 10-20x-quota "
                    "long-prompt abuser, one shared worker",
        "victim_itl_p95_ms_alone": res["victim_alone"]["itl_p95_ms"],
        "victim_itl_p95_ms_qos": res["victim_with_abuser_qos"]["itl_p95_ms"],
        "victim_itl_p95_ms_no_qos": res["victim_with_abuser_no_qos"]["itl_p95_ms"],
        "victim_itl_p95_ratio_qos": res["victim_itl_p95_ratio_qos"],
        "victim_itl_p95_ratio_no_qos": res["victim_itl_p95_ratio_no_qos"],
        "victim_itl_max_ms_qos": res["victim_with_abuser_qos"]["itl_max_ms"],
        "victim_shed_qos": res["victim_with_abuser_qos"]["shed"],
        "abuser_shed_share_qos": round(
            res["abuser_qos"]["shed"] / max(res["abuser_qos"]["offered"], 1), 4
        ),
        "abuser_ttft_p95_ms_qos": res["abuser_qos"]["ttft_p95_ms"],
    }


def bench_resilience() -> dict:
    """Mid-stream resume overhead (docs/resilience.md §Mid-stream resume;
    no TPU — deterministic token engines over the real statestore + RPC +
    EndpointClient planes). Two legs at identical load: a control with no
    failures, and a kill leg where a fixed share of live streams is cut
    after 10 items (the `cut` fault = worker death mid-decode). Reports
    the resume rate and what recovery costs the caller: the added ITL gap
    p95, and the p95 of the worst per-stream gap (the resume pause
    itself). BENCH_RESUME=0 skips."""
    import asyncio

    import numpy as np

    from dynamo_tpu.runtime import faults as faults_mod
    from dynamo_tpu.runtime import resilience
    from dynamo_tpu.runtime.annotated import Annotated
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.engine import AsyncEngine, Context
    from dynamo_tpu.runtime.faults import FaultInjector, FaultRule
    from dynamo_tpu.runtime.resilience import ResiliencePolicy
    from dynamo_tpu.runtime.statestore import StateStoreServer

    n_requests = int(os.environ.get("BENCH_RESUME_REQUESTS", "24"))
    gen_tokens = int(os.environ.get("BENCH_RESUME_TOKENS", "40"))
    kills = int(os.environ.get("BENCH_RESUME_KILLS", "6"))
    token_delay = 0.002

    class TokenEngine(AsyncEngine):
        async def generate(self, request: Context):
            req = request.data
            toks = list(req["token_ids"])
            for _ in range(int(req["stop_conditions"]["max_tokens"])):
                if request.context.is_stopped:
                    return
                toks.append((toks[-1] * 31 + len(toks) * 7 + 13) % 50021)
                yield Annotated.from_data({"token_ids": [toks[-1]]})
                await asyncio.sleep(token_delay)
            yield Annotated.from_data(
                {"token_ids": [], "finish_reason": "length"}
            )

    async def leg(kill: bool) -> dict:
        resilience.reset_resume_counters()
        ss = StateStoreServer(port=0)
        await ss.start()
        rts = []
        for _ in range(3):
            rt = await DistributedRuntime.create(ss.url, "127.0.0.1:1")
            await rt.namespace("bres").component("w").endpoint("gen").serve(
                TokenEngine()
            )
            rts.append(rt)
        fe = await DistributedRuntime.create(ss.url, "127.0.0.1:1")
        client = await fe.namespace("bres").component("w").endpoint(
            "gen"
        ).client("round_robin", policy=ResiliencePolicy(
            request_timeout=60.0, connect_timeout=2.0, max_attempts=4,
            backoff_base=0.01, backoff_max=0.05, resume_attempts=2, seed=3,
        ))
        await client.wait_for_instances(3, timeout=10)
        gaps: list = []
        stream_max_gap: list = []

        async def one(i: int) -> None:
            ctx = Context({
                "token_ids": [11 + i, 17 + 2 * i],
                "stop_conditions": {"max_tokens": gen_tokens},
                "sampling_options": {"temperature": 0.0},
            })
            last = None
            worst = 0.0
            async for item in client.generate(ctx):
                if item.is_error:
                    raise RuntimeError(item.error_message())
                now = time.perf_counter()
                if last is not None:
                    gap = now - last
                    gaps.append(gap)
                    worst = max(worst, gap)
                last = now
            stream_max_gap.append(worst)

        inj = None
        if kill:
            inj = FaultInjector([FaultRule(
                plane="rpc", point="item", action="cut", after_ops=10,
                max_fires=kills,
            )])
            faults_mod.install(inj)
        try:
            t0 = time.perf_counter()
            await asyncio.gather(*[one(i) for i in range(n_requests)])
            wall = time.perf_counter() - t0
        finally:
            if inj is not None:
                faults_mod.uninstall()
            await client.close()
            for rt in rts + [fe]:
                await rt.shutdown()
            await ss.stop()
        arr = np.asarray(gaps) * 1e3
        return {
            "wall_s": round(wall, 3),
            "itl_p50_ms": round(float(np.percentile(arr, 50)), 3),
            "itl_p95_ms": round(float(np.percentile(arr, 95)), 3),
            "worst_gap_p95_ms": round(
                float(np.percentile(np.asarray(stream_max_gap) * 1e3, 95)), 3
            ),
            "resumes": client.stats["resumes"],
            "resume_failures": client.stats["resume_failures"],
        }

    control = asyncio.run(leg(kill=False))
    killed = asyncio.run(leg(kill=True))
    return {
        "scenario": (
            f"{n_requests} concurrent streams x {gen_tokens} tokens on 3 "
            f"workers; kill leg cuts {kills} live streams after 10 items"
        ),
        "control": control,
        "kill": killed,
        "resume_rate": round(killed["resumes"] / n_requests, 4),
        "added_itl_p95_ms": round(
            killed["itl_p95_ms"] - control["itl_p95_ms"], 3
        ),
        "added_worst_gap_p95_ms": round(
            killed["worst_gap_p95_ms"] - control["worst_gap_p95_ms"], 3
        ),
    }


def bench_integrity() -> dict:
    """The integrity plane's tax and its catch (docs/resilience.md §Silent
    corruption; tiny REAL engine on the host platform). Leg 1/2: identical
    decode load with DYN_TPU_KV_INTEGRITY on vs off — the on/off tok/s
    ratio IS the seal-checksum + watchdog cost. Leg 3: the corruption
    drill — every host-tier spill bit-flipped; reports trips counted and
    asserts the replayed prompts still produced byte-identical tokens
    (the recompute path, never the rotten bytes). BENCH_INTEGRITY=0
    skips."""
    import asyncio
    import dataclasses

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
    from dynamo_tpu.models.llama import LLAMA_PRESETS, init_params
    from dynamo_tpu.runtime import faults as faults_mod
    from dynamo_tpu.runtime import integrity as integrity_mod
    from dynamo_tpu.runtime.engine import Context

    n_requests = int(os.environ.get("BENCH_INTEGRITY_REQUESTS", "8"))
    gen_tokens = int(os.environ.get("BENCH_INTEGRITY_TOKENS", "96"))
    prompt_len = int(os.environ.get("BENCH_INTEGRITY_PROMPT", "64"))
    # restore the CALLER's knob afterwards: a user benching with
    # DYN_TPU_KV_INTEGRITY=0 must not have later sections silently pay the
    # checksum tax because this one popped the var
    prior_knob = os.environ.get("DYN_TPU_KV_INTEGRITY")

    cfg = dataclasses.replace(LLAMA_PRESETS["tiny"], dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = [
        [(7 * i + 3 + j) % 101 for j in range(prompt_len)]
        for i in range(n_requests)
    ]

    async def collect(eng, toks):
        out = []
        async for item in eng.generate(Context({
            "token_ids": list(toks),
            "stop_conditions": {"max_tokens": gen_tokens,
                                "ignore_eos": True},
            "sampling_options": {"temperature": 0.0},
        })):
            if item.is_error:
                raise RuntimeError(item.error_message())
            out.extend((item.data or {}).get("token_ids", []))
        return out

    def leg(enabled: bool, host_blocks: int = 0) -> tuple:
        os.environ["DYN_TPU_KV_INTEGRITY"] = "1" if enabled else "0"
        integrity_mod.reset_for_tests()
        eng = JaxServingEngine(cfg, params, EngineConfig(
            max_slots=4, kv_block_size=8,
            max_model_len=prompt_len + gen_tokens + 16,
            host_cache_blocks=host_blocks,
        ))

        async def run_all():
            # warm the compiles out of the timed window
            await collect(eng, prompts[0])
            t0 = time.perf_counter()
            outs = await asyncio.gather(
                *[collect(eng, p) for p in prompts]
            )
            return outs, time.perf_counter() - t0

        outs, wall = asyncio.run(run_all())
        eng.close()
        toks = sum(len(o) for o in outs)
        return outs, round(toks / wall, 1), round(wall, 3)

    try:
        _, tps_on, wall_on = leg(True)
        _, tps_off, wall_off = leg(False)

        # corruption drill: host-tier spills rot; replays must recompute
        os.environ["DYN_TPU_KV_INTEGRITY"] = "1"
        integrity_mod.reset_for_tests()
        inj = faults_mod.FaultInjector([faults_mod.FaultRule(
            plane="engine", point="pages", action="corrupt",
        )])
        eng = JaxServingEngine(cfg, params, EngineConfig(
            max_slots=4, kv_block_size=8,
            max_model_len=prompt_len + gen_tokens + 16,
            host_cache_blocks=256,
        ))

        async def drill():
            with faults_mod.active(inj):
                first = [await collect(eng, p) for p in prompts[:4]]
                # evict the first wave into the (corrupted) host tier
                for p in prompts[4:]:
                    await collect(eng, p)
                replay = [await collect(eng, p) for p in prompts[:4]]
            return first, replay

        first, replay = asyncio.run(drill())
        eng.close()
        wrong = sum(1 for a, b in zip(first, replay) if a != b)
        trips = integrity_mod.counters()["kv_integrity_failures_total"]
        return {
            "decode_tps_integrity_on": tps_on,
            "decode_tps_integrity_off": tps_off,
            "overhead_ratio": round(tps_off / max(tps_on, 1e-9), 3),
            "wall_on_s": wall_on, "wall_off_s": wall_off,
            "corrupt_drill": {
                "replayed_streams": len(replay),
                "wrong_streams": wrong,  # MUST be 0
                "integrity_trips": trips,
            },
        }
    finally:
        if prior_knob is None:
            os.environ.pop("DYN_TPU_KV_INTEGRITY", None)
        else:
            os.environ["DYN_TPU_KV_INTEGRITY"] = prior_knob
        integrity_mod.reset_for_tests()


def bench_migration() -> dict:
    """Live in-flight migration vs resume-only drain (docs/resilience.md
    §Live migration; tiny REAL engines on the host platform — the point is
    KV pages actually moving over the transfer plane). Two legs at
    identical load: a control where a draining worker is stopped and its
    streams recover via the PR10 resume path (full prompt+generated
    recompute on a sibling), and a migrate leg where the drain ships each
    stream's KV to a sibling first. Reports recomputed prefill tokens,
    worst per-stream gap p95, KV bytes moved, and the drain wall-clock.
    BENCH_MIGRATE=0 skips."""
    import asyncio
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.disagg import migration as mig_mod
    from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
    from dynamo_tpu.models.llama import LLAMA_PRESETS, init_params
    from dynamo_tpu.runtime import resilience
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.engine import Context
    from dynamo_tpu.runtime.resilience import ResiliencePolicy
    from dynamo_tpu.runtime.statestore import StateStoreServer

    n_requests = int(os.environ.get("BENCH_MIGRATE_REQUESTS", "6"))
    gen_tokens = int(os.environ.get("BENCH_MIGRATE_TOKENS", "48"))
    prompt_len = int(os.environ.get("BENCH_MIGRATE_PROMPT", "96"))

    cfg = dataclasses.replace(LLAMA_PRESETS["tiny"], dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    block_bytes = None  # filled from the first extract

    async def leg(migrate: bool) -> dict:
        resilience.reset_resume_counters()
        mig_mod.reset_migration_counters()
        os.environ["DYN_TPU_MIGRATE"] = "1" if migrate else "0"
        ss = StateStoreServer(port=0)
        await ss.start()
        rts, engines, coords = [], [], []
        for _ in range(3):
            rt = await DistributedRuntime.create(ss.url, "127.0.0.1:1")
            eng = JaxServingEngine(cfg, params, EngineConfig(
                max_slots=8, kv_block_size=8,
                max_model_len=prompt_len + gen_tokens + 16,
            ))
            ep = rt.namespace("bmig").component("w").endpoint("gen")
            await ep.serve(eng)
            if migrate:
                coords.append(await mig_mod.attach_migration(ep, eng))
            rts.append(rt)
            engines.append(eng)
        fe = await DistributedRuntime.create(ss.url, "127.0.0.1:1")
        client = await fe.namespace("bmig").component("w").endpoint(
            "gen"
        ).client("round_robin", policy=ResiliencePolicy(
            request_timeout=120.0, connect_timeout=2.0, max_attempts=4,
            backoff_base=0.01, backoff_max=0.05, resume_attempts=2, seed=3,
        ))
        await client.wait_for_instances(3, timeout=10)
        stream_max_gap: list = []
        failures: list = []

        async def one(i: int) -> None:
            ctx = Context({
                "token_ids": [((i * 131 + j * 17) % 1000) + 3
                              for j in range(prompt_len)],
                "stop_conditions": {"max_tokens": gen_tokens,
                                    "ignore_eos": True},
                "sampling_options": {"temperature": 0.0},
            })
            last = None
            worst = 0.0
            async for item in client.generate(ctx):
                if item.is_error:
                    failures.append(item.error_message())
                    return
                now = time.perf_counter()
                if last is not None:
                    worst = max(worst, now - last)
                last = now
            stream_max_gap.append(worst)

        t0 = time.perf_counter()
        tasks = [asyncio.create_task(one(i)) for i in range(n_requests)]
        # the moment worker 0 is mid-DECODE (tokens generated, streams
        # live), drain it; the control leg stops it instead (bounded
        # maintenance window → PR10 resume recovers with a full history
        # recompute). Mid-decode matters: a pre-first-token stop would be
        # absorbed by plain failover, which recomputes nothing to measure.
        for _ in range(800):
            await asyncio.sleep(0.01)
            if (engines[0].live_request_count()
                    and engines[0].total_generated_tokens >= 4):
                break
        drain_t0 = time.perf_counter()
        rts[0].set_draining(True)
        if migrate:
            while engines[0].live_request_count():
                await asyncio.sleep(0.02)
                if time.perf_counter() - drain_t0 > 60:
                    break
        else:
            await rts[0]._rpc_server.stop(drain_timeout=0.01)
        drain_s = time.perf_counter() - drain_t0
        await asyncio.gather(*tasks)
        wall = time.perf_counter() - t0
        recompute = sum(
            e.metrics_snapshot()["resume_recompute_tokens"] for e in engines
        )
        m_ok, m_bad, m_blocks = mig_mod.migration_counters()
        kv_bytes = 0
        if m_blocks:
            # one block = [L, bs, KVH, D] for k and v in the engine dtype
            e = engines[1]
            per = (
                2 * cfg.num_layers * e.config.kv_block_size
                * cfg.num_kv_heads * cfg.head_dim
                * jnp.dtype(cfg.dtype).itemsize
            )
            kv_bytes = m_blocks * per
        out = {
            "wall_s": round(wall, 3),
            "drain_s": round(drain_s, 3),
            "failures": len(failures),
            "recomputed_prefill_tokens": int(recompute),
            "resumes": client.stats["resumes"],
            "migrations": client.stats["migrations"],
            "migrations_failed": m_bad,
            "kv_blocks_moved": m_blocks,
            "kv_bytes_moved": int(kv_bytes),
            "worst_gap_p95_ms": round(float(np.percentile(
                np.asarray(stream_max_gap or [0.0]) * 1e3, 95
            )), 3),
        }
        await client.close()
        for rt in rts + [fe]:
            await rt.shutdown()
        for e in engines:
            e.close()
        await ss.stop()
        os.environ.pop("DYN_TPU_MIGRATE", None)
        return out

    control = asyncio.run(leg(migrate=False))
    migrated = asyncio.run(leg(migrate=True))
    return {
        "scenario": (
            f"{n_requests} streams x {prompt_len}-token prompts x "
            f"{gen_tokens} generated on 3 tiny real engines; worker 0 "
            f"drained mid-decode (control: stopped → resume recompute; "
            f"migrate: KV shipped to siblings)"
        ),
        "control_resume": control,
        "migrate": migrated,
        "recompute_saved_tokens": (
            control["recomputed_prefill_tokens"]
            - migrated["recomputed_prefill_tokens"]
        ),
    }


def bench_blackout() -> dict:
    """Control-plane blackout tolerance (docs/resilience.md §Control-plane
    blackout; no TPU — deterministic token engines over the real statestore
    + bus + RPC planes). Two legs at identical 2x load: a control with a
    healthy control plane, and a blackout leg where the statestore AND bus
    are stopped mid-run for ~a third of the wall time, then restarted
    EMPTY (worst case: every lease and key gone). Reports served tok/s and
    ITL p95 during the outage window vs control, plus time-to-reconverge:
    how long after the store restart until every worker re-registered
    under a fresh lease. BENCH_BLACKOUT=0 skips."""
    import asyncio

    import numpy as np

    from dynamo_tpu.runtime.annotated import Annotated
    from dynamo_tpu.runtime.bus import MessageBusServer
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.engine import AsyncEngine, Context
    from dynamo_tpu.runtime.resilience import ResiliencePolicy
    from dynamo_tpu.runtime.statestore import StateStoreServer

    n_requests = int(os.environ.get("BENCH_BLACKOUT_REQUESTS", "24"))
    gen_tokens = int(os.environ.get("BENCH_BLACKOUT_TOKENS", "120"))
    outage_s = float(os.environ.get("BENCH_BLACKOUT_OUTAGE_S", "3.0"))
    lease_ttl = float(os.environ.get("BENCH_BLACKOUT_LEASE_TTL", "1.0"))
    token_delay = 0.004
    os.environ.setdefault("DYN_TPU_REJOIN_JITTER", "1.0")
    os.environ.setdefault("DYN_TPU_STALE_GRACE", "5.0")

    class TokenEngine(AsyncEngine):
        async def generate(self, request: Context):
            req = request.data
            toks = list(req["token_ids"])
            for _ in range(int(req["stop_conditions"]["max_tokens"])):
                if request.context.is_stopped:
                    return
                toks.append((toks[-1] * 31 + len(toks) * 7 + 13) % 50021)
                yield Annotated.from_data({"token_ids": [toks[-1]]})
                await asyncio.sleep(token_delay)
            yield Annotated.from_data(
                {"token_ids": [], "finish_reason": "length"}
            )

    async def leg(blackout: bool) -> dict:
        ss = StateStoreServer(port=0)
        await ss.start()
        bus = MessageBusServer(port=0)
        await bus.start()
        ss_port, bus_port = ss.port, bus.port
        rts = []
        for _ in range(3):
            rt = await DistributedRuntime.create(ss.url, bus.url)
            ep = rt.namespace("bbo").component("w").endpoint("gen")
            await ep.serve(
                TokenEngine(), lease=await rt.store.grant_lease(ttl=lease_ttl)
            )
            rts.append(rt)
        fe = await DistributedRuntime.create(ss.url, bus.url)
        client = await fe.namespace("bbo").component("w").endpoint(
            "gen"
        ).client("round_robin", policy=ResiliencePolicy(
            request_timeout=120.0, connect_timeout=2.0, max_attempts=4,
            backoff_base=0.01, backoff_max=0.05, seed=3,
        ))
        await client.wait_for_instances(3, timeout=10)
        window: dict = {"t0": None, "t1": None}
        gaps_out: list = []  # inter-token gaps inside the outage window
        gaps_all: list = []
        tokens_out = [0]
        errors = [0]

        last_token_t = [0.0]

        async def one(i: int) -> None:
            ctx = Context({
                "token_ids": [11 + i, 17 + 2 * i],
                "stop_conditions": {"max_tokens": gen_tokens},
                "sampling_options": {"temperature": 0.0},
            })
            last = None
            async for item in client.generate(ctx):
                if item.is_error:
                    errors[0] += 1
                    continue
                now = time.perf_counter()
                last_token_t[0] = max(last_token_t[0], now)
                in_window = (
                    window["t0"] is not None
                    and now >= window["t0"]
                    and (window["t1"] is None or now <= window["t1"])
                )
                if in_window:
                    tokens_out[0] += 1
                if last is not None:
                    gaps_all.append(now - last)
                    if in_window:
                        gaps_out.append(now - last)
                last = now

        async def chaos() -> float:
            await asyncio.sleep(0.3)
            window["t0"] = time.perf_counter()
            if blackout:
                await ss.stop()
                await bus.stop()
            await asyncio.sleep(outage_s)
            # the measured window is the dark time only; reconvergence after
            # the restart is reported separately
            window["t1"] = time.perf_counter()
            reconverge = 0.0
            if blackout:
                ss2 = StateStoreServer("127.0.0.1", ss_port)  # restart EMPTY
                await ss2.start()
                bus2 = MessageBusServer("127.0.0.1", bus_port)
                await bus2.start()
                restart_t = time.perf_counter()
                # reconvergence: all 3 workers re-registered (fresh leases)
                from dynamo_tpu.runtime.statestore import StateStoreClient

                probe = await StateStoreClient.connect(ss2.url)
                while len(await probe.get_prefix(
                    "bbo/components/w/endpoints/gen/instances/"
                )) < 3:
                    await asyncio.sleep(0.05)
                await probe.close()
                reconverge = time.perf_counter() - restart_t
                chaos.servers = (ss2, bus2)  # type: ignore[attr-defined]
            return reconverge

        t0 = time.perf_counter()
        chaos_task = asyncio.create_task(chaos())
        await asyncio.gather(*[one(i) for i in range(n_requests)])
        reconverge_s = await chaos_task
        wall = time.perf_counter() - t0
        await client.close()
        for rt in rts + [fe]:
            await rt.shutdown()
        for srv in getattr(chaos, "servers", ()):  # the restarted planes
            await srv.stop()
        if not blackout:
            await ss.stop()
            await bus.stop()
        arr_out = np.asarray(gaps_out or [0.0]) * 1e3
        # the throughput window is the overlap of the outage and the
        # traffic: if the streams drained before the planes came back, the
        # traffic-free tail must not dilute tok/s for both legs
        w_end = min(window["t1"], max(last_token_t[0], window["t0"]))
        return {
            "wall_s": round(wall, 3),
            "errors": errors[0],
            "outage_window_s": round(window["t1"] - window["t0"], 3),
            "outage_traffic_overlap_s": round(w_end - window["t0"], 3),
            "outage_tok_s": round(
                tokens_out[0] / max(w_end - window["t0"], 1e-9), 1
            ),
            "outage_itl_p95_ms": round(float(np.percentile(arr_out, 95)), 3),
            "reconverge_s": round(reconverge_s, 3),
        }

    control = asyncio.run(leg(blackout=False))
    dark = asyncio.run(leg(blackout=True))
    return {
        "scenario": (
            f"{n_requests} concurrent streams x {gen_tokens} tokens on 3 "
            f"workers; blackout leg kills statestore+bus for {outage_s}s "
            f"mid-run and restarts them EMPTY (lease ttl {lease_ttl}s)"
        ),
        "control": control,
        "blackout": dark,
        "outage_tok_s_ratio": round(
            dark["outage_tok_s"] / max(control["outage_tok_s"], 1e-9), 4
        ),
        "added_outage_itl_p95_ms": round(
            dark["outage_itl_p95_ms"] - control["outage_itl_p95_ms"], 3
        ),
        "reconverge_s": dark["reconverge_s"],
    }


def bench_profiling() -> dict:
    """The profiling plane's tax and its books (docs/observability.md
    §Profiling; tiny REAL engine on the host platform). Legs 1/2:
    identical decode load with DYN_TPU_PROFILE off vs on (default
    sampling) — the on/off tok/s ratio IS the steady-state overhead the
    acceptance bounds at <2% on chips. Leg 3: a sample-every-dispatch
    capture whose decode device+host split must cover the sampled wall
    span (the ±10% books check `llmctl profile capture` relies on).
    BENCH_PROFILING=0 skips."""
    import asyncio
    import dataclasses

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
    from dynamo_tpu.models.llama import LLAMA_PRESETS, init_params
    from dynamo_tpu.runtime import profiling as profiling_mod
    from dynamo_tpu.runtime.engine import Context

    n_requests = int(os.environ.get("BENCH_PROFILING_REQUESTS", "8"))
    gen_tokens = int(os.environ.get("BENCH_PROFILING_TOKENS", "96"))
    prompt_len = int(os.environ.get("BENCH_PROFILING_PROMPT", "64"))
    # restore the CALLER's knobs afterwards (the bench_integrity pattern):
    # a user benching with DYN_TPU_PROFILE=1 must not have later sections
    # silently lose their profiling because this one popped the var
    prior = {
        k: os.environ.get(k)
        for k in ("DYN_TPU_PROFILE", "DYN_TPU_PROFILE_SAMPLE")
    }

    cfg = dataclasses.replace(LLAMA_PRESETS["tiny"], dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = [
        [(7 * i + 3 + j) % 101 for j in range(prompt_len)]
        for i in range(n_requests)
    ]

    async def collect(eng, toks):
        out = []
        async for item in eng.generate(Context({
            "token_ids": list(toks),
            "stop_conditions": {"max_tokens": gen_tokens,
                                "ignore_eos": True},
            "sampling_options": {"temperature": 0.0},
        })):
            if item.is_error:
                raise RuntimeError(item.error_message())
            out.extend((item.data or {}).get("token_ids", []))
        return out

    def leg(profile: bool, sample: str = "") -> tuple:
        if profile:
            os.environ["DYN_TPU_PROFILE"] = "1"
        else:
            os.environ.pop("DYN_TPU_PROFILE", None)
        if sample:
            os.environ["DYN_TPU_PROFILE_SAMPLE"] = sample
        else:
            os.environ.pop("DYN_TPU_PROFILE_SAMPLE", None)
        profiling_mod.reset_for_tests()
        eng = JaxServingEngine(cfg, params, EngineConfig(
            max_slots=4, kv_block_size=8,
            max_model_len=prompt_len + gen_tokens + 16,
        ))

        async def run_all():
            await collect(eng, prompts[0])  # warm the compiles out
            t0 = time.perf_counter()
            outs = await asyncio.gather(
                *[collect(eng, p) for p in prompts]
            )
            return outs, time.perf_counter() - t0

        outs, wall = asyncio.run(run_all())
        eng.close()
        toks = sum(len(o) for o in outs)
        return round(toks / wall, 1), round(wall, 3)

    try:
        tps_off, wall_off = leg(False)
        tps_on, wall_on = leg(True)  # default sampling stride

        # books leg: sample EVERY dispatch, then audit the decode split
        tps_full, _ = leg(True, sample="1")
        tl = profiling_mod.maybe_timeline()
        summary = tl.summary() if tl is not None else {}
        recs = [
            r for r in (tl.records() if tl is not None else [])
            if r["phase"] == "decode"
        ]
        coverage = None
        if len(recs) >= 8:
            # consecutive-step pairs: the split must fill the gap between
            # adjacent sampled dispatches (the ±10% acceptance check)
            recs.sort(key=lambda r: r["ts"])
            spans = busy = 0.0
            for a, b in zip(recs, recs[1:]):
                if b["step"] - a["step"] != 1:
                    continue
                gap = b["ts"] - a["ts"]
                if gap <= 0:
                    continue
                spans += gap
                busy += (a["host_us"] + a["device_us"] + a["post_us"]) / 1e6
            coverage = round(busy / spans, 4) if spans > 0 else None
        dec = (summary.get("phases") or {}).get("decode") or {}
        return {
            "decode_tps_profile_off": tps_off,
            "decode_tps_profile_on": tps_on,
            "overhead_ratio": round(tps_off / max(tps_on, 1e-9), 3),
            "decode_tps_sample_every": tps_full,
            "wall_off_s": wall_off, "wall_on_s": wall_on,
            "device_us_p95": dec.get("device_us_p95"),
            "host_us_p95": dec.get("host_us_p95"),
            "device_idle_frac": summary.get("device_idle_frac"),
            # host+device+post split over adjacent sampled dispatch gaps —
            # MUST sit in [0.9, 1.02] for the capture to be trustworthy
            "split_wall_coverage": coverage,
        }
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        profiling_mod.reset_for_tests()


def bench_straggler() -> dict:
    """Fail-slow defense: the detector's tax and the defense's payoff
    (docs/resilience.md §Fail-slow; tiny REAL engines on the host
    platform). Overhead legs: identical single-engine decode load with
    DYN_TPU_STRAGGLER off vs on — the off/on tok/s ratio is the
    detector's steady-state tax (two perf_counter reads + one EWMA
    update per dispatch; the acceptance pins it ~1.0). Defense legs: a
    3-worker fleet with one worker dragged by an injected "slow"
    dispatch fault, undefended (plane off) vs defended (the telemetry
    aggregator's arbiter judges the worker suspect and clients
    soft-demote it); reports each leg's post-verdict fleet p95
    inter-token gap and their ratio. BENCH_STRAGGLER=0 skips."""
    import asyncio
    import contextlib
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.components.telemetry_aggregator import (
        run_telemetry_aggregator,
    )
    from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
    from dynamo_tpu.models.llama import LLAMA_PRESETS, init_params
    from dynamo_tpu.runtime import faults as faults_mod
    from dynamo_tpu.runtime import straggler as straggler_mod
    from dynamo_tpu.runtime.bus import MessageBusServer
    from dynamo_tpu.runtime.distributed import (
        DistributedRuntime,
        attach_kv_publishing,
    )
    from dynamo_tpu.runtime.engine import Context
    from dynamo_tpu.runtime.faults import FaultInjector, FaultRule
    from dynamo_tpu.runtime.statestore import StateStoreServer

    n_requests = int(os.environ.get("BENCH_STRAGGLER_REQUESTS", "6"))
    gen_tokens = int(os.environ.get("BENCH_STRAGGLER_TOKENS", "64"))
    prompt_len = int(os.environ.get("BENCH_STRAGGLER_PROMPT", "32"))
    # per-dispatch fixed delay on the victim: ~3-6x a tiny engine's host
    # decode step, a clean differential signal without minutes of wall
    slow_s = float(os.environ.get("BENCH_STRAGGLER_SLOW_S", "0.03"))
    prior = {
        k: os.environ.get(k)
        for k in (
            straggler_mod.ENV_STRAGGLER, straggler_mod.ENV_FACTOR,
            straggler_mod.ENV_WINDOW, straggler_mod.ENV_MIN_PEERS,
            straggler_mod.ENV_TRIPS, "DYN_TPU_HEALTH_CHECK_INTERVAL",
            "DYN_TPU_LOAD_REPORT_INTERVAL",
        )
    }

    cfg = dataclasses.replace(LLAMA_PRESETS["tiny"], dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = [
        [(11 * i + 5 + j) % 97 for j in range(prompt_len)]
        for i in range(2 * n_requests + 1)
    ]

    def _ctx(toks) -> Context:
        return Context({
            "token_ids": list(toks),
            "stop_conditions": {"max_tokens": gen_tokens,
                                "ignore_eos": True},
            "sampling_options": {"temperature": 0.0},
        })

    async def collect(gen_fn, toks, gaps=None):
        out, last = [], None
        async for item in gen_fn(_ctx(toks)):
            if item.is_error:
                raise RuntimeError(item.error_message())
            ids = (item.data or {}).get("token_ids", [])
            if ids:
                now = time.perf_counter()
                if gaps is not None and last is not None:
                    gaps.append(now - last)
                last = now
                out.extend(ids)
        return out

    # -- overhead legs: the detector's per-dispatch tax --------------------

    def overhead_leg(on: bool) -> float:
        if on:
            os.environ[straggler_mod.ENV_STRAGGLER] = "1"
        else:
            os.environ.pop(straggler_mod.ENV_STRAGGLER, None)
        straggler_mod.reset_for_tests()
        eng = JaxServingEngine(cfg, params, EngineConfig(
            max_slots=4, kv_block_size=8,
            max_model_len=prompt_len + gen_tokens + 16,
        ))

        async def run_all():
            await collect(eng.generate, prompts[0])  # warm the compiles
            t0 = time.perf_counter()
            outs = await asyncio.gather(
                *[collect(eng.generate, p) for p in prompts[1:n_requests + 1]]
            )
            return outs, time.perf_counter() - t0

        outs, wall = asyncio.run(run_all())
        eng.close()
        return round(sum(len(o) for o in outs) / wall, 1)

    # -- defense legs: one dragged worker, soft-demotion on vs off ---------

    async def fleet_leg(defended: bool) -> dict:
        if defended:
            os.environ[straggler_mod.ENV_STRAGGLER] = "1"
            os.environ[straggler_mod.ENV_FACTOR] = "3.0"
            os.environ[straggler_mod.ENV_WINDOW] = "0.5"
            # park the verdict at suspect: the bench measures the
            # soft-demotion payoff; the confirmed-tier migrate-off drill
            # is the chaos gate's job (tests/test_straggler.py)
            os.environ[straggler_mod.ENV_TRIPS] = "99"
        else:
            os.environ.pop(straggler_mod.ENV_STRAGGLER, None)
        os.environ["DYN_TPU_HEALTH_CHECK_INTERVAL"] = "0.1"
        os.environ["DYN_TPU_LOAD_REPORT_INTERVAL"] = "0.1"
        straggler_mod.reset_for_tests()
        ss = StateStoreServer(port=0)
        await ss.start()
        bus = MessageBusServer(port=0)
        await bus.start()
        agg = await DistributedRuntime.create(ss.url, bus.url)
        ready = asyncio.Event()
        agg_task = asyncio.create_task(run_telemetry_aggregator(
            agg, "bstrag", port=0, host="127.0.0.1", ready=ready,
            register=False,
        ))
        await asyncio.wait_for(ready.wait(), 10)
        rts, engines = [], []
        for _ in range(3):
            rt = await DistributedRuntime.create(ss.url, bus.url)
            eng = JaxServingEngine(cfg, params, EngineConfig(
                max_slots=4, kv_block_size=8,
                max_model_len=prompt_len + gen_tokens + 16,
            ))
            if defended:
                # one process hosts the whole bench fleet, but the
                # detector is process-global (one engine per process in
                # production): give each worker a private detector so the
                # victim's EWMA actually diverges from its peers'
                eng._straggler = straggler_mod.StragglerDetector()
            ep = rt.namespace("bstrag").component("w").endpoint("gen")
            await ep.serve(eng)
            await attach_kv_publishing(ep, eng, interval=0.1)
            rts.append(rt)
            engines.append(eng)
        if defended:
            # the verdict latch is process-global too: freeze the
            # siblings' monitors (at healthy) so only the victim's health
            # plane mirrors the latched verdict
            for rt in rts[1:]:
                await rt._health_monitor.stop()
        fe = await DistributedRuntime.create(ss.url, bus.url)
        client = await fe.namespace("bstrag").component("w").endpoint(
            "gen"
        ).client("round_robin")
        await client.wait_for_instances(3, timeout=10)
        victim = rts[0].worker_id
        inj = FaultInjector([FaultRule(
            plane="engine", point="dispatch", action="slow",
            match_addr=victim, delay=slow_s, jitter=slow_s / 3,
        )])
        gaps: list = []
        try:
            # warm every engine's compiles before the fault lands
            await asyncio.gather(
                *[collect(e.generate, prompts[0]) for e in engines]
            )
            faults_mod.install(inj)
            # load wave: spreads over all three workers, feeds the
            # victim's dragged EWMA into the metrics stream
            load = [
                asyncio.create_task(collect(client.generate, p))
                for p in prompts[1:n_requests + 1]
            ]
            if defended:
                deadline = asyncio.get_running_loop().time() + 10.0
                while (straggler_mod.verdict() == straggler_mod.OK
                       and asyncio.get_running_loop().time() < deadline):
                    await asyncio.sleep(0.05)
            else:
                await asyncio.sleep(1.5)  # the defended leg's verdict wait
            # measured wave: post-verdict admissions — the defended router
            # soft-demotes the victim, the undefended one keeps feeding it
            t0 = time.perf_counter()
            await asyncio.gather(*[
                collect(client.generate, p, gaps=gaps)
                for p in prompts[n_requests + 1:2 * n_requests + 1]
            ])
            wall = time.perf_counter() - t0
            await asyncio.gather(*load)
            return {
                "itl_p95_ms": round(float(np.percentile(
                    np.asarray(gaps or [0.0]) * 1e3, 95
                )), 2),
                "wall_s": round(wall, 3),
                "verdict_seen": straggler_mod.verdict(),
            }
        finally:
            faults_mod.uninstall()
            await client.close()
            for rt in rts + [fe]:
                await rt.shutdown()
            for e in engines:
                e.close()
            agg_task.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await agg_task
            await agg.shutdown()
            await bus.stop()
            await ss.stop()

    try:
        tps_off = overhead_leg(False)
        tps_on = overhead_leg(True)
        undefended = asyncio.run(fleet_leg(defended=False))
        defended = asyncio.run(fleet_leg(defended=True))
        return {
            "decode_tps_straggler_off": tps_off,
            "decode_tps_straggler_on": tps_on,
            "overhead_ratio": round(tps_off / max(tps_on, 1e-9), 3),
            "undefended": undefended,
            "defended": defended,
            # defended/undefended post-verdict fleet p95 ITL: the payoff
            # headline (<1 means the soft-demotion actually routed load
            # off the dragged worker)
            "defense_itl_p95_ratio": round(
                defended["itl_p95_ms"]
                / max(undefended["itl_p95_ms"], 1e-9), 3,
            ),
            "slow_fault_s": slow_s,
        }
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        straggler_mod.reset_for_tests()


# ---------------------------------------------------------------------------
# machine-readable summary + CI regression gate (BENCH_SUMMARY.json)
# ---------------------------------------------------------------------------

# tracked metrics: (summary name, path into the bench JSON, direction).
# Only metrics PRESENT in both baseline and current runs are compared, so
# skipped sections (BENCH_*=0) never fail the gate.
SUMMARY_SPECS = [
    ("tok_s_per_chip", ("value",), "higher"),
    ("roofline_fraction", ("roofline_fraction",), "higher"),
    ("overall_fraction", ("overall_fraction",), "higher"),
    ("mfu", ("mfu",), "higher"),
    ("ttft_p50_ms", ("ttft_p50_ms",), "lower"),
    ("ttft_p95_ms", ("ttft_p95_ms",), "lower"),
    ("itl_p95_ms", ("itl_p95_ms",), "lower"),
    ("frontend_tok_s", ("frontend", "frontend_tok_s"), "higher"),
    ("frontend_cpu_us_per_token",
     ("frontend", "frontend_cpu_us_per_token"), "lower"),
    ("spec_speedup", ("spec_decode", "speedup"), "higher"),
    ("integrity_overhead_ratio",
     ("integrity", "overhead_ratio"), "lower"),
    ("profiling_overhead_ratio",
     ("profiling", "overhead_ratio"), "lower"),
    ("profiling_split_coverage",
     ("profiling", "split_wall_coverage"), "higher"),
    ("migration_kv_blocks_moved",
     ("migration", "migrate", "kv_blocks_moved"), "higher"),
    ("blackout_outage_tok_s_ratio",
     ("blackout", "outage_tok_s_ratio"), "higher"),
    ("straggler_overhead_ratio",
     ("straggler", "overhead_ratio"), "lower"),
    ("straggler_defense_itl_ratio",
     ("straggler", "defense_itl_p95_ratio"), "lower"),
]


def build_bench_summary(out: dict) -> dict:
    """Flatten a bench JSON into the tracked-metric summary shape
    ``bench.py --check`` compares (written beside the full output as
    BENCH_SUMMARY.json)."""
    metrics = {}
    for name, path, better in SUMMARY_SPECS:
        node = out
        for key in path:
            if not isinstance(node, dict) or key not in node:
                node = None
                break
            node = node[key]
        if isinstance(node, bool) or not isinstance(node, (int, float)):
            continue
        metrics[name] = {"value": float(node), "better": better}
    return {
        "schema": 1,
        "model": out.get("model"),
        "quantize": out.get("quantize"),
        "chips": out.get("chips"),
        "metrics": metrics,
    }


def check_bench_summary(
    baseline: dict, current: dict, tolerance: float = 0.15
) -> list:
    """Compare two summaries; returns the regressions as
    ``[(metric, base, cur, ratio)]``. A tracked metric regressed when it
    moved past ``tolerance`` in its bad direction; metrics missing from
    either side are skipped (a section the baseline never ran can't
    regress)."""
    base_m = baseline.get("metrics") or {}
    cur_m = current.get("metrics") or {}
    regressions = []
    for name, base in base_m.items():
        cur = cur_m.get(name)
        if cur is None:
            continue
        bv, cv = float(base["value"]), float(cur["value"])
        if bv == 0:
            continue
        ratio = cv / bv
        better = base.get("better", "higher")
        if better == "higher" and ratio < 1.0 - tolerance:
            regressions.append((name, bv, cv, round(ratio, 4)))
        elif better == "lower" and ratio > 1.0 + tolerance:
            regressions.append((name, bv, cv, round(ratio, 4)))
    return regressions


def write_bench_summary(out: dict) -> str:
    path = os.environ.get("BENCH_SUMMARY_PATH", "BENCH_SUMMARY.json")
    with open(path, "w") as f:
        json.dump(build_bench_summary(out), f, indent=2, sort_keys=True)
    return path


def run_check(argv: list) -> int:
    """``bench.py --check BASELINE.json [--summary BENCH_SUMMARY.json]
    [--tolerance 0.15]``: the CI-scriptable perf gate — compares an
    existing summary against a baseline WITHOUT running the bench (no
    jax import), exit 2 on any tracked metric regressing past the
    tolerance, 1 on unreadable inputs. A baseline holding a full bench
    JSON (no "metrics" key) is summarized on the fly, so any historical
    BENCH_rNN.json works as a baseline."""
    try:
        baseline_path = argv[argv.index("--check") + 1]
        summary_path = "BENCH_SUMMARY.json"
        if "--summary" in argv:
            summary_path = argv[argv.index("--summary") + 1]
        tolerance = float(os.environ.get("BENCH_CHECK_TOLERANCE", "0.15"))
        if "--tolerance" in argv:
            tolerance = float(argv[argv.index("--tolerance") + 1])
    except (IndexError, ValueError) as e:
        # a malformed invocation must exit 1 like unreadable inputs — a CI
        # script keying on exit 2 = regression must not see a traceback
        print(
            f"bench --check usage: bench.py --check BASELINE.json "
            f"[--summary BENCH_SUMMARY.json] [--tolerance 0.15] ({e})",
            file=sys.stderr,
        )
        return 1
    try:
        with open(baseline_path) as f:
            baseline = json.load(f)
        with open(summary_path) as f:
            current = json.load(f)
    except (OSError, ValueError) as e:
        print(f"bench --check: cannot read inputs: {e}", file=sys.stderr)
        return 1
    if "metrics" not in baseline:
        baseline = build_bench_summary(baseline)
    if "metrics" not in current:
        current = build_bench_summary(current)
    regressions = check_bench_summary(baseline, current, tolerance)
    compared = sorted(
        set(baseline.get("metrics") or {}) & set(current.get("metrics") or {})
    )
    if regressions:
        print(f"REGRESSION: {len(regressions)} tracked metric(s) moved "
              f">{tolerance:.0%} the wrong way (of {len(compared)} "
              f"compared):")
        for name, bv, cv, ratio in regressions:
            print(f"  {name:32s} {bv:g} -> {cv:g}  (x{ratio})")
        return 2
    print(f"ok: {len(compared)} tracked metric(s) within {tolerance:.0%} "
          f"of {baseline_path}")
    return 0


def main() -> None:
    from dynamo_tpu.engine_jax.compile_cache import enable_compile_cache

    enable_compile_cache()
    _require_tpu()
    if MODE == "multiturn":
        bench_multiturn()
        return

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.models.llama import LLAMA_PRESETS
    from dynamo_tpu.runtime.engine import Context

    n_chips = len(jax.devices())
    cfg = dataclasses.replace(LLAMA_PRESETS[PRESET], dtype=jnp.bfloat16)
    params = _init_params_fast(cfg)
    mesh = None
    if BENCH_TP > 1:
        # sharded serving bench (the first-real-multi-chip runbook,
        # docs/multihost_serving.md): tp mesh over the local chips
        from dynamo_tpu.models.llama import param_shardings
        from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(tp=BENCH_TP))
        params = jax.device_put(params, param_shardings(cfg, mesh))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))

    engine_cfg = EngineConfig(
        max_slots=MAX_SLOTS,
        kv_block_size=16,
        max_model_len=max(256, PROMPT_LEN + GEN_TOKENS + 8),
        decode_steps=DECODE_STEPS,
        prefill_chunk=min(256, PROMPT_LEN),
        quantize=QUANTIZE or None,
    )
    engine = JaxServingEngine(cfg, params, engine_cfg, mesh=mesh)
    # bf16 bytes = the UNQUANTIZED decode ceiling (the classical roofline a
    # bf16 engine can never beat); stream bytes = what this engine's decode
    # actually re-reads per step (the int8 copy under quantize="int8")
    param_bytes = _tree_bytes(engine.params)
    stream_bytes = _tree_bytes(engine.params_decode)
    t0 = time.perf_counter()
    warmup_timings = engine.warmup()
    warmup_s = time.perf_counter() - t0

    rng = np.random.default_rng(0)
    # several independent waves (median reported): host-clock numbers vary
    # run to run, by how much is not measured on the current machine. Every wave gets fresh
    # prompts so nothing hits the prefix cache.
    n_waves = max(1, int(os.environ.get("BENCH_WAVES", "3")))
    waves = [
        [
            rng.integers(0, cfg.vocab_size, PROMPT_LEN).tolist()
            for _ in range(N_REQUESTS)
        ]
        for _ in range(n_waves)
    ]
    # warmup uses its own prompts so the timed set stays prefix-cache-cold
    warm_prompts = [
        rng.integers(0, cfg.vocab_size, PROMPT_LEN).tolist() for _ in range(2)
    ]

    # warm run: touches every dispatch path once, with prompts disjoint from
    # the timed set so no timed request hits the prefix cache
    drive_wave(engine, warm_prompts, GEN_TOKENS)

    # latency-shape bookkeeping starts AFTER warmup: reset the tracing
    # plane's phase histograms so the reported percentiles cover only the
    # timed waves (warmup's first-boot compile would dominate p99 otherwise)
    from dynamo_tpu.runtime import tracing as _tracing

    _tracing.configure()

    # decode phase (inside drive_wave): every lane prefilled → done. This is
    # the steady state the weight-bandwidth roofline describes; the whole-run
    # rate (which also pays prefill+admission) rides along as
    # overall_fraction.
    per_wave = []
    for wave in waves:
        out, elapsed, ttfts, decode_tok_s = drive_wave(engine, wave, GEN_TOKENS)
        per_wave.append((out / elapsed, elapsed, out, ttfts, decode_tok_s))
    # live perf accounting (PR6, runtime/telemetry.py): the gauges a serving
    # worker would publish, snapped before teardown — lets a reader compare
    # the offline roofline numbers below against what the live telemetry
    # plane would have reported for the same run
    engine_perf = {
        k: v for k, v in engine.metrics_snapshot().items()
        if k in ("decode_tokens_per_s", "step_time_ms", "batch_slot_util",
                 "jit_recompiles", "kv_peak_occupancy_perc",
                 "spec_accept_rate", "spec_drafted_tokens",
                 "spec_accepted_tokens", "kv_quantized")
    }
    engine.close()
    del engine  # free the primary engine's HBM before the sections
    params = None
    _release_device_memory()

    # median wave by throughput; its own TTFT distribution rides along
    per_wave.sort(key=lambda w: w[0])
    tok_s, elapsed, total_out, ttfts, decode_tok_s = per_wave[len(per_wave) // 2]
    total_processed = total_out + N_REQUESTS * PROMPT_LEN
    tok_s_chip = tok_s / max(n_chips, 1)

    # weight-bandwidth decode roofline: every step re-reads the params once.
    # roofline_fraction compares the DECODE-PHASE rate against it (the phase
    # the roofline describes — all lanes prefilled, pure token generation);
    # overall_fraction is the whole-run rate (admission + prefill included)
    # against the same roofline.
    roofline_tok_s = MAX_SLOTS * HBM_GBPS * 1e9 / param_bytes
    stream_roofline_tok_s = MAX_SLOTS * HBM_GBPS * 1e9 / stream_bytes
    decode_tok_s_chip = decode_tok_s / max(n_chips, 1)
    mfu = (2.0 * n_params * total_processed / elapsed) / (PEAK_TFLOPS * 1e12 * n_chips)

    out = {
        "metric": "output_tokens_per_s_per_chip",
        "value": round(tok_s_chip, 2),
        "unit": "tok/s/chip",
        "vs_baseline": round(tok_s_chip / roofline_tok_s, 3),
        "model": PRESET,
        "quantize": QUANTIZE or "bf16",
        "chips": n_chips,
        "requests": N_REQUESTS,
        "prompt_len": PROMPT_LEN,
        "gen_tokens": GEN_TOKENS,
        "total_output_tokens": total_out,
        "elapsed_s": round(elapsed, 3),
        "ttft_p50_ms": round(ttfts[len(ttfts) // 2] * 1e3, 1) if ttfts else None,
        "ttft_p95_ms": round(ttfts[int(len(ttfts) * 0.95)] * 1e3, 1) if ttfts else None,
        "hbm_roofline_tok_s": round(roofline_tok_s, 1),
        "decode_tok_s_chip": round(decode_tok_s_chip, 2),
        # roofline_fraction keeps its quantize-aware meaning across rounds:
        # decode-phase rate vs the roofline of the bytes the decode ACTUALLY
        # streams (= bf16 bytes when quantize is off)
        "stream_roofline_tok_s": round(stream_roofline_tok_s, 1),
        "roofline_fraction": round(decode_tok_s_chip / stream_roofline_tok_s, 3),
        "roofline_fraction_basis": (
            "decode-phase tok/s vs the roofline of the streamed weight bytes"
        ),
        # fraction of the bf16 (unquantized-ceiling) decode roofline — what a
        # bf16-weight engine could at BEST do on this chip; the int8 mode
        # passes it by streaming half the bytes
        "bf16_ceiling_fraction": round(decode_tok_s_chip / roofline_tok_s, 3),
        "overall_fraction": round(tok_s_chip / roofline_tok_s, 3),
        "mfu": round(mfu, 4),
        # wall time of the parallel AOT warmup (six variants compile
        # concurrently; cold-boot serial sum is ~4.5x the wall). Per-variant
        # seconds recorded so regressions are attributable.
        "warmup_compile_s": round(warmup_s, 1),
        "warmup_variants": warmup_timings,
        # the live-telemetry view of the same run (empty when DYN_TPU_SLO=0)
        "engine_perf": engine_perf,
    }
    # latency SHAPE from the tracing plane's phase histograms (ttft /
    # inter_token observed by drive_wave, queue_wait / prefill / decode by
    # the engine's own phase spans): the perf trajectory captures p50/p95/
    # p99, not just throughput. Empty when DYN_TPU_TRACE=0.
    phases = _tracing.phase_summary()
    if phases:
        out["phase_latency"] = phases
        ttft_ph = phases.get("ttft", {})
        itl_ph = phases.get("inter_token", {})
        out["ttft_p99_ms"] = ttft_ph.get("p99_ms")
        out["itl_p50_ms"] = itl_ph.get("p50_ms")
        out["itl_p95_ms"] = itl_ph.get("p95_ms")
        out["itl_p99_ms"] = itl_ph.get("p99_ms")
    alt_enabled = os.environ.get(
        "BENCH_ALT_MODE", os.environ.get("BENCH_INT8", "1")
    )
    if alt_enabled == "1":
        alt = "" if QUANTIZE == "int8" else "int8"
        try:
            out["alt_mode"] = bench_alt_mode(alt)
        except Exception as e:  # secondary measurement must never kill the bench
            out["alt_mode"] = {"error": str(e)[:200]}
        _release_device_memory()
    if os.environ.get("BENCH_PALLAS_KERNEL", "1") == "1":
        try:
            out["pallas_kernel"] = bench_pallas_kernel()
        except Exception as e:  # secondary measurement must never kill the bench
            out["pallas_kernel"] = {"error": str(e)[:200]}
        _release_device_memory()
    if os.environ.get("BENCH_PALLAS_D128", "1") == "1":
        try:
            out["pallas_d128"] = bench_pallas_d128()
        except Exception as e:  # secondary measurement must never kill the bench
            out["pallas_d128"] = {"error": str(e)[:200]}
        _release_device_memory()
    if os.environ.get("BENCH_SPEC", "1") == "1":
        try:
            out["spec_decode"] = bench_spec_decode()
        except Exception as e:  # secondary measurement must never kill the bench
            out["spec_decode"] = {"error": str(e)[:200]}
        _release_device_memory()
    if os.environ.get("BENCH_KV_INT8", "1") == "1":
        try:
            out["kv_int8"] = bench_kv_int8()
        except Exception as e:
            out["kv_int8"] = {"error": str(e)[:200]}
        _release_device_memory()
    if os.environ.get("BENCH_FRONTEND", "1") == "1":
        try:
            out["frontend"] = bench_frontend()
        except Exception as e:
            out["frontend"] = {"error": str(e)[:200]}
        _release_device_memory()
    if os.environ.get("BENCH_ISL_SWEEP", "1") == "1":
        try:
            out["isl_sweep"] = bench_isl_sweep()
        except Exception as e:
            out["isl_sweep"] = {"error": str(e)[:200]}
        _release_device_memory()
    if os.environ.get("BENCH_CONCURRENCY", "1") == "1":
        try:
            out["concurrency"] = bench_concurrency()
        except Exception as e:
            out["concurrency"] = {"error": str(e)[:200]}
        _release_device_memory()
    if os.environ.get("BENCH_PLANNER_SIM", "1") == "1":
        try:
            out["planner_sim"] = bench_planner_sim()
        except Exception as e:
            out["planner_sim"] = {"error": str(e)[:200]}
    if os.environ.get("BENCH_QOS", "1") == "1":
        try:
            out["qos"] = bench_qos()
        except Exception as e:
            out["qos"] = {"error": str(e)[:200]}
    if os.environ.get("BENCH_RESUME", "1") == "1":
        try:
            out["resilience"] = bench_resilience()
        except Exception as e:
            out["resilience"] = {"error": str(e)[:200]}
    if os.environ.get("BENCH_BLACKOUT", "1") == "1":
        try:
            out["blackout"] = bench_blackout()
        except Exception as e:
            out["blackout"] = {"error": str(e)[:200]}
    if os.environ.get("BENCH_MIGRATE", "1") == "1":
        try:
            out["migration"] = bench_migration()
        except Exception as e:
            out["migration"] = {"error": str(e)[:200]}
    if os.environ.get("BENCH_INTEGRITY", "1") == "1":
        try:
            out["integrity"] = bench_integrity()
        except Exception as e:
            out["integrity"] = {"error": str(e)[:200]}
    if os.environ.get("BENCH_PROFILING", "1") == "1":
        try:
            out["profiling"] = bench_profiling()
        except Exception as e:
            out["profiling"] = {"error": str(e)[:200]}
    if os.environ.get("BENCH_STRAGGLER", "1") == "1":
        try:
            out["straggler"] = bench_straggler()
        except Exception as e:
            out["straggler"] = {"error": str(e)[:200]}
    # LAST: the largest model's first-boot compilation must not eat the
    # other sections' budget if it times out
    if os.environ.get("BENCH_MODEL_8B", "1") == "1":
        try:
            out["model_8b"] = bench_model_8b()
        except Exception as e:
            out["model_8b"] = {"error": str(e)[:200]}
        _release_device_memory()
    print(json.dumps(out))
    # machine-readable summary for the CI perf gate (bench.py --check):
    # written beside the full JSON, never allowed to kill the bench
    try:
        write_bench_summary(out)
    except OSError as e:
        print(f"(bench summary not written: {e})", file=sys.stderr)


if __name__ == "__main__":
    if "--check" in sys.argv:
        sys.exit(run_check(sys.argv))
    main()
