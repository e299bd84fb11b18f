"""Decode-step ablation profile on real TPU: localize the roofline gap.

``python tools/profile_decode.py history`` times the decode program ALONE at
the served shapes by history profile, the live form beside the full-width one
(:func:`history_profiles`); ``... experts`` times the expert layer ALONE at
``batch.kimi-linear-48b-a3b``'s and ``batch.lfm2-24b-a2b``'s two shapes each
by routing and by grouped product, beside its own roofline
(:func:`expert_profiles`); ``... kda`` times a KDA layer's recurrence ALONE
over a chunk, the scan over the tokens beside the kernel that keeps the state
on the chip (:func:`kda_profiles`); ``... mamba`` times a Mamba layer's
selective scan ALONE over a chunk and over a decode step beside the whole mixer
(:func:`mamba_profiles`); ``... groups`` times ``jamba2-3b``'s chunk program with
its rows in groups of 2, 4 and 8 (:func:`chunk_group_profiles`); ``... mla``
times ONE layer's absorbed latent attention ALONE at ``batch.openpangu-ultra-moe-718b``'s two shapes, beside its
bytes and operations (:func:`mla_profiles`); ``... mhc`` times the residual
path of ONE sublayer of ``xing4.0-29b-a4b`` ALONE, beside the bytes it must
move (:func:`mhc_profiles`); ``... swa`` times ONE window layer's attention of
``trinity-large-preview`` ALONE over its rings at ``long.trinity-large-preview``'s
two shapes (:func:`swa_profiles`); ``... ep`` times ONE expert layer of
``mellum2-12b-a2.5b-tp4`` over the four chips of a host, 16 of 64 experts a
chip, with and without the all-reduce of the chips' partial sums
(:func:`ep_profiles`); without a word, the round-5 ablation below.

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
import shutil
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


# -- the decode program alone, by history profile ------------------------------

def cell_profile(slots: int) -> list:
    """``batch``'s own ragged lengths: the prompt quantiles of
    ``benchmark/traffic.py``, each with half of the answer it is paired with
    (a lane is on average halfway through its answer)."""
    import json

    from benchmark import traffic

    with open(os.path.join(traffic.HERE, "workloads", "batch.qwen2.5-1.5b.json")) as f:
        cell = json.load(f)
    prompts = traffic.lognormal_clipped(cell["prompt_tokens"], traffic.BLOCK, None)
    answers = traffic.lognormal_clipped(cell["output_tokens"], traffic.BLOCK, None)
    return [
        prompts[i % traffic.BLOCK] + answers[traffic.PAIRING[i % traffic.BLOCK]] // 2
        for i in range(slots)
    ]


def history_profiles():
    """One decode dispatch (greedy variant, ``decode_steps`` steps) of
    Qwen2.5-1.5B at the served shapes, timed on the host clock around
    ``block_until_ready`` (median of PROF_ITERS, default 10) at every lane on
    0 / 256 / 1,024 / 1,920 positions of history and at the cell's ragged
    profile: the program the engine serves on one device (live history) beside
    the one a mesh engine keeps (every table's full width).
    With PROF_TRACE=1 each form's ragged dispatches are traced and the op
    events of device 0 counted: the benchmark's tracer has a budget of them
    (PERF.md 7)."""
    from dynamo_tpu.engine_jax.compile_cache import enable_compile_cache
    from dynamo_tpu.models.llama import decode_history_tiles, history_tiles_full

    enable_compile_cache()
    n_iter = int(os.environ.get("PROF_ITERS", "10"))
    cfg = LLAMA_PRESETS["qwen2.5-1.5b"]
    params = init_params(jax.random.PRNGKey(0), cfg)
    ec = EngineConfig(
        max_slots=32, kv_block_size=16, max_model_len=2048, decode_steps=4,
        prefill_chunk=128,
    )
    engine = JaxServingEngine(cfg, params, ec)
    S, MB = ec.max_slots, ec.max_blocks_per_seq
    assert 1 + S * MB <= engine.num_blocks
    tables = jnp.asarray(1 + np.arange(S * MB, dtype=np.int32).reshape(S, MB))
    tokens = jnp.asarray(np.arange(S) % cfg.vocab_size, jnp.int32)
    step_ctr = jnp.asarray(1, jnp.int32)
    ipack = jnp.zeros((2, S), jnp.int32)
    fpack = jnp.asarray(
        np.stack([np.zeros(S), np.ones(S), np.zeros(S), np.zeros(S)]), jnp.float32
    )
    profiles = {f"all {n}": [n] * S for n in (0, 256, 1024, 1920)}
    profiles["ragged"] = cell_profile(S)
    rows = {}
    for form in ("live", "full width"):
        if form == "full width":
            # a mesh engine's program, on a mesh of this one device
            from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

            engine.mesh = make_mesh(MeshConfig(), jax.devices()[:1])
            engine._decode_fns.clear()
        fn = engine._decode(False, False, False)
        # what a decode program costs set-up: its trace and lowering (Python,
        # under the interpreter lock), then its compile, which a warm
        # persistent cache turns into a load
        args = (params, engine.cache, engine._dummy_counts, tokens, tokens,
                tables, step_ctr, ipack, fpack, *engine._wd_args())
        t0 = time.perf_counter()
        lowered = fn.lower(*args)
        t1 = time.perf_counter()
        lowered.compile()
        print(f"{form:10s} lowering {t1 - t0:.2f} s, compile or cache load "
              f"{time.perf_counter() - t1:.2f} s", flush=True)

        def dispatch(base):
            out, _, _, engine.cache, engine._dummy_counts = fn(
                params, engine.cache, engine._dummy_counts, tokens, base,
                tables, step_ctr, ipack, fpack, *engine._wd_args(),
            )
            return out.block_until_ready()

        for name, lengths in profiles.items():
            base = jnp.asarray(lengths, jnp.int32)
            dispatch(base)  # compile, then settle
            dispatch(base)
            times = []
            for _ in range(n_iter):
                t0 = time.perf_counter()
                dispatch(base)
                times.append(time.perf_counter() - t0)
            ms = float(np.median(times)) * 1e3
            read = int(decode_history_tiles(np.asarray(lengths), 16, MB))
            rows.setdefault(name, {})[form] = ms
            print(f"{form:10s} {name:9s} dispatch {ms:7.2f} ms "
                  f"({ms / ec.decode_steps:5.2f} a step); live form reads "
                  f"{read} of {S * history_tiles_full(16, MB)} lane-tiles",
                  flush=True)
        if os.environ.get("PROF_TRACE") == "1":
            from benchmark.trace_reduce import find_xplane, read_xplane

            trace_dir = os.path.join(
                "chiprun_out", "profile_decode", form.replace(" ", "_")
            )
            base = jnp.asarray(profiles["ragged"], jnp.int32)
            jax.profiler.start_trace(trace_dir)
            for _ in range(n_iter):
                dispatch(base)
            jax.profiler.stop_trace()
            dev = read_xplane(find_xplane(trace_dir))["devices"]["0"]
            long = sorted(dev["ops"], key=lambda e: -e[2])[:12]
            print(f"{form}: {len(dev['ops']) / n_iter:.0f} op events a dispatch; "
                  f"module {np.median([m[2] for m in dev['modules']]) / 1e6:.2f} ms")
            for name, _, dur in long:
                print(f"    {dur / 1e3:9.1f} us  {name}")
    for name, by_form in rows.items():
        print(f"{name:9s} full width {by_form['full width']:7.2f} ms -> "
              f"live {by_form['live']:7.2f} ms")
    engine.close()


# -- the expert layer alone, by routing and by grouped product -----------------

def draw_routing(t: int, skew: float, rng, total: int = 256, k: int = 8):
    """``[t, k]`` expert ids: the ``k`` largest of an expert's popularity
    (``skew`` x a standard normal) + Gumbel noise. ``skew`` 0 is even routing
    (64 tokens hit 86.5 % of 128 held experts); 1.5 hits 54 %, which is what
    ``/debug/engine`` reads of the cell's random weights (47-60 %, PERF.md)."""
    scores = skew * rng.standard_normal(total) + rng.gumbel(size=(t, total))
    return np.argsort(-scores, axis=1)[:, :k].astype(np.int32)


def megablox_product(parts, w, schedule, *, rows_per_tile, interpret=False):
    """Candidate (a) as JAX ships it: the parts of a row interleaved in its
    run, ``megablox.gmm`` over ``[P * M, K]``, the parts summed after."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from dynamo_tpu.ops.pallas.grouped_product import columns_per_block

    p, m, k = parts.shape
    sizes = jnp.diff(schedule[0]) * p
    out = gmm(jnp.moveaxis(parts, 0, 1).reshape(m * p, k), w, sizes, jnp.float32,
              (rows_per_tile * p, k, columns_per_block(k, w.shape[2], 2)), interpret=interpret)
    return out.reshape(m, p, -1).sum(axis=1)


def ragged_dot_product(parts, w, schedule, *, rows_per_tile, interpret=False):
    """Candidate (b): ``lax.ragged_dot`` a part."""
    sizes = jnp.diff(schedule[0])
    return sum(jax.lax.ragged_dot(part, w, sizes, preferred_element_type=jnp.float32)
               for part in parts)


def tile_loop_product(parts, w, schedule, *, rows_per_tile, interpret=False):
    """Candidate (c): a loop over the (row tile, expert) visits of the kernel's
    own schedule, each slicing its expert's matrix."""
    offsets, groups, tiles, visits = schedule
    p, m, k = parts.shape
    tm = rows_per_tile

    def visit(i, out):
        g, at = groups[i], tiles[i] * tm
        rows = jax.lax.dynamic_slice_in_dim(parts, at, tm, axis=1).reshape(p * tm, k)
        got = jnp.dot(rows, w[g], preferred_element_type=jnp.float32).reshape(p, tm, -1).sum(axis=0)
        row = at + jnp.arange(tm)
        mine = (row >= offsets[g]) & (row < offsets[g + 1])
        old = jax.lax.dynamic_slice_in_dim(out, at, tm)
        return jax.lax.dynamic_update_slice_in_dim(out, jnp.where(mine[:, None], got, old), at, 0)

    return jax.lax.fori_loop(0, visits, visit, jnp.zeros((m, w.shape[2]), jnp.float32))


def expert_layers() -> dict:
    """The expert layers ``expert_profiles`` times, by configuration: experts
    held and scored, experts a token, E, F, the model's ``parts_of`` (and how
    many bfloat16 parts it makes), a chosen expert's weight, and a chunk
    group's tokens with the valid ones of each row of 128."""
    from dynamo_tpu.models import kimi_linear as kl
    from dynamo_tpu.models import lfm2
    from dynamo_tpu.models import qwen3_next as qn

    return {
        "kimi-linear-48b-a3b": dict(held=128, total=256, k=8, e=2304, f=1024, parts_of=kl._expert_parts,
                                    parts=kl.PASSES, weight=2.446 / 8, chunk=2048, valid_a_row=64),
        "lfm2-24b-a2b": dict(held=64, total=64, k=4, e=2048, f=1536, parts_of=lfm2._expert_parts,
                             parts=lfm2.PARTS, weight=1.0 / 4, chunk=lfm2.ROWS_AT_ONCE * 128, valid_a_row=128),
        "qwen3-next-80b-a3b": dict(held=128, total=512, k=10, e=2048, f=512, parts_of=qn._expert_parts,
                                   parts=qn.PARTS, weight=1.0 / 10, chunk=qn.ROWS_AT_ONCE * 128, valid_a_row=128),
    }


def expert_profiles():
    """``ops/moe.py:dropless_experts`` as a configuration calls it (PROF_MODELS,
    default all: ``kimi-linear-48b-a3b``, 128 experts held of 256, 8 a token,
    E 2,304, F 1,024, three bfloat16 parts; ``lfm2-24b-a2b``, all 64 held, 4 a
    token, E 2,048, F 1,536; ``qwen3-next-80b-a3b``, 128 held of 512, 10 a
    token, E 2,048, F 512: 1.25 rows an expert in a decode step, the thinnest;
    bf16 weights, float32 rows) at the
    cell's two shapes: a decode step's 64 tokens, and a chunk group's (Kimi's
    2,048 of which half are padding, LFM2's 1,024). Each is timed under routing
    drawn even, drawn as Kimi's cell's, and all to ONE expert (a run of
    many tiles: whether the kernel reads an expert once a run or once a tile),
    PROF_ITERS (default 8) layers chained in one dispatch, and printed beside
    the layer's own roofline: the matrices of the experts it reads over the
    chip's bytes a second, or the products of the rows it computes (every part)
    over its bf16 peak, whichever is longer. PROF_PRODUCTS names the grouped
    products to compare (``kernel`` is the tree's; ``megablox``,
    ``ragged_dot``, ``tile_loop``), PROF_TILES the rows a tile to try beside
    the layer's own (``rows_per_tile``: 0)."""
    from benchmark import bytes_and_flops
    from dynamo_tpu.engine_jax.compile_cache import enable_compile_cache
    from dynamo_tpu.ops import moe

    enable_compile_cache()
    peaks = bytes_and_flops.load_peaks(jax.devices()[0].device_kind)
    n_iter = int(os.environ.get("PROF_ITERS", "8"))
    products = {"kernel": moe.grouped_product, "megablox": megablox_product,
                "ragged_dot": ragged_dot_product, "tile_loop": tile_loop_product}
    chosen = os.environ.get("PROF_PRODUCTS", "kernel").split(",")
    tiles = [int(r) for r in os.environ.get("PROF_TILES", "0").split(",")]
    layers = expert_layers()
    own = moe.rows_per_tile

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)).astype(jnp.bfloat16)

    for model in os.environ.get("PROF_MODELS", ",".join(layers)).split(","):
        z = layers[model]
        held, total, k, e, f = z["held"], z["total"], z["k"], z["e"], z["f"]
        key = jax.random.split(jax.random.PRNGKey(0), 4)
        w = (dense(key[0], (held, e, f), e), dense(key[1], (held, e, f), e), dense(key[2], (held, f, e), f))

        def timed(t, ids, valid):
            x = jax.random.normal(key[3], (t, e), jnp.float32)
            weights = jnp.full((t, k), z["weight"], jnp.float32)

            @jax.jit
            def chain(x, ids, weights, valid, w_gate, w_up, w_down):
                def layer(x, _):
                    y, stats = moe.dropless_experts(
                        x, ids, weights, w_gate, w_up, w_down, num_experts_total=total,
                        token_valid=valid, parts_of=z["parts_of"])
                    return x + 1e-3 * y, stats
                return jax.lax.scan(layer, x, None, length=n_iter)

            args = (x, jnp.asarray(ids), weights, jnp.asarray(valid), *w)
            chain(*args)[0].block_until_ready()
            times = []
            for _ in range(5):
                t0 = time.perf_counter()
                out, stats = chain(*args)
                out.block_until_ready()
                times.append(time.perf_counter() - t0)
            return float(np.median(times)) * 1e3 / n_iter, np.asarray(stats[0]).tolist()

        rng = np.random.default_rng(0)
        shapes = {"decode 64": (64, np.ones(64, bool)),
                  f"chunk {z['chunk']}": (z["chunk"], np.tile(np.arange(128) < z["valid_a_row"], z["chunk"] // 128))}
        for shape, (t, valid) in shapes.items():
            routings = {"even": draw_routing(t, 0.0, rng, total, k),
                        "as the cell": draw_routing(t, 1.5, rng, total, k),
                        "one expert": np.tile(np.arange(k, dtype=np.int32) * held, (t, 1))}
            for name in chosen:
                moe.grouped_product = products[name]
                for r in tiles:
                    moe.rows_per_tile = (lambda *a, r=r: r) if r else own
                    for routing, ids in routings.items():
                        ms, stats = timed(t, ids, valid)
                        read_ms = stats[5] * 3 * e * f * 2 / peaks["hbm_bytes_per_s"] * 1e3
                        mxu_ms = stats[4] * z["parts"] * 3 * 2 * e * f / peaks["bf16_flops_per_s"] * 1e3
                        print(f"{model:20s} {shape:10s} {name:10s} tile {r or own(t, k, total):3d} {routing:11s} "
                              f"{ms:7.3f} ms a layer; held rows {stats[1]}, experts hit {stats[2]}, "
                              f"rows computed {stats[4]}, expert reads {stats[5]}; its roofline "
                              f"{max(read_ms, mxu_ms):6.3f} ms ({'bytes' if read_ms >= mxu_ms else 'products'}): "
                              f"{max(read_ms, mxu_ms) / ms:5.1%}", flush=True)
        moe.rows_per_tile, moe.grouped_product = own, products["kernel"]
        del w


def median_ms(fn, *args) -> float:
    """ms a call of ``fn(*args)``: the median of five, after one that compiles."""
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times)) * 1e3


def largest(a, b) -> float:
    """The largest difference between two arrays, or two trees of them."""
    return max(float(jnp.abs(x - y).max()) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def chunk_valid_counts(rows: int, used: int, rng, chunk: int = 128) -> np.ndarray:
    """Valid tokens of each row of a chunk dispatch in ``batch``'s traffic:
    ``used`` rows hold one chunk (any of them, drawn) of a prompt of the
    cell's lengths (lognormal, median 256, sigma 0.7, clipped 32-1,024), the
    rest are the rung's padding."""
    counts = np.zeros(rows, np.int32)
    for row in range(used):
        n = int(np.clip(np.exp(rng.normal(np.log(256), 0.7)), 32, 1024))
        at = int(rng.integers(0, -(-n // chunk)))
        counts[row] = min(chunk, n - at * chunk)
    return counts


def kda_profiles():
    """The delta-rule recurrence of ONE KDA layer of ``kimi-linear-48b-a3b``
    (32 heads of 128, float32) over a chunk of 128 tokens, from a carried
    state: ``lax.scan`` of ``_kda_step`` over the tokens (what the chunk
    program ran before PR 40: the rows' whole state through HBM once a token)
    beside ``ops/pallas/kda_scan.py`` (once a chunk). At 8 and 16 rows with the
    valid counts the cell's traffic gives a rung (5 and 12 rows hold a chunk of
    a prompt), at 8 full rows and at 8 rows of padding (what the layout around
    the kernel and a load and a store of the state cost); PROF_ITERS (default 8) layers chained in
    one dispatch, each from the state the last one left. Also the largest
    difference between the two, state and outputs, on this device. The last
    line is ``qwen3-next-80b-a3b``'s Gated DeltaNet shape on the same kernel (8
    rows at the cell's counts, 32 value heads, a head's ONE decay spread over
    its 128 key channels), and every line ends with what the kernel must move
    (the rows' state there and back, q, k, v, the decay and the outputs) and its
    share of the chip's bytes a second. PROF_HEADS (default 32) cuts the heads
    for a rehearsal on the CPU (interpreted)."""
    from benchmark import bytes_and_flops

    peak = (bytes_and_flops.load_peaks(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
            if jax.default_backend() == "tpu" else float("nan"))
    from dynamo_tpu.engine_jax.compile_cache import enable_compile_cache
    from dynamo_tpu.models import kimi_linear as kl
    from dynamo_tpu.ops.pallas.kda_scan import kda_scan

    enable_compile_cache()
    n_iter = int(os.environ.get("PROF_ITERS", "8"))
    t, h, d = 128, int(os.environ.get("PROF_HEADS", "32")), 128
    kernel = functools.partial(kda_scan, interpret=jax.default_backend() == "cpu")

    def scanned(q, k, v, log_decay, beta, s, n):
        def token(s, xs):
            q, k, v, log_decay, beta, i = xs
            new, o = kl._kda_step(s, q, k, v, log_decay, beta)
            return jnp.where((i < n)[:, None, None, None], new, s), o

        per_token = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, log_decay, beta))
        s, o = jax.lax.scan(token, s, (*per_token, jnp.arange(t)))
        return jnp.where((jnp.arange(t) < n[:, None])[:, :, None, None], jnp.moveaxis(o, 0, 1), 0.0), s

    def timed(fn, xs, s0, n):
        @jax.jit
        def chain(xs, s, n):
            def layer(s, _):
                o, s = fn(*xs, s, n)
                return s, o[:, :, 0, 0]
            return jax.lax.scan(layer, s, None, length=n_iter)

        chain(xs, s0, n)[0].block_until_ready()
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            chain(xs, s0, n)[0].block_until_ready()
            times.append(time.perf_counter() - t0)
        return float(np.median(times)) * 1e3 / n_iter

    rng = np.random.default_rng(0)
    for rows, used, spread in ((8, 5, False), (16, 12, False), (8, 8, False), (8, 0, False), (8, 5, True)):
        key = jax.random.split(jax.random.PRNGKey(rows), 6)
        q, k, v = (jax.random.normal(key[i], (rows, t, h, d), jnp.float32) for i in range(3))
        q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True)) * d ** -0.5
        k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True))
        log_decay = jax.random.uniform(key[3], (rows, t, h, 1 if spread else d), jnp.float32, -1.6, -0.1)
        log_decay = jnp.broadcast_to(log_decay, (rows, t, h, d))  # Gated DeltaNet: a head's one decay
        beta = jax.nn.sigmoid(jax.random.normal(key[4], (rows, t, h), jnp.float32))
        s0 = jax.random.normal(key[5], (rows, h, d, d), jnp.float32)
        xs = (q, k, v, log_decay, beta)
        n = jnp.asarray(np.full(rows, t, np.int32) if used == rows
                        else chunk_valid_counts(rows, used, rng))
        (o_scan, s_scan), (o_kernel, s_kernel) = jax.jit(scanned)(*xs, s0, n), kernel(*xs, s0, n)
        ms_scan, ms_kernel = timed(scanned, xs, s0, n), timed(kernel, xs, s0, n)
        moved = 4 * (2 * rows * h * d * d + int(n.sum()) * h * (5 * d + 1))  # state there and back; q, k, v, decay, o, beta
        print(f"{'gdn' if spread else 'kda'} {rows:2d} rows, valid {np.asarray(n).tolist()} ({int(n.sum())} tokens): "
              f"scan {ms_scan:7.3f} ms a layer, kernel {ms_kernel:7.3f}; largest difference "
              f"state {float(jnp.abs(s_kernel - s_scan).max()):.3g} of {float(jnp.abs(s_scan).max()):.3g}, "
              f"outputs {float(jnp.abs(o_kernel - o_scan).max()):.3g} of "
              f"{float(jnp.abs(o_scan).max()):.3g}; the kernel must move {moved / 1e6:.1f} MB: "
              f"{moved / peak / ms_kernel * 1e3:5.1%} of the chip's bytes a second", flush=True)


def mamba_profiles():
    """The selective scan of ONE Mamba layer of ``jamba2-3b`` (a float32 state
    of ``[16, 5120]`` a row) ALONE over a chunk of 128 tokens, from a carried
    state: ``ops/pallas/selective_scan.py`` with the layout XLA makes around it
    (what ``models/jamba.py:_scan_tokens`` calls for a chunk: the state on the
    chip over a row's valid tokens), beside the step it replaced scanned over
    the tokens (``_scan_tokens``'s one-token form under ``lax.scan``: the rows'
    state through HBM once a token) and the whole mixer of the layer (the four
    projections, the convolution, the norms and the kernel). At the rungs of 64
    slots (8, 16 and 64 rows): full rows, the valid counts the cell's traffic
    gives a rung (5 of 8 and 12 of 16 rows hold a chunk of a prompt; all 64 in
    the pre-roll's admission wave) and 8 rows of padding (the layout and a load
    and a store of the state). The kernel's own event comes from a device
    trace of one chain (the chain's ``lax.scan`` and the slice it stacks add to
    the wall time). The kernel's share of the vector unit's bound:
    ``bytes_and_flops_jamba.SCAN_OPS_PER_ELEMENT`` operations an element of a
    valid token's ``[16, 5120]`` at 4 x 1,024 lanes a cycle of 1.5 GHz. Also
    the largest difference between kernel and scanned step, state and outputs,
    on this device. Then a decode step's pass alone, 64 lanes of one token: the
    step kernel over a run of PROF_ITERS layers' state carried by a loop, a
    layer a trip, as the decode program's layer loop calls it (ms a layer in
    the chain and by its own traced event, GB/s of a layer's state there and
    back, the largest difference from the chunk kernel on a chunk of that one
    token: 0 on the chip), at each row block of PROF_ROWS (default: the
    kernel's own).
    PROF_ITERS (default 8) layers chained in one dispatch, each from the state
    the last one left. PROF_DINNER (default 5120) cuts the width for a
    rehearsal on the CPU (interpreted).

    Then a lane's rows handed over (PR 51), full rows of 128 tokens: at 8 rows
    one row a lane, lanes of two, 5 + 3 and one lane of eight, at 16 rows one
    row a lane, 8 + 8 and one lane of sixteen; the kernel in the chain and by
    its own event, the whole mixer, and the largest difference from the same
    kernel called a row at a time, each call from the state the call before it
    left (0: the hand-over is those calls). Last the WHOLE chunk program of
    ``jamba2-3b`` at those layouts, beside the same rows in successive
    dispatches, a piece of every lane a dispatch: the largest difference of the
    first run of Mamba layers' state (0: nothing stands in front of it) and of
    all of it (what two orders of attention's sums leave)."""
    from benchmark.bytes_and_flops_jamba import SCAN_OPS_PER_ELEMENT
    from dynamo_tpu.engine_jax.compile_cache import enable_compile_cache
    from dynamo_tpu.models import jamba

    enable_compile_cache()
    n_iter = int(os.environ.get("PROF_ITERS", "8"))
    d = int(os.environ.get("PROF_DINNER", "5120"))
    c = jamba.JambaConfig(hidden_size=d // 2, num_layers=1, attn_layer_offset=1, vocab_size=256)
    lp = jax.tree.map(lambda a: a[0], jamba.init_params(jax.random.PRNGKey(0), c)["mamba"][0])
    n = c.mamba_d_state
    vector_ops_per_s = 4 * 1024 * 1.5e9  # four vector slots an instruction over 8 x 128 lanes

    def scanned(s, delta, x, b, cc, valid):
        def token(s, xs):
            y, s = jamba._scan_tokens(lp, s, *(a[:, None] for a in xs))
            return s, jnp.where(xs[4][:, None], y[:, 0], 0.0)

        s, y = jax.lax.scan(token, s, tuple(jnp.moveaxis(a, 1, 0) for a in (delta, x, b, cc, valid)))
        return jnp.moveaxis(y, 0, 1), s

    def chained(fn):
        @jax.jit
        def chain(s, *args):
            def layer(s, _):
                y, s = fn(s, *args)
                return s, y[:, :, 0]
            return jax.lax.scan(layer, s, None, length=n_iter)
        return chain

    def chain_ms(chain, *args):
        """ms a layer of a chain of ``n_iter``: the median of five calls."""
        return median_ms(chain, *args) / n_iter

    def timed(fn, *args):
        return chain_ms(chained(fn), *args)

    def kernel_event_ms(chain, *args, name="selective_scan"):
        """The mean event of that name in one traced call of ``chain``: the
        kernel's own time, without what the chain or XLA puts around it."""
        from benchmark.trace_reduce import find_xplane, read_xplane

        trace_dir = os.path.join("chiprun_out", "profile_decode", "mamba")
        shutil.rmtree(trace_dir, ignore_errors=True)
        chain(*args)[0].block_until_ready()
        jax.profiler.start_trace(trace_dir)
        chain(*args)[0].block_until_ready()
        jax.profiler.stop_trace()
        ops = read_xplane(find_xplane(trace_dir))["devices"].get("0", {"ops": []})["ops"]
        events = [dur for op, _, dur in ops if name in op]
        return float(np.mean(events)) / 1e6 if events else float("nan")

    def step_profile(rows, s0, delta, x, b, cc, valid, ms_mixer):
        """A decode step's pass alone: the run's state carried, a layer a trip."""
        from dynamo_tpu.ops.pallas import selective_scan as kernels

        # under jit as the step is, so that A is made the same way on both sides
        y_c, s_c = jax.jit(lambda s: kernels.selective_scan(
            delta, x, b, cc, -jnp.exp(lp["a_log"]), s, valid.sum(axis=1).astype(jnp.int32),
            interpret=jax.default_backend() == "cpu"))(s0)
        own = kernels.ROWS
        state_gb = 2 * rows * n * d * 4 / 1e9  # a layer's, read once and written once
        for block in [int(r) for r in os.environ.get("PROF_ROWS", str(kernels.ROWS)).split(",")]:
            kernels.ROWS = block
            jax.clear_caches()

            @jax.jit
            def chain(run):
                def layer(run, i):
                    y, (run, _) = jamba._scan_tokens(lp, (run, i), delta, x, b, cc, valid)
                    return run, y[:, 0, 0]
                return jax.lax.scan(layer, run, jnp.arange(n_iter))

            run = jnp.broadcast_to(s0, (n_iter, *s0.shape)) + 0.0
            ms = chain_ms(chain, run)
            event = kernel_event_ms(chain, run, name="selective_step")
            y_k, (s_k, _) = jax.jit(lambda s: jamba._scan_tokens(lp, (s, 1), delta, x, b, cc, valid))(run)
            print(f"mamba {rows:2d} rows x   1 token, {block:2d} rows a grid step: the step's pass {ms:7.3f} ms a layer "
                  f"in the chain ({state_gb / ms * 1e3:6.1f} GB/s of state there and back), its own event "
                  f"{event:7.3f} ({state_gb / event * 1e3:6.1f} GB/s), whole mixer {ms_mixer:7.3f}; largest "
                  f"difference from the chunk kernel on the one-token chunk: state "
                  f"{float(jnp.abs(s_k[1] - s_c).max()):.3g} of {float(jnp.abs(s_c).max()):.3g}, outputs "
                  f"{float(jnp.abs(y_k - y_c).max()):.3g} of {float(jnp.abs(y_c).max()):.3g}; the other layers "
                  f"{float(jnp.abs(jnp.concatenate([s_k[:1], s_k[2:]]) - s0).max()):.3g}", flush=True)
        kernels.ROWS = own
        jax.clear_caches()

    def inputs(rows, t):
        """What the kernel takes of a chunk (``delta``, ``x``, ``b``, ``c``, a carried state)
        and what the mixer takes (its normed inputs, a tail)."""
        key = jax.random.split(jax.random.PRNGKey(rows), 6)
        delta = jax.nn.softplus(jax.random.normal(key[0], (rows, t, d), jnp.float32) - 3.0)
        x = jax.random.normal(key[1], (rows, t, d), jnp.float32)
        b, cc = (jax.random.normal(key[i], (rows, t, n), jnp.float32) for i in (2, 3))
        s0 = jax.random.normal(key[4], (rows, n, d), jnp.float32)
        u = jax.random.normal(key[5], (rows, t, c.hidden_size), jnp.float32).astype(c.dtype)
        return (delta, x, b, cc), s0, u, jnp.zeros((rows, (c.mamba_d_conv - 1) * d), jnp.float32)

    rng = np.random.default_rng(0)
    for rows, t, used in ((8, 128, 5), (16, 128, 12), (8, 128, 8), (8, 128, 0), (16, 128, 16),
                          (64, 128, 64), (64, 128, None), (64, 1, 64)):
        (delta, x, b, cc), s0, u, tail = inputs(rows, t)
        counts = (np.full(rows, t, np.int32) if used == rows
                  else chunk_valid_counts(rows, rows if used is None else used, rng))
        valid = jnp.arange(t)[None, :] < jnp.asarray(counts)[:, None]
        xs = (delta, x, b, cc, valid)
        ms_mixer = timed(lambda s, u, valid, tail: jamba.mamba_mixer(lp, c, u, valid, s, tail)[:2],
                         s0, u, valid, tail)
        if t == 1:
            step_profile(rows, s0, *xs, ms_mixer)
            continue
        ms = timed(lambda s, *a: jamba._scan_tokens(lp, s, *a), s0, *xs)
        bound = int(counts.sum()) * n * d * SCAN_OPS_PER_ELEMENT / vector_ops_per_s * 1e3
        (y_k, s_k), (y_s, s_s) = jax.jit(lambda *a: jamba._scan_tokens(lp, *a))(s0, *xs), jax.jit(scanned)(s0, *xs)
        event = kernel_event_ms(chained(lambda s, *a: jamba._scan_tokens(lp, s, *a)), s0, *xs)
        print(f"mamba {rows:2d} rows, valid {counts.tolist() if rows <= 16 else '...'} ({int(counts.sum())} tokens): "
              f"kernel {ms:7.3f} ms a layer in the chain, its own event {event:7.3f} "
              f"({bound / event:5.1%} of the vector unit's bound, {bound:.3f} ms), "
              f"scanned step {timed(scanned, s0, *xs):7.3f}, whole mixer {ms_mixer:7.3f}; largest difference "
              f"state {float(jnp.abs(s_k - s_s).max()):.3g} of {float(jnp.abs(s_s).max()):.3g}, "
              f"outputs {float(jnp.abs(y_k - y_s).max()):.3g} of {float(jnp.abs(y_s).max()):.3g}", flush=True)

    # -- a lane's rows handed over: full rows, the lanes as the engine lays them --------------
    for rows, named in LANE_LAYOUTS.items():
        made, s0, u, tail = inputs(rows, 128)
        valid = jnp.ones((rows, 128), bool)
        xs = (*made, valid)
        a_row = jax.jit(lambda s, *a: jamba._scan_tokens(lp, s, *a))
        for name, sizes in named.items():
            above = jnp.asarray([k > 0 for m in sizes for k in range(m)])
            scan = lambda s, *a: jamba._scan_tokens(lp, s, *a, above)  # noqa: E731
            ms, event = timed(scan, s0, *xs), kernel_event_ms(chained(scan), s0, *xs)
            ms_mixer = timed(lambda s, u, valid, tail: jamba.mamba_mixer(lp, c, u, valid, s, tail, above)[:2],
                             s0, u, valid, tail)
            y_k, s_k = jax.jit(scan)(s0, *xs)
            off_y = off_s = 0.0
            for first, m in zip(np.cumsum([0] + sizes[:-1]), sizes):  # a call a row, from the state the last left
                state = s0[first:first + 1]
                for r in range(first, first + m):
                    y_r, state = a_row(state, *(v[r:r + 1] for v in xs))
                    off_y = max(off_y, largest(y_r[0], y_k[r]))
                off_s = max(off_s, largest(state[0], s_k[first]))  # the lane's state: at its first row
            print(f"mamba {rows:2d} full rows, {name}: kernel {ms:7.3f} ms a layer in the chain, its own event "
                  f"{event:7.3f}, whole mixer {ms_mixer:7.3f}; largest difference from a call a row: state "
                  f"{off_s:.3g}, outputs {off_y:.3g}", flush=True)
    chunk_program_profile(jamba, d)


# (rows of a chunk dispatch: {a layout's name: the rows each lane fills, in order})
LANE_LAYOUTS = {
    8: {"one row a lane": [1] * 8, "lanes of two": [2] * 4, "5 + 3": [5, 3], "one lane of eight": [8]},
    16: {"one row a lane": [1] * 16, "8 + 8": [8, 8], "one lane of sixteen": [16]},
}


def chunk_program_at_the_cell(jamba, d_inner):
    """``jamba2-3b``'s whole chunk program at the cell's shapes (64 slots,
    12,288 blocks of 16, tables of 2,048 positions, random bf16 weights):
    (params, pool, state, ``dispatch(rows, pieces)`` -> the arrays of a dispatch
    of ``rows`` rows, ``pieces`` = (lane, which full piece of its prompt) a row
    from the top, the rest padding)."""
    c = jamba.JambaConfig(hidden_size=d_inner // 2)
    slots, mb, t = 64, 128, 128
    params = jax.jit(lambda: jamba.init_params(jax.random.PRNGKey(0), c))()
    cache = jamba.make_kv_cache(c, 12288, 16)
    state = jax.tree.map(lambda a: 0.1 * jax.random.normal(jax.random.PRNGKey(1), a.shape, a.dtype),
                         jamba.make_slot_state(c, slots))

    def dispatch(rows, pieces):
        toks = np.zeros((rows, t), np.int32)
        pos = np.full((rows, t), -1, np.int32)
        tables, lanes = np.zeros((rows, mb), np.int32), np.full((rows,), slots, np.int32)
        for r, (lane, k) in enumerate(pieces):
            toks[r] = (np.arange(t) * 7 + lane * 131 + k * 17) % (c.vocab_size - 1) + 1
            pos[r] = k * t + np.arange(t)
            tables[r], lanes[r] = 1 + lane * mb + np.arange(mb), lane
        return tuple(jnp.asarray(a) for a in (toks, pos, tables, lanes))

    def program(donate=()):  # traced when first called: under the ``ROWS_AT_ONCE`` of that moment
        return jax.jit(lambda p, kv, st, toks, pos, tables, lanes: jamba.forward_chunk(
            p, c, toks, pos, kv, tables, st, lanes), donate_argnums=donate)

    return params, cache, state, dispatch, program


def chunk_program_profile(jamba, d_inner):
    """``jamba2-3b``'s whole chunk program ALONE at the cell's shapes at the
    layouts of ``LANE_LAYOUTS``, every row full: ms a dispatch (the median of
    five), beside the same rows a piece of every lane a dispatch."""
    params, cache, state, dispatch, program = chunk_program_at_the_cell(jamba, d_inner)
    mine = program()
    for rows, named in LANE_LAYOUTS.items():
        for name, sizes in named.items():
            once = dispatch(rows, [(lane, k) for lane, m in enumerate(sizes) for k in range(m)])
            ms = median_ms(mine, params, cache, state, *once)
            _, _, st, sums = mine(params, cache, state, *once)
            line = (f"chunk program {rows:2d} full rows, {name}: {ms:8.3f} ms a dispatch; handovers "
                    f"{int(sums[4])}, state passes {int(sums[2])}")
            if max(sizes) > 1:  # the same rows, a piece of every lane a dispatch
                kv_p, st_p, total = cache, state, 0.0
                for k in range(max(sizes)):
                    step = dispatch(rows, [(lane, k) for lane, m in enumerate(sizes) if k < m])
                    total += median_ms(mine, params, kv_p, st_p, *step)
                    _, kv_p, st_p, _ = mine(params, kv_p, st_p, *step)
                line += (f"; in {max(sizes)} successive dispatches {total:8.3f} ms; largest difference of the "
                         f"state they leave: the first run of Mamba layers {largest(st['s'][0], st_p['s'][0]):.3g}, "
                         f"all {largest(st, st_p):.3g} of {largest(st_p, jax.tree.map(jnp.zeros_like, st_p)):.3g}")
            print(line, flush=True)


def chunk_group_profiles():
    """What chose ``models/jamba.py:ROWS_AT_ONCE`` (PR 67): ``jamba2-3b``'s chunk
    program at the cell's shapes, the 8-row rung, with the module's groups at
    each of PROF_ROWS' heights (default 2,4,8), one line a height: a group
    ALONE (the rung's first ``height`` rows hold full pieces of one prompt, so
    the loop makes one trip) in ms a group and ms a row, then all 8 rows full
    (8 / height groups; two lanes of four rows, so a lane straddles the groups
    of 2) and the 16-row rung with 12 rows full (three lanes of four). Then the
    module as it is at the rows a prompt of the cell's traffic fills (1-8 of 8,
    9-16 of 16): ms a dispatch by live rows, which is what the engine's host
    step waits for. PROF_DINNER (default 5120) cuts the width for a rehearsal
    on the CPU."""
    from dynamo_tpu.engine_jax.compile_cache import enable_compile_cache
    from dynamo_tpu.models import jamba

    enable_compile_cache()
    params, cache, state, dispatch, program = chunk_program_at_the_cell(
        jamba, int(os.environ.get("PROF_DINNER", "5120")))

    def lanes_of_four(live):
        return [(r // 4, r % 4) for r in range(live)]

    def ms_of(mine, once) -> float:
        """ms a dispatch, the median of five after one that compiles, the pool and the state DONATED as
        the engine donates them (each call takes what the last one left: the same pieces over again)."""
        _, kv, st, _ = mine(params, jax.tree.map(jnp.copy, cache), jax.tree.map(jnp.copy, state), *once)
        times = []
        for _ in range(5):
            jax.block_until_ready(st)
            t0 = time.perf_counter()
            _, kv, st, _ = mine(params, kv, st, *once)
            jax.block_until_ready(st)
            times.append(time.perf_counter() - t0)
        return float(np.median(times)) * 1e3

    chosen = jamba.ROWS_AT_ONCE
    for height in (int(x) for x in os.environ.get("PROF_ROWS", "2,4,8").split(",")):
        jamba.ROWS_AT_ONCE = height
        mine = program(donate=(1, 2))
        alone = ms_of(mine, dispatch(8, lanes_of_four(height)))
        full = ms_of(mine, dispatch(8, lanes_of_four(8)))
        line = (f"chunk groups of {height} rows: a group alone {alone:8.3f} ms, {alone / height:7.3f} ms a row; "
                f"8 full rows of 8 in {8 // height} groups {full:8.3f} ms, {full / 8:7.3f} ms a row")
        if height < 8:  # a loop over groups of 8 rows aborts the chip's compiler: the 8-row rung at once is all of it
            twelve = ms_of(mine, dispatch(16, lanes_of_four(12)))
            line += (f"; 12 full rows of 16 in {-(-12 // height)} groups {twelve:8.3f} ms, "
                     f"{twelve / 12:7.3f} ms a row")
        print(line, flush=True)
    jamba.ROWS_AT_ONCE = chosen
    mine = program(donate=(1, 2))
    for rows, fewest in ((8, 1), (16, 9)):
        by_live = [ms_of(mine, dispatch(rows, lanes_of_four(live))) for live in range(fewest, rows + 1)]
        print(f"chunk program as it is (groups of {chosen}), {rows:2d}-row rung, ms a dispatch by live rows "
              f"{fewest}..{rows}: " + " ".join(f"{ms:.2f}" for ms in by_live), flush=True)


def draw_contexts(lanes: int, decoding: int, seed: int = 0) -> np.ndarray:
    """``[lanes]``: the history ``decoding`` of them hold in the middle of a run
    of the ``batch`` traffic (a prompt of lognormal length, median 256, sigma
    0.7, in 32 ... 1,024, and an even share of an output of median 128, sigma
    0.5, in 16 ... 384: ``benchmark/workloads/batch.*.json``), -1 for the
    lanes that prefill or wait."""
    rng = np.random.default_rng(seed)
    prompts = np.exp(np.log(256) + 0.7 * rng.standard_normal(lanes)).round().clip(32, 1024)
    outputs = np.exp(np.log(128) + 0.5 * rng.standard_normal(lanes)).round().clip(16, 384)
    held = (prompts + np.floor(rng.random(lanes) * outputs)).astype(np.int32)
    held[rng.permutation(lanes)[decoding:]] = -1
    return held


def mla_decode_profiles(h: int, peaks: dict):
    """ONE layer's absorbed attention of a decode step's 64 lanes at ``h``
    heads, at the cell's OCCUPANCY (:func:`draw_contexts`: 58 lanes decode,
    each at its own context), PROF_ITERS (default 8) layers chained in one
    dispatch, beside the bytes each form must read and does:

    - the full-width form the decode programs had until PR 69
      (``attend_absorbed`` over every lane's whole table under a mask);
    - form (i), ``models/llama.py``'s: the (lane, tile) pairs that hold
      history packed to the front, attended at a static width (all, half and a
      quarter of the pairs; the narrowest that holds the live ones is what a
      ``lax.switch`` would run), a lane's queries taken out to its slots and
      the slots' partials brought back by a product with ``own``;
    - form (i)'s slots under ONE text of the step loop: PROF_WIDTHS' widths
      (of 512 slots) in a ``lax.switch`` around the attention's core alone;
    - form (ii), what the programs run (``ops/latent.py:
      attend_absorbed_live``): one text of the step loop, the lanes longest
      first in blocks of PROF_LANES' lanes (default 4,8), a block's tiles a
      tile a trip under a traced trip count, the steps' buffer folded in;
    - what a dispatch pays ONCE: the gather of every table whole, one layer,
      in the tables' order and in the live form's (``live_latents``)."""
    from dynamo_tpu.models.llama import history_tile, history_tiles_full
    from dynamo_tpu.ops import latent as ops
    from dynamo_tpu.ops.latent import wdot

    on_chip = bool(peaks)
    n_iter = int(os.environ.get("PROF_ITERS", "8"))
    b, steps = int(os.environ.get("PROF_SLOTS", "64")), 4
    r, dn, dr, dv, w, e, table, bs = 512, 128, 64, 128, 640, 7680, 2048, 16
    dims = (r, dn, dv, (dn + dr) ** -0.5)
    key = jax.random.split(jax.random.PRNGKey(0), 6)
    w_kvb = (jax.random.normal(key[0], (r, h * (dn + dv)), jnp.float32) / r ** 0.5).astype(jnp.bfloat16)
    wo = (jax.random.normal(key[1], (h * dv, e), jnp.float32) / (h * dv) ** 0.5).astype(jnp.bfloat16)
    weights = 2 * (w_kvb.size + wo.size)
    base = draw_contexts(b, b - b // 10)
    mb = table // bs
    tile, tiles = history_tile(bs, mb), history_tiles_full(bs, mb)
    pairs = int((-(-base.clip(0) // tile)).sum())
    need = 2 * int(base.clip(0).sum()) * (r + dr) * 4 + weights
    print(f"mla decode, {h} heads, {b} lanes: {int((base >= 0).sum())} decode at contexts "
          f"{int(base[base >= 0].min())} ... {int(base.max())} (mean {base[base >= 0].mean():.0f}); "
          f"{pairs} of {b * tiles} (lane, tile) pairs hold history; a layer and step must read "
          f"{need / 1e6:.1f} MB", flush=True)

    def ms_a_layer(attend, q, *args) -> float:
        @jax.jit
        def chain(q, *args):
            def layer(q, _):
                y = attend(q, *args)
                return q + 1e-3 * y[..., None, :dn + dr], y[:, :, 0]
            return jax.lax.scan(layer, q, None, length=n_iter)
        return median_ms(lambda *a: chain(*a)[0], q, *args) / n_iter

    def report(name, ms, slots, carried=0):
        read = 2 * slots * tile * w * 4 + weights + carried
        line = (f"mla decode, {h} heads, {name}: {ms:8.3f} ms a layer and step; reads {slots:3d} pairs of "
                f"{b * tiles} ({slots / (b * tiles):5.1%}), {read / 1e6:7.1f} MB where {need / 1e6:.1f} are needed")
        if on_chip:
            line += (f"; of the chip's bytes a second {need / peaks['hbm_bytes_per_s'] / ms * 1e3:5.1%} needed, "
                     f"{read / peaks['hbm_bytes_per_s'] / ms * 1e3:5.1%} read")
        print(line, flush=True)
        return ms

    pool = jax.random.normal(key[3], (1, b * mb + 1, bs, w), jnp.float32).at[..., r + dr:].set(0.0)
    tables = 1 + jnp.arange(b * mb, dtype=jnp.int32).reshape(b, mb)
    q = jax.random.normal(key[2], (b, 1, h, dn + dr), jnp.float32)
    pos = jnp.asarray(base)
    recent = jax.random.normal(key[4], (b, steps, w), jnp.float32).at[..., r + dr:].set(0.0)
    fed = pos >= 0

    # the form the programs had: every table whole, the mask over it
    whole = ops.gather_latent(pool, 0, tables)
    mask = (jnp.arange(table)[None, None, :] < pos[:, None, None])
    report("every table whole (attend_absorbed)", ms_a_layer(
        lambda q, latent, mask, w_kvb, wo: ops.attend_absorbed(q, w_kvb, wo, latent, mask, *dims),
        q, whole, mask, w_kvb, wo), b * tiles)

    # form (i): the live pairs packed first, a static width of slots
    held = base.clip(0)
    live_pair = (np.arange(tiles)[None, :] * tile < held[:, None]).reshape(-1)
    order = np.argsort(~live_pair, kind="stable")
    lane, first = order // tiles, order % tiles * tile
    length = jnp.asarray((held[lane] - first).clip(0, tile))
    packed_all = whole.reshape(b * tiles, tile, w)[order]

    def packed_core(q_all, slots, lane, length):
        """The slots' partials brought back to their lanes: the weighted sum ``[B, 1, H, rank]``."""
        own = lane[None, :] == jnp.arange(b)[:, None]  # [B, n]
        scores = wdot("nhc,npc->nhp", q_all[lane, 0], slots) * dims[3]
        scores = jnp.where((jnp.arange(tile)[None, :] < length[:, None])[:, None], scores, -jnp.inf)
        top = jnp.maximum(jnp.where(own[:, :, None], scores.max(axis=-1)[None], -jnp.inf).max(axis=1), -1e30)
        p = jnp.exp(scores - top[lane][:, :, None])
        den = jnp.einsum("bn,nh->bh", own.astype(jnp.float32), p.sum(axis=-1), precision="highest")
        num = jnp.einsum("bn,nhr->bhr", own.astype(jnp.float32), wdot("nhp,npr->nhr", p, slots[..., :r]),
                         precision="highest")
        return (num / jnp.maximum(den, 1e-30)[..., None])[:, None]

    def packed(q, slots, lane, length, w_kvb, wo):
        q_all, w_kvb = ops._into_latent_space(q, w_kvb, r, dn, dv, w)
        return ops._out_of_latent_space(packed_core(q_all, slots, lane, length), w_kvb, wo, dn)

    for n in sorted({b * tiles // 4, b * tiles // 2, b * tiles}):
        if n >= pairs:
            report(f"form (i), {n} packed slots", ms_a_layer(
                packed, q, packed_all[:n], jnp.asarray(lane[:n]), length[:n], w_kvb, wo), n)

    # between the two: form (i)'s packed slots, one step-loop text, the widths around the core alone
    ladder = [int(x) * b * tiles // 512 for x in os.environ.get("PROF_WIDTHS", "0,128,256,512").split(",")]

    def packed_by_rung(q, slots, lane, length, rung, w_kvb, wo):
        q_all, w_kvb = ops._into_latent_space(q, w_kvb, r, dn, dv, w)
        out_lat = jax.lax.switch(rung, [
            (lambda n=n: packed_core(q_all, slots[:n], lane[:n], length[:n]) if n
             else jnp.zeros((b, 1, h, r), jnp.float32)) for n in ladder])
        return ops._out_of_latent_space(out_lat, w_kvb, wo, dn)

    rung = int(np.searchsorted(ladder, pairs))
    report(f"form (i) slots, the widths {ladder} around the core alone, at {ladder[rung]}", ms_a_layer(
        packed_by_rung, q, packed_all, jnp.asarray(lane), length, jnp.int32(rung), w_kvb, wo), ladder[rung])

    # form (ii): the lanes longest first in blocks, one step-loop text
    def lively(q, live, recent, fed, w_kvb, wo):  # the dispatch's second step
        return ops.attend_absorbed_live(q, w_kvb, wo, live, 0, recent, recent[:, 1:2], 1, fed, *dims)[0]

    checked = False
    for lanes in (int(x) for x in os.environ.get("PROF_LANES", "4,8").split(",")):
        ops.LANES_AT_ONCE = lanes
        lb = ops.lanes_at_once(b)
        gather = jax.jit(lambda pool, tables, pos: ops.live_latents(pool, 1, tables, pos))  # this block's own trace
        live = gather(pool, tables, pos)
        read = int(ops.live_history_tiles(base, bs, mb))
        if not checked:
            checked = True
            want = packed(q, packed_all, jnp.asarray(lane), length, w_kvb, wo)
            got = lively(q, live, recent, fed & False, w_kvb, wo)
            print(f"mla decode, {h} heads: form (ii) against form (i) at full width, no step folded in: largest "
                  f"difference {largest(got, want):.2e} of {float(jnp.abs(want).max()):.2f}", flush=True)
        trips = int(live.trips.sum())
        report(f"form (ii), blocks of {lb} lanes, {trips} trips (attend_absorbed_live)", ms_a_layer(
            lively, q, live, recent, fed, w_kvb, wo), read, carried=trips * 3 * lb * h * r * 4)
        ms = median_ms(gather, pool, tables, pos)
        print(f"mla decode, once a dispatch: every table of one layer gathered whole, the lanes longest first in "
              f"blocks of {lb} {ms:8.3f} ms", flush=True)
    ms = median_ms(jax.jit(ops.gather_latent, static_argnums=1), pool, 0, tables)
    print(f"mla decode, once a dispatch: every table of one layer gathered whole {ms:8.3f} ms "
          f"({b * tiles * tile * w * 4 / 1e6:.0f} MB)", flush=True)


def mla_profiles():
    """ONE layer's absorbed latent attention of ``openpangu-ultra-moe-718b``
    (``ops/latent.py`` as ``models/openpangu.py`` calls it: 128 heads, a latent
    of 512 + 64 in a row of 640, float32, ``W_kvb`` and ``W_o`` bf16) ALONE,
    PROF_ITERS (default 8) layers chained in one dispatch, at each of
    PROF_CONTEXT's contexts (positions a row holds, its queries the last of
    them; default 192,320,640,1024; the traffic's mean is 320):

    - a decode step's 64 lanes x 1 token over a dense history of 2,048
      positions a lane, attended whole under the mask, as ``decode`` does
      (``attend_absorbed``; at the first context only: the mask hides it);
    - a chunk group of PROF_ROWS (default 4) rows x 128 tokens the same way,
      the form the chunk program had until PR 53, from a history in hand and
      from its gather out of the pool (which the program paid too);
    - that full form over a table CUT to the power of two of positions that
      holds the context (what a ``lax.switch`` over static widths would run);
    - the tiled form the chunk program runs (``attend_absorbed_tiled``: the
      gather inside the loop, trips up to the context) at each of PROF_TILES'
      positions a tile (default 128,256,512).

    Beside each time: the bytes the call must read (the live part of the
    history, twice: scores and values, ``W_kvb`` and ``W_o``) and what it does
    read (the positions attended; the tiled form also carries its running
    numerator through the trips), the operations it needs (the live keys) and
    does, and their shares of the chip's peaks. PROF_HEADS (default 128,32: a
    comma list) are the head counts; the decode step's forms at the cell's
    occupancy come first, at every head count (:func:`mla_decode_profiles`),
    and the forms above at the first (not at all where PROF_FORMS is
    ``decode``)."""
    from benchmark import bytes_and_flops

    on_chip = jax.default_backend() == "tpu"
    peaks = bytes_and_flops.load_peaks(jax.devices()[0].device_kind) if on_chip else {}
    from dynamo_tpu.engine_jax.compile_cache import enable_compile_cache
    from dynamo_tpu.ops.latent import attend_absorbed, attend_absorbed_tiled, gather_latent

    enable_compile_cache()
    n_iter = int(os.environ.get("PROF_ITERS", "8"))
    heads = [int(x) for x in os.environ.get("PROF_HEADS", "128,32").split(",")]
    for h in heads:
        mla_decode_profiles(h, peaks)
    if os.environ.get("PROF_FORMS") == "decode":
        return
    h = heads[0]
    contexts = [int(x) for x in os.environ.get("PROF_CONTEXT", "192,320,640,1024").split(",")]
    tiles = [int(x) for x in os.environ.get("PROF_TILES", "128,256,512").split(",")]
    group = int(os.environ.get("PROF_ROWS", "4"))
    r, dn, dr, dv, w, e, table, bs = 512, 128, 64, 128, 640, 7680, 2048, 16
    dims = (r, dn, dv, (dn + dr) ** -0.5)
    key = jax.random.split(jax.random.PRNGKey(0), 5)
    w_kvb = (jax.random.normal(key[0], (r, h * (dn + dv)), jnp.float32) / r ** 0.5).astype(jnp.bfloat16)
    wo = (jax.random.normal(key[1], (h * dv, e), jnp.float32) / (h * dv) ** 0.5).astype(jnp.bfloat16)
    weights = 2 * (w_kvb.size + wo.size)
    per_key = 2 * h * (2 * r + dr)  # a score over rank + rope, a value over rank, a head

    def ms_a_layer(attend, q, *args) -> float:
        """``attend(q, *args) -> [B, T, E]`` chained ``n_iter`` times in one dispatch."""
        @jax.jit
        def chain(q, *args):
            def layer(q, _):
                y = attend(q, *args)
                return q + 1e-3 * y[..., None, :dn + dr], y[:, :, 0]
            return jax.lax.scan(layer, q, None, length=n_iter)
        return median_ms(lambda *a: chain(*a)[0], q, *args) / n_iter

    def report(name, ms, b, t, context, attended, carried=0):
        through = 2 * b * t * (h * dn * r + h * r * dv + h * dv * e)  # the two halves of W_kvb, W_o
        need_bytes = 2 * b * context * (r + dr) * 4 + weights
        read_bytes = 2 * b * attended * w * 4 + weights + carried
        need_ops, done_ops = b * t * per_key * context + through, b * t * per_key * attended + through
        line = (f"mla {name}, context {context} of {table}: {ms:8.3f} ms a layer; attends {attended:4d} positions "
                f"a row; must read {need_bytes / 1e6:7.1f} MB and reads {read_bytes / 1e6:7.1f}; needs "
                f"{need_ops / 1e9:7.2f} GFLOP and does {done_ops / 1e9:7.2f}")
        if on_chip:
            line += (f"; of the chip's bytes a second {need_bytes / peaks['hbm_bytes_per_s'] / ms * 1e3:5.1%} "
                     f"needed, {read_bytes / peaks['hbm_bytes_per_s'] / ms * 1e3:5.1%} read; of its bf16 "
                     f"operations a second {need_ops / peaks['bf16_flops_per_s'] / ms * 1e3:5.1%} needed, "
                     f"{done_ops / peaks['bf16_flops_per_s'] / ms * 1e3:5.1%} done (float32 at the highest "
                     f"precision is six bfloat16 passes a product: a sixth of this peak is its ceiling)")
        print(line, flush=True)

    def queries(b, t, context):
        """(queries, their positions: the context's last ``t``, the mask over a whole table)."""
        at = jnp.broadcast_to(context - t + jnp.arange(t), (b, t))
        mask = jnp.arange(table)[None, None, :] <= at[:, :, None]
        return jax.random.normal(key[2], (b, t, h, dn + dr), jnp.float32), at, mask

    def full(q, latent, mask, w_kvb, wo):
        return attend_absorbed(q, w_kvb, wo, latent[:, :mask.shape[-1]], mask, *dims)

    b, t = 64, 1
    q, _, mask = queries(b, t, contexts[0])
    latent = jax.random.normal(key[3], (b, table, w), jnp.float32).at[..., r + dr:].set(0.0)
    report(f"a decode step, {b} lanes x {t} token", ms_a_layer(full, q, latent, mask, w_kvb, wo),
           b, t, contexts[0], table)

    b, t = group, 128
    latent = latent[:b]
    # the group's pages: a pool of one layer, every row its own blocks (block 0 is nobody's)
    pool = jnp.concatenate([jnp.zeros((bs, w)), latent.reshape(-1, w)]).reshape(1, -1, bs, w)
    tables = 1 + jnp.arange(b * table // bs, dtype=jnp.int32).reshape(b, -1)
    name = f"a chunk group, {b} rows x {t} tokens"
    for context in contexts:
        q, at, mask = queries(b, t, context)
        report(f"{name}, the whole table in hand", ms_a_layer(full, q, latent, mask, w_kvb, wo),
               b, t, context, table)
        cut = min(table, 1 << (context - 1).bit_length())

        def gathered(q, pool, tables, mask, w_kvb, wo):
            return full(q, gather_latent(pool, 0, tables), mask, w_kvb, wo)

        for width in sorted({cut, table}, reverse=True):
            report(f"{name}, a table of {width} gathered", ms_a_layer(
                gathered, q, pool, tables[:, :width // bs], mask[..., :width], w_kvb, wo), b, t, context, width)
        for tile in tiles:
            trips = -(-context // tile)

            def tiled(q, pool, tables, at, n_tiles, w_kvb, wo):  # the trips traced, as the program's are
                return attend_absorbed_tiled(q, w_kvb, wo, pool, 0, tables, at, n_tiles, tile // bs, *dims)

            # a trip reads the running numerator, the tile's own, and writes the merged one
            report(f"{name}, {trips} tiles of {tile}",
                   ms_a_layer(tiled, q, pool, tables, at, jnp.int32(trips), w_kvb, wo),
                   b, t, context, trips * tile, carried=trips * 3 * b * t * h * r * 4)


def mhc_profiles():
    """The residual path of ONE sublayer of ``xing4.0-29b-a4b`` (``ops/mhc.py``
    as ``models/xing4.py`` calls it: four float32 streams of 3,584, ``φ`` bf16
    ``[24, 14336]``, 20 Sinkhorn sweeps) ALONE, PROF_ITERS (default 8)
    sublayers chained in one dispatch (the sublayer between mixing in and
    mixing out is ``y = u``), at each of PROF_ROWS' token rows (default
    64,512: a decode step's lanes, a chunk group's positions): the whole path,
    then the maps alone (``x̂ φ`` and the sweeps, no mixing) and the sweeps
    alone (the 40 dependent normalisations over ``[4, 4, rows]``). Beside each
    time: the bytes the path must move (``benchmark/bytes_and_flops_xing4.py``:
    ``mhc_bytes_per_token`` x rows + ``φ``) over the chip's bandwidth."""
    from benchmark import bytes_and_flops, bytes_and_flops_xing4 as counts
    from dynamo_tpu.engine_jax.compile_cache import enable_compile_cache
    from dynamo_tpu.models import xing4
    from dynamo_tpu.ops import mhc

    on_chip = jax.default_backend() == "tpu"
    peaks = bytes_and_flops.load_peaks(jax.devices()[0].device_kind) if on_chip else {}
    enable_compile_cache()
    n_iter = int(os.environ.get("PROF_ITERS", "8"))
    c = xing4.Xing4Config(num_layers=1, first_k_dense=1)
    shape = {"hc_mult": c.hc_mult, "hidden_size": c.hidden_size}
    hp = xing4._init_hc(jax.random.PRNGKey(0), c)
    args = (c.hc_sinkhorn_iters, c.hc_eps, c.rms_norm_eps, c.hc_clamp)

    def path(streams, hp):
        h_pre, h_post, h_res = mhc.mhc_maps(streams, hp["phi"], hp["b"], hp["alpha"], *args)
        return mhc.mix_out(streams, h_res, h_post, mhc.mix_in(streams, h_pre))

    def maps_alone(streams, hp):
        h_pre, h_post, h_res = mhc.mhc_maps(streams, hp["phi"], hp["b"], hp["alpha"], *args)
        nudge = 1e-3 * (h_pre.sum(-1) + h_post.sum(-1) + h_res.sum((-1, -2)))[..., None]
        return tuple(x + nudge for x in streams)

    def sweeps_alone(m, hp):
        return jnp.exp(-mhc.sinkhorn(m, c.hc_sinkhorn_iters, c.hc_eps))  # positive again, and dependent

    def ms_a_call(fn, carry) -> float:
        @jax.jit
        def chain(carry, hp):
            return jax.lax.scan(lambda x, _: (fn(x, hp), ()), carry, None, length=n_iter)[0]
        return median_ms(chain, carry, hp) / n_iter

    for rows in (int(x) for x in os.environ.get("PROF_ROWS", "64,512").split(",")):
        streams = tuple(jax.random.normal(jax.random.PRNGKey(j), (rows, c.hidden_size)) for j in range(c.hc_mult))
        logits = jax.random.uniform(jax.random.PRNGKey(9), (c.hc_mult, c.hc_mult, rows), jnp.float32, -3.0, 3.0)
        must = counts.mhc_bytes_per_token(shape) * rows + counts.mhc_phi_bytes(shape)
        for name, ms in (("the whole path", ms_a_call(path, streams)),
                         ("the maps alone", ms_a_call(maps_alone, streams)),
                         ("the sweeps alone", ms_a_call(sweeps_alone, jnp.exp(logits)))):
            line = (f"mhc {name}, {rows} rows: {ms:8.4f} ms a sublayer; the path must move "
                    f"{must / 1e6:7.2f} MB ({counts.mhc_bytes_per_token(shape)} B a token + phi "
                    f"{counts.mhc_phi_bytes(shape)} B)")
            if on_chip:
                floor = must / peaks["hbm_bytes_per_s"] * 1e3
                line += f": {floor:7.4f} ms at the chip's bandwidth, {floor / ms:5.1%} of this time"
            print(line, flush=True)


def swa_profiles():
    """ONE window layer's attention of ``trinity-large-preview`` ALONE at
    ``long.trinity-large-preview``'s shapes (``ops/ring.py`` as
    ``models/trinity.py`` calls it: 48 query heads over 8 KV heads of 128, a
    float32 ring of 4,112 positions a slot, 8 slots, every lane past the
    window, float32's precision), PROF_ITERS (default 8) layers chained in one
    dispatch: a decode step's 8 queries against the 8 rings read in place, and
    a chunk group's 8 rows x 128 queries against their lanes' rings a tile a
    trip plus their own fresh keys. Beside each time: the bytes of the rings
    over the chip's bandwidth (a decode step's floor) and the scores' and
    values' operations over its bf16 peak, counted once (the program takes six
    bf16 passes a float32 product)."""
    from benchmark import bytes_and_flops
    from dynamo_tpu.engine_jax.compile_cache import enable_compile_cache
    from dynamo_tpu.models import trinity
    from dynamo_tpu.models.llama import _chunk_self_partial, _merge_partials
    from dynamo_tpu.ops import ring

    on_chip = jax.default_backend() == "tpu"
    peaks = bytes_and_flops.load_peaks(jax.devices()[0].device_kind) if on_chip else {}
    enable_compile_cache()
    n_iter = int(os.environ.get("PROF_ITERS", "8"))
    c = trinity.TrinityConfig(num_layers=1, layer_types=(trinity.WINDOW,), num_dense_layers=1)
    slots, rows, chunk = 8, 8, 128
    w, p, scale = c.sliding_window, c.ring_positions, c.head_dim ** -0.5
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    ring_k, ring_v = (jax.random.normal(k, (slots, c.num_kv_heads, p, c.head_dim), jnp.float32) for k in keys[:2])
    starts = jnp.asarray([4200 + 300 * i for i in range(slots)], jnp.int32)  # every lane past the window
    lanes = jnp.arange(rows, dtype=jnp.int32)

    def decode_step(q, rings):
        sees = ring.in_window(ring.held_positions(starts, p), starts[:, None], w)
        with jax.default_matmul_precision(trinity.ATTENTION_PRECISION):
            out = ring.attended(ring.masked_partial(q, *rings, sees, scale))
        return q + 1e-3 * out

    def chunk_group(q, rings):
        pos = starts[:, None] + jnp.arange(chunk)[None, :]
        kv = q[:, :, ::c.num_heads // c.num_kv_heads]
        with jax.default_matmul_precision(trinity.ATTENTION_PRECISION):
            part = ring.chunk_ring_partial(q, *rings, lanes, starts, ring.ring_trips(starts, w), pos, w, scale)
            out = ring.attended(_merge_partials(part, _chunk_self_partial(c, q, kv, kv, pos, scale, w)))
        return q + 1e-3 * out

    def ms_a_call(fn, q) -> float:
        @jax.jit
        def chain(q, rings):
            return jax.lax.scan(lambda x, _: (fn(x, rings), ()), q, None, length=n_iter)[0]
        return median_ms(chain, q, (ring_k, ring_v)) / n_iter

    ring_bytes = 2 * slots * c.num_kv_heads * p * c.head_dim * 4
    for name, fn, t in (("a decode step's 8 lanes", decode_step, 1), ("a chunk group of 8 rows x 128", chunk_group, chunk)):
        q = jax.random.normal(keys[2], (rows, t, c.num_heads, c.head_dim), jnp.float32)
        ms = ms_a_call(fn, q)
        flops = 2 * 2 * rows * t * c.num_heads * c.head_dim * (w + (t - 1) / 2)
        line = (f"swa {name}: {ms:8.4f} ms a layer; the rings are {ring_bytes / 1e6:7.2f} MB, "
                f"the scores and values {flops / 1e9:7.3f} GFLOP counted once")
        if on_chip:
            line += (f": {ring_bytes / peaks['hbm_bytes_per_s'] * 1e3:7.4f} ms at the chip's bandwidth, "
                     f"{flops / peaks['bf16_flops_per_s'] * 1e3:7.4f} ms at its bf16 peak")
        print(line, flush=True)


def ep_profiles():
    """ONE expert layer of ``mellum2-12b-a2.5b-tp4`` ALONE on the chips of a
    host (``models/mellum.py:_expert_layer`` as its step programs call it: the
    router whole on every chip, 16 of 64 experts a chip out of a stack of one
    period's four layers, three bfloat16 parts a product, the chips' partial
    sums added by an all-reduce of ``[rows, 2304]`` float32), PROF_ITERS
    (default 8) layers chained in one dispatch, at ``code.mellum2-12b-a2.5b-tp4``'s
    two shapes: a chunk group's 8 rows x 128 tokens (8,192 pairs, 128 rows an
    expert) and a decode step's 16 lanes (128 pairs, 2 rows an expert). Each
    with the all-reduce and with a chip's own part alone, so that their
    difference is the exchange; beside them the bytes a chip streams (the
    experts it holds, once) and the bytes it hands the all-reduce over the
    chip's bandwidth, and the pairs the fullest chip got. On one device (or
    the CPU) it runs the same at ``tp`` = the devices there are."""
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import bytes_and_flops
    from dynamo_tpu.engine_jax.compile_cache import enable_compile_cache
    from dynamo_tpu.models import mellum
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    on_chip = jax.default_backend() == "tpu"
    peaks = bytes_and_flops.load_peaks(jax.devices()[0].device_kind) if on_chip else {}
    enable_compile_cache()
    n_iter = int(os.environ.get("PROF_ITERS", "8"))
    shards = min(4, len(jax.devices()))
    mesh = make_mesh(MeshConfig(tp=shards)) if shards > 1 else None
    c = mellum.MellumConfig(num_layers=4, layer_types=(mellum.WINDOW,) * 3 + (mellum.FULL,))
    axis = "tp" if mesh is not None else None
    layers = mellum._param_specs(axis)["layers"]
    made = jax.jit(lambda: mellum.init_params(jax.random.PRNGKey(0), c)["layers"], out_shardings=(
        jax.tree.map(lambda spec: NamedSharding(mesh, spec), layers, is_leaf=lambda s: isinstance(s, P))
        if mesh is not None else None))()
    e = c.hidden_size

    def layers_chained(exchange):
        def local(params, x):
            per_period, experts = mellum._split({"layers": params})
            lp = jax.tree.map(lambda a: a[0, 0], per_period)
            valid = jnp.ones(x.shape[:2], bool)

            def one(x, i):
                y, _, pairs = mellum._expert_layer(lp, experts, c, axis if exchange else None, i % 4,
                                                   mellum.rms_norm(x, lp["mlp_norm"], c.rms_norm_eps), valid)
                return x + y, pairs
            x, pairs = jax.lax.scan(one, x, jnp.arange(n_iter))
            return x, pairs[0]

        if mesh is None:
            return jax.jit(local)
        return jax.jit(shard_map(local, mesh=mesh, in_specs=(layers, P()), out_specs=(P(), P()),
                                 check_vma=False))

    held = c.num_experts // shards * 3 * e * c.moe_intermediate_size * 2
    for name, rows, t in (("a chunk group of 8 rows x 128", 8, 128), ("a decode step's 16 lanes", 16, 1)):
        x = jax.random.normal(jax.random.PRNGKey(1), (rows, t, e), jnp.float32)
        summed, own = layers_chained(True), layers_chained(False)
        with_sum, alone = (median_ms(fn, made, x) / n_iter for fn in (summed, own))
        pairs = np.asarray(summed(made, x)[1])
        sent = rows * t * e * 4
        line = (f"ep {name}: {with_sum:8.4f} ms a layer with the all-reduce, {alone:8.4f} ms a chip's own part "
                f"alone; a chip holds {held / 1e6:7.2f} MB of experts and hands the sum {sent / 1e6:6.3f} MB; "
                f"pairs a chip {pairs.tolist()} of {int(pairs.sum())}")
        if on_chip:
            line += (f": {held / peaks['hbm_bytes_per_s'] * 1e3:7.4f} ms and {sent / peaks['hbm_bytes_per_s'] * 1e3:7.4f} ms "
                     f"at the chip's bandwidth")
        print(line, flush=True)


if __name__ == "__main__":
    {"history": history_profiles, "experts": expert_profiles, "kda": kda_profiles,
     "mamba": mamba_profiles, "groups": chunk_group_profiles, "mla": mla_profiles, "mhc": mhc_profiles,
     "swa": swa_profiles, "ep": ep_profiles}.get(" ".join(sys.argv[1:2]), main)()
