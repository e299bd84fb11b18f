"""Speculative decoding (self-draft) + int8 KV cache: engine-level tests.

The decisive assertions (ISSUE 7 acceptance): speculative decode emits
token-for-token IDENTICAL output to non-speculative greedy decode for
k ∈ {1, 2, 4} — including the penalties and logprobs paths — and the
spec-off default pays nothing (no drafter is ever constructed, no verify
variant ever compiles). int8 KV pages stay within tolerance of the native
pool on the tiny model and survive host-tier offload/re-hit with exact
output parity.
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine_jax.drafter import (
    MAX_SPEC_K,
    NgramDrafter,
    env_kv_dtype,
    env_spec_k,
    env_spec_ngram,
)
from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
from dynamo_tpu.llm.protocols.common import PreprocessedRequest, StopConditions
from dynamo_tpu.models.llama import LLAMA_PRESETS, init_params
from dynamo_tpu.runtime.engine import Context

from .step_programs import collect

CFG = dataclasses.replace(LLAMA_PRESETS["tiny"], dtype=jnp.float32)
ENGINE_CFG = EngineConfig(max_slots=4, kv_block_size=8, max_model_len=128)

# repetition-heavy prompt: the shape prompt-lookup drafting exists for
REP_PROMPT = ([3, 1, 4, 1, 5, 9, 2, 6] * 4)[:24]


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _spec_engine(params, k, **kw):
    return JaxServingEngine(
        CFG, params, dataclasses.replace(ENGINE_CFG, spec_k=k, **kw)
    )


# -- knob parsers -------------------------------------------------------------


@pytest.mark.parametrize("raw,expect", [
    (None, 0), ("", 0), ("garbage", 0), ("-3", 0), ("4", 4),
    ("999", MAX_SPEC_K), ("0", 0),
])
def test_env_spec_k_clamps(monkeypatch, raw, expect):
    if raw is None:
        monkeypatch.delenv("DYN_TPU_SPEC_K", raising=False)
    else:
        monkeypatch.setenv("DYN_TPU_SPEC_K", raw)
    assert env_spec_k() == expect


@pytest.mark.parametrize("raw,expect", [
    (None, 3), ("junk", 3), ("0", 1), ("5", 5), ("99", 8),
])
def test_env_spec_ngram_clamps(monkeypatch, raw, expect):
    if raw is None:
        monkeypatch.delenv("DYN_TPU_SPEC_NGRAM", raising=False)
    else:
        monkeypatch.setenv("DYN_TPU_SPEC_NGRAM", raw)
    assert env_spec_ngram() == expect


@pytest.mark.parametrize("raw,expect", [
    (None, "bf16"), ("", "bf16"), ("INT8", "int8"), (" int8 ", "int8"),
    ("fp8", "bf16"), ("1", "bf16"),
])
def test_env_kv_dtype_never_accidentally_quantizes(monkeypatch, raw, expect):
    if raw is None:
        monkeypatch.delenv("DYN_TPU_KV_DTYPE", raising=False)
    else:
        monkeypatch.setenv("DYN_TPU_KV_DTYPE", raw)
    assert env_kv_dtype() == expect


@pytest.mark.parametrize("bad", ["INT8", "Int8", "fp8", "bfloat16"])
def test_engine_config_kv_dtype_validated(params, bad):
    """The env parser degrades typos to native (a typo must never silently
    quantize a fleet), but an explicit config value is a programming error:
    'INT8' silently measuring bf16 would invalidate a benchmark run."""
    with pytest.raises(ValueError, match="kv_dtype"):
        JaxServingEngine(
            CFG, params, dataclasses.replace(ENGINE_CFG, kv_dtype=bad)
        )


# -- drafter unit -------------------------------------------------------------


def test_drafter_proposes_continuation_of_repeated_suffix():
    d = NgramDrafter([1, 2, 3, 4, 1, 2, 3], k=4, ngram_max=3)
    # suffix (2, 3) last occurred at position 3 → proposes what followed: 4...
    assert d.draft() == [4, 1, 2, 3][:4]


def test_drafter_no_match_returns_none():
    d = NgramDrafter([1, 2, 3, 4, 5, 6], k=4)
    assert d.draft() is None


def test_drafter_live_suffix_skips_itself():
    # the trailing gram registers itself on append; a draft must use the
    # occurrence BEFORE it, and with no earlier occurrence there is none
    d = NgramDrafter([7, 8], k=4)
    assert d.draft() is None
    d.extend([7, 8])  # now (7, 8) occurred twice → draft continues from pos 2
    assert d.draft() == [7, 8]


def test_drafter_goes_dormant_under_sustained_rejection():
    d = NgramDrafter([1, 2] * 16, k=4)
    assert d.draft() is not None
    for _ in range(20):
        d.note_result(4, 0)  # 80 drafted, 0 accepted
    assert d.dormant
    assert d.draft() is None


def test_drafter_would_draft_mirrors_draft():
    """would_draft is the pre-drain gate: it must agree with draft() on
    match/no-match (incl. the live-suffix self-skip) and respect dormancy,
    without building a proposal."""
    assert not NgramDrafter([1, 2, 3, 4, 5, 6], k=4).would_draft()
    assert NgramDrafter([1, 2, 3, 4, 1, 2, 3], k=4).would_draft()
    d = NgramDrafter([7, 8], k=4)
    assert not d.would_draft()  # trailing gram only matches itself
    d.extend([7, 8])
    assert d.would_draft()
    for _ in range(20):
        d.note_result(4, 0)
    assert d.dormant and not d.would_draft()


# -- greedy equivalence (the tentpole assertion) ------------------------------


@pytest.mark.parametrize("k", [1, 2, 4])
def test_spec_greedy_bitwise_equals_nonspec(params, run, k):
    base = JaxServingEngine(CFG, params, ENGINE_CFG)
    try:
        golden, _, gfin = run(collect(base, REP_PROMPT))
    finally:
        base.close()
    eng = _spec_engine(params, k)
    try:
        toks, _, fin = run(collect(eng, REP_PROMPT))
        snap = eng.metrics_snapshot()
    finally:
        eng.close()
    assert (toks, fin) == (golden, gfin)
    assert snap["spec_drafted_tokens"] > 0, "test must actually speculate"


def test_spec_penalties_path_equivalence(params, run):
    """Penalized greedy decode is deterministic: the verify scan's
    sequentially-carried count buffer must reproduce it token for token,
    and the post-dispatch count resync must keep later dispatches exact.

    Penalties make output anti-repetitive, so a penalized lane itself
    rarely drafts — the penalized VERIFY path is exercised by batching a
    penalized lane with a drafting (repetitive, unpenalized) lane: every
    verify dispatch then runs the with_pen variant with real drafts."""
    pen = dict(frequency_penalty=0.7, presence_penalty=0.4)
    async def both(engine):
        return await asyncio.gather(
            collect(engine, REP_PROMPT, **pen),
            collect(engine, REP_PROMPT),
        )

    base = JaxServingEngine(CFG, params, ENGINE_CFG)
    try:
        golden = run(both(base))
    finally:
        base.close()
    eng = _spec_engine(params, 4)
    try:
        results = run(both(eng))
        snap = eng.metrics_snapshot()
    finally:
        eng.close()
    assert results[0][0] == golden[0][0], "penalized lane diverged"
    assert results[1][0] == golden[1][0], "drafting lane diverged"
    assert snap["spec_drafted_tokens"] > 0, "batch must actually speculate"


def test_spec_penalties_no_per_step_count_rebuild(params, run):
    """Verify dispatches correct penalty-count pollution with an O(spec_k)
    subtraction of the non-emitted targets (``_counts_fix_fn``), NOT by
    invalidating rows: across a whole penalized speculative generation the
    [S, V] count buffer is rebuilt from out_tokens at most once per lane
    (admission) — a per-dispatch rebuild would re-stream the entire output
    history every step, O(out_tokens²) over a generation."""
    pen = dict(frequency_penalty=0.7, presence_penalty=0.4)
    eng = _spec_engine(params, 4)
    rebuilds = []
    orig_fn = eng._counts_sync_fn

    def spy(rb, pb):
        rebuilds.append((rb, pb))
        return orig_fn(rb, pb)

    eng._counts_sync_fn = spy

    async def wave():
        return await asyncio.gather(
            collect(eng, REP_PROMPT, **pen), collect(eng, REP_PROMPT)
        )

    try:
        run(wave())
        snap = eng.metrics_snapshot()
    finally:
        eng.close()
    assert snap["spec_drafted_tokens"] > 0, "batch must actually speculate"
    # one rebuild program at the penalized lane's admission (out_tokens
    # empty → pair bucket 1), nothing per step after that
    assert len(rebuilds) <= 1, rebuilds


def test_spec_logprobs_path_equivalence(params, run):
    base = JaxServingEngine(CFG, params, ENGINE_CFG)
    try:
        golden, glps, _ = run(collect(base, REP_PROMPT, with_lp=True))
    finally:
        base.close()
    eng = _spec_engine(params, 4)
    try:
        toks, lps, _ = run(collect(eng, REP_PROMPT, with_lp=True))
        snap = eng.metrics_snapshot()
    finally:
        eng.close()
    assert toks == golden
    assert len(lps) == len(glps)
    # logits flow through a different (chunk vs window) attention schedule:
    # identical math, different f32 reduction order
    np.testing.assert_allclose(lps, glps, atol=1e-3)
    assert snap["spec_drafted_tokens"] > 0


def test_spec_concurrent_mixed_workload(params, run):
    """Repetitive and adversarial prompts sharing the batch: every lane
    matches the non-speculative engine exactly (lanes without drafts ride
    the verify dispatch as single-position lanes)."""
    prompts = [
        REP_PROMPT,
        [11, 22, 33, 44, 55, 66, 77],
        ([9, 8, 7] * 8)[:18],
        [5, 4, 3, 2, 1],
    ]
    async def wave(engine):
        return await asyncio.gather(
            *[collect(engine, p, max_tokens=10) for p in prompts]
        )

    base = JaxServingEngine(CFG, params, ENGINE_CFG)
    try:
        golden = run(wave(base))
    finally:
        base.close()
    eng = _spec_engine(params, 4)
    try:
        results = run(wave(eng))
    finally:
        eng.close()
    for p, got, want in zip(prompts, results, golden):
        assert got[0] == want[0], f"prompt {p}"


def test_spec_non_repeating_prompt_never_pays_verify_drain(params, run):
    """Adversarial-workload overhead bound: a verify dispatch drains the
    decode pipeline, so the engine must not even ATTEMPT one for a lane
    whose suffix index holds no match (would_draft pre-drain gate) —
    dormancy alone can't cover this, a drafter that never proposes never
    accumulates drafted tokens. With an all-distinct prompt and
    max_tokens=2, no gram can have a prior occurrence at any probe point
    (the earliest possible generated repeat indexes only after the final
    token), so _verify_step is provably unreachable; REP_PROMPT on the
    same spy must take it."""
    distinct = list(range(40, 60))
    eng = _spec_engine(params, 4)
    calls = []
    orig = eng._verify_step
    eng._verify_step = lambda: (calls.append(1), orig())[1]
    try:
        toks, _, _ = run(collect(eng, distinct, max_tokens=2))
        assert calls == []
        run(collect(eng, REP_PROMPT, max_tokens=12))
        assert calls, "repetitive prompt must exercise the verify path"
        snap = eng.metrics_snapshot()
    finally:
        eng.close()
    assert snap["spec_drafted_tokens"] > 0
    base = JaxServingEngine(CFG, params, ENGINE_CFG)
    try:
        golden, _, _ = run(collect(base, distinct, max_tokens=2))
    finally:
        base.close()
    assert toks == golden


def test_spec_eos_cuts_inside_accepted_run(params, run):
    base = JaxServingEngine(CFG, params, ENGINE_CFG)
    try:
        ref, _, _ = run(collect(base, REP_PROMPT, max_tokens=12))
    finally:
        base.close()
    eos = ref[5]
    first = ref.index(eos)

    async def go(engine):
        req = PreprocessedRequest(
            token_ids=REP_PROMPT,
            stop_conditions=StopConditions(max_tokens=12),
            eos_token_ids=[eos],
        )
        toks, finish = [], None
        async for item in engine.generate(Context(req)):
            d = item.data or {}
            toks.extend(d.get("token_ids", []))
            if d.get("finish_reason"):
                finish = d["finish_reason"]
        return toks, finish

    eng = _spec_engine(params, 4)
    try:
        toks, finish = run(go(eng))
    finally:
        eng.close()
    assert finish == "eos"
    assert toks == ref[: first + 1]


def test_spec_preemption_parity(params, run):
    """Out-of-blocks preemption during speculative decode must
    recompute-resume with exact greedy parity, like the plain path."""
    cfg = EngineConfig(
        max_slots=2, kv_block_size=8, max_model_len=48, num_kv_blocks=6,
        prefill_chunk=16,
    )
    async def both(engine):
        return await asyncio.gather(
            collect(engine, REP_PROMPT[:8], max_tokens=18),
            collect(engine, REP_PROMPT[2:10], max_tokens=18),
        )

    base = JaxServingEngine(CFG, params, cfg)
    try:
        golden = run(both(base))
    finally:
        base.close()
    eng = JaxServingEngine(CFG, params, dataclasses.replace(cfg, spec_k=4))
    try:
        results = run(both(eng))
        assert eng.preemptions > 0, "test must actually exercise preemption"
    finally:
        eng.close()
    assert [r[0] for r in results] == [g[0] for g in golden]


# -- zero-overhead guard (spec off, native KV: the defaults pay nothing) ------


def test_spec_off_never_builds_drafter_or_verify_fn(params, run, monkeypatch):
    """DYN_TPU_SPEC_K unset (the default): no NgramDrafter is ever
    constructed, no verify variant is ever compiled, and the snapshot
    reports zeroed speculation counters — the PR5/PR6 zero-work pattern."""
    from dynamo_tpu.engine_jax import engine as engine_mod

    monkeypatch.delenv("DYN_TPU_SPEC_K", raising=False)
    monkeypatch.delenv("DYN_TPU_KV_DTYPE", raising=False)

    def _boom(*a, **kw):
        raise AssertionError("NgramDrafter constructed with speculation off")

    monkeypatch.setattr(engine_mod, "NgramDrafter", _boom)
    eng = JaxServingEngine(CFG, params, ENGINE_CFG)
    try:
        assert eng._spec_k == 0 and not eng._kv_quantized
        toks, _, _ = run(collect(eng, REP_PROMPT, max_tokens=8))
        assert len(toks) == 8
        assert eng._verify_fns == {}
        snap = eng.metrics_snapshot()
    finally:
        eng.close()
    assert snap["spec_drafted_tokens"] == 0
    assert snap["spec_accepted_tokens"] == 0
    assert snap["kv_quantized"] == 0


# -- int8 KV cache ------------------------------------------------------------


def test_int8_kv_within_tolerance_of_native(params, run):
    base = JaxServingEngine(CFG, params, ENGINE_CFG)
    try:
        golden, _, _ = run(collect(base, REP_PROMPT, max_tokens=16))
    finally:
        base.close()
    eng = JaxServingEngine(
        CFG, params, dataclasses.replace(ENGINE_CFG, kv_dtype="int8")
    )
    try:
        assert "k_scale" in eng.cache and eng.cache["k"].dtype == jnp.int8
        toks, _, _ = run(collect(eng, REP_PROMPT, max_tokens=16))
        snap = eng.metrics_snapshot()
    finally:
        eng.close()
    assert snap["kv_quantized"] == 1
    agree = sum(a == b for a, b in zip(toks, golden))
    assert agree >= int(0.9 * len(golden)), (toks, golden)


def test_int8_kv_with_speculation_matches_itself(params, run):
    """Speculation must stay output-neutral over an int8 pool too (verify
    and decode read the same dequantized pages)."""
    plain = JaxServingEngine(
        CFG, params, dataclasses.replace(ENGINE_CFG, kv_dtype="int8")
    )
    try:
        golden, _, _ = run(collect(plain, REP_PROMPT, max_tokens=16))
    finally:
        plain.close()
    eng = JaxServingEngine(
        CFG, params,
        dataclasses.replace(ENGINE_CFG, kv_dtype="int8", spec_k=4),
    )
    try:
        toks, _, _ = run(collect(eng, REP_PROMPT, max_tokens=16))
        snap = eng.metrics_snapshot()
    finally:
        eng.close()
    assert toks == golden
    assert snap["spec_drafted_tokens"] > 0


def test_int8_kv_host_pool_offload_and_rehit_parity(params, run):
    """Eviction of int8 pages spills values AND scale tables to the host
    pool; the re-hit injects both back — output must be exactly the first
    run's (scale-less reinjection would corrupt every dequantized read)."""
    cfg = EngineConfig(
        max_slots=2, kv_block_size=8, max_model_len=64, num_kv_blocks=8,
        prefill_chunk=16, host_cache_blocks=32, kv_dtype="int8",
    )
    eng = JaxServingEngine(CFG, params, cfg)
    try:
        prompt_a = [(3 * i + 1) % 100 for i in range(32)]
        prompt_b = [(5 * i + 2) % 100 for i in range(32)]
        t1, _, _ = run(collect(eng, prompt_a, max_tokens=4))
        run(collect(eng, prompt_b, max_tokens=4))
        assert eng.host_pool.offloaded > 0
        # spilled entries carry their scale tables
        block, _ = next(iter(eng.host_pool._data.values()))
        assert {"k_scale", "v_scale"} <= set(block)
        assert block["k"].dtype == np.int8
        hits_before = eng.host_pool.hits
        t2, _, _ = run(collect(eng, prompt_a, max_tokens=4))
        assert eng.host_pool.hits > hits_before
        assert t2 == t1
    finally:
        eng.close()


def test_int8_kv_rejects_sharded_cache(params):
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(tp=2))
    with pytest.raises(ValueError, match="int8"):
        JaxServingEngine(
            CFG, params,
            dataclasses.replace(ENGINE_CFG, kv_dtype="int8"), mesh=mesh,
        )
