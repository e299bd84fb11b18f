"""Performance attribution plane (ISSUE 15): per-dispatch engine
profiling, frontend hot-path timing, and Perfetto-loadable timeline
export (docs/observability.md §Profiling).

Coverage:

- knob clamp tables + the DYN_TPU_PROFILE-off zero-overhead guard
  (monkeypatched StepTimeline/FrontendCpu/EventLoopLagSampler
  constructors: nothing is ever built on an engine step, an HTTP stream,
  or a detokenize pass);
- StepTimeline units: ring bound, sampling stride, since-window reads,
  per-phase summary quantiles, device_idle_frac math, jit-compile events
  forwarded from ``compile_cache.record_compile`` with shape detail;
- a REAL tiny engine with profiling armed: decode records whose
  host+device+post split covers the sampled wall span (the llmctl
  acceptance books), request/trace ids (PR5) riding the records, and the
  profiling gauges on metrics_snapshot;
- Chrome-trace export: JSON round trip, slices sorted and non-overlapping
  per track, process/thread metadata, PR5 ids in slice args;
- frontend: serialize/transport-write CPU attribution + the
  ``detokenize``/``serialize`` phases on the PR5 histograms, the
  event-loop lag sampler, ``GET /debug/profile`` (+ ``?trace=1``), and
  promtext-valid /metrics exposition;
- gauges worker → aggregator → cluster (promtext-parsed: max-not-sum for
  p95s/idle, summed recompiles) + mock_worker drill flags;
- ``llmctl profile capture`` e2e over a real statestore + RPC plane
  (--json summary and --trace Chrome-trace file).
"""

import asyncio
import dataclasses
import json

import pytest

from dynamo_tpu.runtime import profiling
from dynamo_tpu.runtime.profiling import (
    FrontendCpu,
    ProfilePolicy,
    StepTimeline,
)

NO_BUS = "127.0.0.1:1"


def _arm(monkeypatch, sample="1", ring=None):
    monkeypatch.setenv("DYN_TPU_PROFILE", "1")
    monkeypatch.setenv("DYN_TPU_PROFILE_SAMPLE", sample)
    if ring is not None:
        monkeypatch.setenv("DYN_TPU_PROFILE_RING", str(ring))
    profiling.reset_for_tests()


# -- knobs ---------------------------------------------------------------------


class TestKnobs:
    def test_defaults_off(self, monkeypatch):
        monkeypatch.delenv("DYN_TPU_PROFILE", raising=False)
        assert not profiling.enabled()
        assert profiling.maybe_from_env() is None
        pol = ProfilePolicy()
        assert pol.enabled is False
        assert pol.sample_every == 8
        assert pol.ring_size == 4096

    def test_armed(self, monkeypatch):
        monkeypatch.setenv("DYN_TPU_PROFILE", "1")
        assert profiling.enabled()
        pol = profiling.maybe_from_env()
        assert pol is not None and pol.enabled

    @pytest.mark.parametrize("env,attr,value,expect", [
        ("DYN_TPU_PROFILE_SAMPLE", "sample_every", "0", 8),      # non-positive
        ("DYN_TPU_PROFILE_SAMPLE", "sample_every", "junk", 8),   # malformed
        ("DYN_TPU_PROFILE_SAMPLE", "sample_every", "99999999", 1_000_000),
        ("DYN_TPU_PROFILE_SAMPLE", "sample_every", "3", 3),
        ("DYN_TPU_PROFILE_RING", "ring_size", "1", 256),         # clamp lo
        ("DYN_TPU_PROFILE_RING", "ring_size", "9999999", 262_144),
        ("DYN_TPU_PROFILE_RING", "ring_size", "-5", 4096),
        ("DYN_TPU_PROFILE_LAG_MS", "lag_ms", "0.001", 5.0),
        ("DYN_TPU_PROFILE_LAG_MS", "lag_ms", "bogus", 100.0),
        ("DYN_TPU_PROFILE_LAG_MS", "lag_ms", "50000", 10_000.0),
    ])
    def test_clamps(self, monkeypatch, env, attr, value, expect):
        monkeypatch.setenv("DYN_TPU_PROFILE", "1")
        monkeypatch.setenv(env, value)
        assert getattr(ProfilePolicy.from_env(), attr) == expect


# -- StepTimeline units --------------------------------------------------------


class TestStepTimeline:
    def test_ring_bound_and_records(self):
        tl = StepTimeline(ProfilePolicy(enabled=True, ring_size=300))
        for i in range(500):
            tl.note_dispatch("decode", step=i, device_us=10.0, host_us=5.0)
        recs = tl.records()
        # the deque maxlen clamps at the POLICY floor (256) or the asked
        # size, whichever the clamp produced — here exactly 300
        assert len(recs) == 300
        assert recs[-1]["step"] == 499

    def test_sampling_stride(self):
        tl = StepTimeline(ProfilePolicy(enabled=True, sample_every=4))
        decisions = [tl.should_sample() for _ in range(12)]
        assert sum(decisions) == 3
        assert tl.dispatches_total == 12

    def test_since_window(self):
        tl = StepTimeline(ProfilePolicy(enabled=True))
        tl.note_dispatch("decode", step=1, device_us=1.0, ts=1000.0)
        tl.note_dispatch("decode", step=2, device_us=1.0)  # now
        assert len(tl.records()) == 2
        assert len(tl.records(since_s=60.0)) == 1

    def test_summary_quantiles_and_gauges(self):
        tl = StepTimeline(ProfilePolicy(enabled=True))
        for i in range(100):
            tl.note_dispatch(
                "decode", step=i, batch=4, tokens=4,
                device_us=float(i + 1), host_us=10.0, post_us=2.0,
            )
        s = tl.summary()
        dec = s["phases"]["decode"]
        assert dec["count"] == 100
        assert dec["device_us_p50"] == pytest.approx(51.0, abs=2.0)
        assert dec["device_us_p95"] == pytest.approx(96.0, abs=2.0)
        assert dec["host_us_p95"] == 12.0  # host + post
        g = tl.gauges()
        assert g["dispatch_device_us_p95"] == dec["device_us_p95"]
        assert g["dispatch_host_overhead_us_p95"] == dec["host_us_p95"]

    def test_device_idle_frac(self):
        # adjacent steps 10ms apart, device busy 5ms each → idle 0.5
        recs = [
            {"ts": 100.0 + i * 0.010, "phase": "decode", "step": i,
             "device_us": 5_000.0, "host_us": 0.0, "post_us": 0.0}
            for i in range(11)
        ]
        assert StepTimeline.device_idle_frac(recs) == pytest.approx(
            0.5, abs=0.01
        )
        # a sampling stride scales the sampled device time by the step
        # delta: a fully-busy device sampled every 4th dispatch (2.5ms
        # device per dispatch → 4 × 2.5ms fills each 10ms gap) must read
        # ~0 idle, never ~0.75
        strided = [
            dict(r, step=r["step"] * 4, device_us=2_500.0) for r in recs
        ]
        assert StepTimeline.device_idle_frac(strided) == pytest.approx(
            0.0, abs=0.01
        )
        assert StepTimeline.device_idle_frac(recs[:1]) == 0.0
        # a step-counter reset (engine restart) mid-window is skipped, not
        # a negative-stride crash
        reset = recs[:3] + [dict(recs[3], step=0)]
        assert 0.0 <= StepTimeline.device_idle_frac(reset) <= 1.0

    def test_lag_samples_ride_their_own_ring(self):
        """Event-loop lag samples must not consume the engine dispatch
        ring or count into sampled_total (a co-hosted engine+frontend
        shares the timeline)."""
        tl = StepTimeline(ProfilePolicy(enabled=True, ring_size=300))
        tl.note_dispatch("decode", step=1, device_us=10.0)
        for _ in range(600):  # far past the engine ring size
            tl.note_dispatch("loop_lag", host_us=100.0)
        assert tl.sampled_total == 1
        recs = tl.records()
        assert sum(1 for r in recs if r["phase"] == "decode") == 1
        assert sum(1 for r in recs if r["phase"] == "loop_lag") > 0

    def test_jit_compile_events_via_record_compile(self, monkeypatch):
        _arm(monkeypatch)
        tl = profiling.timeline()
        from dynamo_tpu.engine_jax.compile_cache import record_compile

        record_compile("decode", detail="lp=False [S=4,k=1]")
        evs = tl.events()
        assert any(
            e["kind"] == "jit_compile" and "S=4" in e["detail"] for e in evs
        )
        assert tl.jit_compiles_total == 1

    def test_note_event_constructor_free(self, monkeypatch):
        monkeypatch.delenv("DYN_TPU_PROFILE", raising=False)
        profiling.reset_for_tests()
        # no timeline armed: note_event must be a no-op, not a constructor
        profiling.note_event("jit_compile", "x")
        assert profiling.maybe_timeline() is None
        assert profiling.gauges() == {}
        st = profiling.dump_state()
        assert st["enabled"] is False and st["records"] == []

    def test_reset_for_tests(self, monkeypatch):
        _arm(monkeypatch)
        profiling.timeline().note_dispatch("decode", step=1)
        profiling.frontend_cpu().note("serialize", 5.0, tokens=1)
        profiling.reset_for_tests()
        assert profiling.maybe_timeline() is None
        assert profiling.maybe_frontend_cpu() is None


class TestFrontendCpu:
    def test_per_part_normalization(self):
        fc = FrontendCpu()
        fc.note("serialize", 100.0, tokens=10)
        fc.note("transport_write", 50.0, tokens=10)
        fc.note("detokenize", 90.0, tokens=30)  # its OWN token count
        per = fc.per_token()
        assert per["serialize"] == 10.0
        assert per["transport_write"] == 5.0
        assert per["detokenize"] == 3.0
        assert per["tokens"]["detokenize"] == 30

    def test_prometheus_render(self, monkeypatch):
        _arm(monkeypatch)
        profiling.frontend_cpu().note("serialize", 42.0, tokens=2)
        text = profiling.render_frontend_prometheus()
        assert 'dynamo_frontend_cpu_us_per_token{part="serialize"} 21' in text


# -- tiny real engine ----------------------------------------------------------


def _tiny_engine(max_slots=4, max_len=128):
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
    from dynamo_tpu.models.llama import LLAMA_PRESETS, init_params

    cfg = dataclasses.replace(LLAMA_PRESETS["tiny"], dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return JaxServingEngine(cfg, params, EngineConfig(
        max_slots=max_slots, kv_block_size=8, max_model_len=max_len,
    ))


async def _drive(eng, prompt, n=32):
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context

    req = PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=n, ignore_eos=True),
        sampling_options=SamplingOptions(),
    )
    ctx = Context(req)
    toks = []
    async for item in eng.generate(ctx):
        d = item.data
        if d:
            toks.extend(d.get("token_ids", []))
    return toks, ctx


class TestEngineProfiling:
    def test_records_split_ids_and_gauges(self, monkeypatch, run):
        """The acceptance books: with every dispatch sampled, the decode
        device/host split must cover the wall time between adjacent
        dispatches, records must carry the PR5 request/trace ids, and the
        snapshot must carry the three worker gauges."""
        _arm(monkeypatch, sample="1")
        from dynamo_tpu.llm.protocols.common import (
            PreprocessedRequest,
            SamplingOptions,
            StopConditions,
        )
        from dynamo_tpu.runtime import tracing
        from dynamo_tpu.runtime.engine import Context

        eng = _tiny_engine()
        try:
            req = PreprocessedRequest(
                token_ids=[3, 1, 4, 1, 5],
                stop_conditions=StopConditions(
                    max_tokens=48, ignore_eos=True
                ),
                sampling_options=SamplingOptions(),
            )
            ctx = Context(req)
            span = tracing.start_span("test.root")
            ctx.context.trace = span

            async def go():
                toks = []
                async for item in eng.generate(ctx):
                    d = item.data
                    if d:
                        toks.extend(d.get("token_ids", []))
                return toks

            toks = run(go())
            assert len(toks) == 48
        finally:
            eng.close()

        tl = profiling.maybe_timeline()
        assert tl is not None
        recs = [r for r in tl.records() if r["phase"] == "decode"]
        assert len(recs) >= 16
        # PR5 link: the batch's request id and trace id ride the record
        assert any(ctx.id in r.get("reqs", []) for r in recs)
        assert any(span.trace_id in r.get("traces", []) for r in recs)
        # the split, as the phase clock feeds it (PR 39): ``host_us`` and
        # ``post_us`` are self time INSIDE one host step, so between adjacent
        # sampled dispatches they never add up to more than the gap;
        # ``device_us`` is dispatch -> its blocking read returned as the host
        # observed it, never forced: in pipelined decode the read of dispatch
        # N returns in host step N + 1, so it spans up to two steps and is an
        # upper bound of the device's own time
        recs.sort(key=lambda r: r["ts"])
        span_s = host = device = 0.0
        for a, b in zip(recs, recs[1:]):
            if b["step"] - a["step"] != 1:
                continue
            gap = b["ts"] - a["ts"]
            if gap <= 0:
                continue
            span_s += gap
            host += (a["host_us"] + a["post_us"]) / 1e6
            device += a["device_us"] / 1e6
        assert span_s > 0
        assert 0.0 < host / span_s <= 1.0, f"host split covers {host / span_s:.2f}"
        # (the first decode step reads nothing back: no dispatch before it)
        assert all(r["device_us"] > 0 for r in recs[1:])
        assert device / span_s <= 2.05, f"observed device time {device / span_s:.2f}"
        # events carry the compile detail (variant key + shapes)
        assert any(
            e["kind"] == "jit_compile" and "S=4" in e["detail"]
            for e in tl.events()
        )
        s = tl.summary()
        assert 0.0 <= s["device_idle_frac"] <= 1.0
        # engine snapshot carries the worker gauges
        # (engine closed above; the timeline outlives it)
        assert tl.gauges()["dispatch_device_us_p95"] > 0

    def test_snapshot_gauges_live(self, monkeypatch, run):
        _arm(monkeypatch, sample="1")
        eng = _tiny_engine()
        try:
            run(_drive(eng, [1, 2, 3], n=8))
            m = eng.metrics_snapshot()
            assert m["dispatch_device_us_p95"] > 0
            assert "dispatch_host_overhead_us_p95" in m
            assert 0.0 <= m["device_idle_frac"] <= 1.0
        finally:
            eng.close()

    def test_sampling_stride_bounds_records(self, monkeypatch, run):
        _arm(monkeypatch, sample="8")
        eng = _tiny_engine()
        try:
            run(_drive(eng, [1, 2, 3], n=33))
        finally:
            eng.close()
        tl = profiling.maybe_timeline()
        assert tl is not None
        assert tl.dispatches_total > tl.sampled_total
        assert tl.sampled_total >= 3

    def test_zero_overhead_guard(self, monkeypatch, run):
        """DYN_TPU_PROFILE off: provably zero profiling objects — the
        constructors raise if anything tries."""
        monkeypatch.delenv("DYN_TPU_PROFILE", raising=False)
        profiling.reset_for_tests()

        def boom(*a, **k):
            raise AssertionError("profiling object built with plane off")

        monkeypatch.setattr(profiling.StepTimeline, "__init__", boom)
        monkeypatch.setattr(profiling.FrontendCpu, "__init__", boom)
        monkeypatch.setattr(profiling.EventLoopLagSampler, "__init__", boom)
        eng = _tiny_engine(max_slots=2, max_len=64)
        try:
            assert eng._timeline is None
            toks, _ = run(_drive(eng, [1, 2, 3], n=6))
            assert len(toks) == 6
            m = eng.metrics_snapshot()
            assert "dispatch_device_us_p95" not in m
        finally:
            eng.close()

    def test_profiling_does_not_change_output(self, monkeypatch, run):
        """Bitwise determinism: greedy output with the profiler sampling
        every dispatch equals the unprofiled output."""
        monkeypatch.delenv("DYN_TPU_PROFILE", raising=False)
        profiling.reset_for_tests()
        eng = _tiny_engine()
        try:
            base, _ = run(_drive(eng, [7, 8, 9, 2], n=24))
        finally:
            eng.close()
        _arm(monkeypatch, sample="1")
        eng = _tiny_engine()
        try:
            prof, _ = run(_drive(eng, [7, 8, 9, 2], n=24))
        finally:
            eng.close()
        assert prof == base


# -- Chrome-trace export -------------------------------------------------------


def _assert_valid_chrome_trace(trace):
    """The schema-validity contract: loads, required keys, slices sorted
    and non-overlapping per (pid, tid) track."""
    trace = json.loads(json.dumps(trace))  # round-trips as plain JSON
    assert "traceEvents" in trace
    per_track = {}
    for ev in trace["traceEvents"]:
        assert "ph" in ev and "pid" in ev
        if ev["ph"] == "X":
            assert ev["dur"] >= 0 and ev["ts"] >= 0
            per_track.setdefault((ev["pid"], ev["tid"]), []).append(ev)
        elif ev["ph"] == "M":
            assert ev["name"] in ("process_name", "thread_name")
    for (pid, tid), slices in per_track.items():
        end = -1.0
        for s in sorted(slices, key=lambda s: s["ts"]):
            assert s["ts"] >= end - 1e-6, (
                f"overlapping slices on track pid={pid} tid={tid}"
            )
            end = s["ts"] + s["dur"]
    return trace


class TestChromeTrace:
    def test_synthetic_trace_schema(self):
        recs = [
            {"ts": 10.0 + i * 0.001, "phase": "decode", "step": i,
             "batch": 2, "tokens": 2, "host_us": 200.0, "device_us": 600.0,
             "post_us": 100.0, "alloc_us": 50.0, "queue": 1,
             "reqs": [f"r{i}"], "traces": [f"t{i}"]}
            for i in range(20)
        ]
        evs = [{"ts": 10.005, "kind": "jit_compile", "detail": "decode x"}]
        trace = _assert_valid_chrome_trace(
            profiling.to_chrome_trace([("w0", recs, evs)])
        )
        names = {
            e["args"]["name"] for e in trace["traceEvents"]
            if e["ph"] == "M" and e["name"] == "thread_name"
        }
        assert "engine/decode" in names and "engine/host" in names
        # PR5 ids land in slice args
        assert any(
            e.get("args", {}).get("reqs") == ["r3"]
            for e in trace["traceEvents"] if e["ph"] == "X"
        )
        # the compile event renders as an instant
        assert any(e["ph"] == "i" for e in trace["traceEvents"])

    def test_pipelined_overlap_clamped(self):
        # device slices that would overlap on one track (pipelined decode)
        # are clamped forward, never emitted overlapping
        recs = [
            {"ts": 5.0, "phase": "decode", "step": 1, "batch": 1,
             "tokens": 1, "host_us": 0.0, "device_us": 10_000.0,
             "post_us": 0.0, "alloc_us": 0.0, "queue": 0},
            {"ts": 5.002, "phase": "decode", "step": 2, "batch": 1,
             "tokens": 1, "host_us": 0.0, "device_us": 10_000.0,
             "post_us": 0.0, "alloc_us": 0.0, "queue": 0},
        ]
        _assert_valid_chrome_trace(
            profiling.to_chrome_trace([("w0", recs, [])])
        )

    def test_engine_capture_renders(self, monkeypatch, run):
        _arm(monkeypatch, sample="1")
        eng = _tiny_engine()
        try:
            run(_drive(eng, [2, 7, 1], n=16))
        finally:
            eng.close()
        tl = profiling.maybe_timeline()
        trace = _assert_valid_chrome_trace(profiling.to_chrome_trace(
            [("worker-a", tl.records(), tl.events())]
        ))
        assert sum(
            1 for e in trace["traceEvents"] if e["ph"] == "X"
        ) >= 16


# -- frontend ------------------------------------------------------------------


def _http_service():
    from dynamo_tpu.llm.engines import EchoEngineFull
    from dynamo_tpu.llm.http.service import HttpService, ModelManager

    manager = ModelManager()
    manager.add_chat_model("echo", EchoEngineFull(delay_s=0.0))
    return HttpService(manager, host="127.0.0.1", port=0)


class TestFrontendProfiling:
    def test_stream_attribution_and_debug_profile(self, monkeypatch, run):
        import aiohttp

        _arm(monkeypatch)
        svc = _http_service()
        assert svc._fcpu is not None

        async def go():
            port = await svc.start()
            base = f"http://127.0.0.1:{port}"
            try:
                async with aiohttp.ClientSession() as session:
                    body = {
                        "model": "echo", "stream": True,
                        "messages": [{"role": "user",
                                      "content": "a b c d e f"}],
                    }
                    async with session.post(
                        f"{base}/v1/chat/completions", json=body
                    ) as resp:
                        assert resp.status == 200
                        async for _ in resp.content:
                            pass
                    # lag sampler needs at least one interval to tick
                    await asyncio.sleep(0.15)
                    async with session.get(f"{base}/metrics") as resp:
                        metrics_text = await resp.text()
                    async with session.get(f"{base}/debug/profile") as resp:
                        state = await resp.json()
                    async with session.get(
                        f"{base}/debug/profile?trace=1"
                    ) as resp:
                        trace = await resp.json()
            finally:
                await svc.stop()
            return metrics_text, state, trace

        metrics_text, state, trace = run(go())
        assert 'dynamo_frontend_cpu_us_per_token{part="serialize"}' in \
            metrics_text
        assert 'dynamo_frontend_event_loop_lag_ms{stat="ema"}' in \
            metrics_text
        assert state["enabled"] is True
        per = state["frontend_cpu_us_per_token"]
        assert per["tokens"]["serialize"] > 0
        assert state["event_loop_lag_ms"]["samples"] >= 1
        _assert_valid_chrome_trace(trace)
        # the serialize phase feeds the PR5 histograms too
        from dynamo_tpu.runtime import tracing

        phases = tracing.phase_summary()
        assert "serialize" in phases

    def test_metrics_exposition_stays_promtext_valid(self, monkeypatch, run):
        from .promtext import parse_prometheus_text

        _arm(monkeypatch)
        profiling.frontend_cpu().note("serialize", 10.0, tokens=1)
        profiling.frontend_cpu().note("detokenize", 10.0, tokens=1)
        svc = _http_service()

        async def go():
            await svc.start()
            try:
                return svc.metrics.render()
            finally:
                await svc.stop()

        text = run(go())
        fams = parse_prometheus_text(text)
        assert "dynamo_frontend_cpu_us_per_token" in fams

    def test_debug_profile_disarmed(self, monkeypatch, run):
        import aiohttp

        monkeypatch.delenv("DYN_TPU_PROFILE", raising=False)
        profiling.reset_for_tests()
        svc = _http_service()
        assert svc._fcpu is None

        async def go():
            port = await svc.start()
            try:
                async with aiohttp.ClientSession() as session:
                    async with session.get(
                        f"http://127.0.0.1:{port}/debug/profile"
                    ) as resp:
                        assert resp.status == 200
                        return await resp.json()
            finally:
                await svc.stop()

        state = run(go())
        assert state["enabled"] is False

    def test_detokenize_attribution(self, monkeypatch, model_dir, run):
        _arm(monkeypatch)
        from dynamo_tpu.llm.model_card import ModelDeploymentCard
        from dynamo_tpu.llm.preprocessor import DetokenizeOperator
        from dynamo_tpu.llm.protocols.common import (
            PreprocessedRequest,
            StopConditions,
        )
        from dynamo_tpu.runtime import Annotated, Context, Pipeline, collect
        from dynamo_tpu.runtime.engine import AsyncEngine

        card = ModelDeploymentCard.from_local_path(model_dir)
        detok = DetokenizeOperator(card)
        assert detok._fcpu is not None
        tok = detok.tokenizer
        ids = tok.encode("hello world")

        class FixedEngine(AsyncEngine):
            async def generate(self, request):
                for tid in ids:
                    yield Annotated.from_data({"token_ids": [tid]})
                yield Annotated.from_data(
                    {"token_ids": [], "finish_reason": "length"}
                )

        engine = Pipeline().link(detok).link_engine(FixedEngine())
        req = PreprocessedRequest(
            token_ids=tok.encode("x"),
            stop_conditions=StopConditions(max_tokens=100),
        )
        run(collect(engine.generate(Context(req))))
        per = profiling.frontend_cpu().per_token()
        assert per["tokens"]["detokenize"] >= len(ids)
        from dynamo_tpu.runtime import tracing

        assert "detokenize" in tracing.phase_summary()


# -- gauges through the metrics planes -----------------------------------------


class TestGauges:
    def _metrics(self, **kw):
        from dynamo_tpu.kv_router.protocols import ForwardPassMetrics

        return ForwardPassMetrics(**kw)

    def test_worker_aggregator_exposition(self):
        from .promtext import parse_prometheus_text

        from dynamo_tpu.components.metrics import MetricsAggregator

        agg = MetricsAggregator("ns")
        agg.update("w0", self._metrics(
            dispatch_device_us_p95=850.5,
            dispatch_host_overhead_us_p95=120.0,
            device_idle_frac=0.42,
        ))
        text = agg.render()
        fams = parse_prometheus_text(text)
        assert "dynamo_worker_dispatch_device_us_p95" in fams
        assert "dynamo_worker_device_idle_frac" in fams
        sample = [
            s for s in fams["dynamo_worker_dispatch_device_us_p95"]["samples"]
            if s[1].get("worker") == "w0"
        ]
        assert sample and sample[0][2] == 850.5

    def test_cluster_rollup_max_and_sum(self):
        from .promtext import parse_prometheus_text

        from dynamo_tpu.components.telemetry_aggregator import (
            ClusterTelemetry,
        )

        ct = ClusterTelemetry("ns")
        ct.ingest("w0", self._metrics(
            model="m", dispatch_device_us_p95=500.0,
            dispatch_host_overhead_us_p95=100.0, device_idle_frac=0.2,
            jit_recompiles=6,
        ))
        ct.ingest("w1", self._metrics(
            model="m", dispatch_device_us_p95=900.0,
            dispatch_host_overhead_us_p95=50.0, device_idle_frac=0.6,
            jit_recompiles=8,
        ))
        entry = ct.rollup()["models"]["m"]
        # p95s/idle: fleet WORST, never a sum; recompiles: fleet sum
        assert entry["dispatch_device_us_p95"] == 900.0
        assert entry["dispatch_host_overhead_us_p95"] == 100.0
        assert entry["device_idle_frac"] == 0.6
        assert entry["jit_recompiles_total"] == 14
        fams = parse_prometheus_text(ct.render_prometheus())
        assert "dynamo_cluster_dispatch_device_us_p95" in fams
        assert "dynamo_cluster_jit_recompiles_total" in fams
        assert "dynamo_cluster_device_idle_frac" in fams

    def test_mock_worker_drill_flags(self):
        from dynamo_tpu.components.mock_worker import MockWorkerStats

        stats = MockWorkerStats(
            dispatch_device_us=777.0, jit_recompiles=42,
            device_idle_frac=0.33,
        )
        m = stats.metrics("m")
        assert m.dispatch_device_us_p95 == 777.0
        assert m.dispatch_host_overhead_us_p95 == pytest.approx(116.6, 0.1)
        assert m.device_idle_frac == 0.33
        assert m.jit_recompiles == 42
        # wire round trip keeps the fields
        from dynamo_tpu.kv_router.protocols import ForwardPassMetrics

        back = ForwardPassMetrics.from_dict(m.to_dict())
        assert back.device_idle_frac == 0.33

    def test_attach_kv_publishing_stamps_gauges(self, monkeypatch):
        """The lazy sys.modules stamping path: a snapshot from an engine
        that doesn't carry the gauges gets the process-global ones."""
        _arm(monkeypatch)
        profiling.timeline().note_dispatch(
            "decode", step=1, device_us=640.0, host_us=50.0
        )
        # the same constructor-free read attach_kv_publishing uses
        import sys as _sys

        prof = _sys.modules.get("dynamo_tpu.runtime.profiling")
        assert prof is not None
        snap = {}
        for k, v in prof.gauges().items():
            snap.setdefault(k, v)
        assert snap["dispatch_device_us_p95"] == 640.0


# -- RPC + llmctl profile capture ---------------------------------------------


class TestProfileCapture:
    def test_llmctl_capture_json_and_trace(
        self, run, monkeypatch, capsys, tmp_path
    ):
        """``llmctl profile capture`` over a real statestore + RPC plane:
        --json prints per-worker summaries, --trace writes a
        Perfetto-loadable Chrome-trace file whose slices carry the PR5
        ids."""
        from .token_engine import TokenEngine

        from dynamo_tpu.cli import llmctl
        from dynamo_tpu.runtime.distributed import DistributedRuntime
        from dynamo_tpu.runtime.statestore import StateStoreServer

        _arm(monkeypatch)
        tl = profiling.timeline()

        async def go():
            ss = StateStoreServer(port=0)
            await ss.start()
            rt = await DistributedRuntime.create(ss.url, NO_BUS)
            ep = rt.namespace("p").component("w").endpoint("gen")
            await ep.serve(TokenEngine("w0", delay=0.0))
            # seed live-looking records (same process answers the
            # profile_dump verb — the CLI reads them over the real wire)
            for i in range(12):
                tl.note_dispatch(
                    "decode", step=i, batch=2, tokens=2,
                    host_us=80.0, device_us=500.0, post_us=20.0,
                    reqs=[f"req-{i}"], traces=[f"tr-{i}"],
                )
            tl.note_event("jit_compile", "decode lp=False [S=4,k=1]")
            capsys.readouterr()
            rc = await llmctl.amain([
                "--statestore", ss.url, "profile", "capture",
                "dyn://p.w.gen", "--seconds", "0.2", "--json",
            ])
            out_json = capsys.readouterr().out
            assert rc == 0, out_json
            payload = json.loads(out_json)
            assert rt.worker_id in payload
            entry = payload[rt.worker_id]
            assert entry["enabled"] is True
            assert entry["summary"]["phases"]["decode"]["count"] == 12

            trace_path = tmp_path / "capture.json"
            rc = await llmctl.amain([
                "--statestore", ss.url, "profile", "capture",
                "dyn://p.w.gen", "--seconds", "0.1",
                "--trace", str(trace_path),
            ])
            out = capsys.readouterr().out
            assert rc == 0, out
            assert "perfetto" in out.lower()
            trace = json.loads(trace_path.read_text())
            _assert_valid_chrome_trace(trace)
            assert any(
                "req-3" in (e.get("args", {}).get("reqs") or [])
                for e in trace["traceEvents"] if e.get("ph") == "X"
            )
            await rt.shutdown()
            await ss.stop()

        run(go())

    def test_capture_reports_disarmed_worker(self, run, monkeypatch, capsys):
        from .token_engine import TokenEngine

        from dynamo_tpu.cli import llmctl
        from dynamo_tpu.runtime.distributed import DistributedRuntime
        from dynamo_tpu.runtime.statestore import StateStoreServer

        monkeypatch.delenv("DYN_TPU_PROFILE", raising=False)
        profiling.reset_for_tests()

        async def go():
            ss = StateStoreServer(port=0)
            await ss.start()
            rt = await DistributedRuntime.create(ss.url, NO_BUS)
            ep = rt.namespace("p2").component("w").endpoint("gen")
            await ep.serve(TokenEngine("w0", delay=0.0))
            capsys.readouterr()
            rc = await llmctl.amain([
                "--statestore", ss.url, "profile", "capture",
                "dyn://p2.w.gen", "--seconds", "0",
            ])
            captured = capsys.readouterr()
            assert rc == 0
            assert "profiling OFF" in captured.out
            assert "DYN_TPU_PROFILE" in captured.out
            await rt.shutdown()
            await ss.stop()

        run(go())

    def test_rpc_profile_dump_verb(self, run, monkeypatch):
        """The raw RPC verb: profile_dump answers local profiling state
        (safe while the engine is wedged — pure memory read)."""
        from .token_engine import TokenEngine

        from dynamo_tpu.runtime.distributed import DistributedRuntime
        from dynamo_tpu.runtime.rpc import RpcClient
        from dynamo_tpu.runtime.statestore import StateStoreServer

        _arm(monkeypatch)
        profiling.timeline().note_dispatch("chunk", step=1, device_us=9.0)

        async def go():
            ss = StateStoreServer(port=0)
            await ss.start()
            rt = await DistributedRuntime.create(ss.url, NO_BUS)
            ep = rt.namespace("p3").component("w").endpoint("gen")
            await ep.serve(TokenEngine("w0"))
            entries = await rt.store.get_prefix(
                "p3/components/w/endpoints/gen/instances/"
            )
            from dynamo_tpu.runtime.distributed import InstanceInfo

            info = InstanceInfo.from_json(next(iter(entries.values())))
            client = await RpcClient.connect(info.address, timeout=5.0)
            state = await client.profile_dump()
            await client.close()
            await rt.shutdown()
            await ss.stop()
            return state

        state = run(go())
        assert state["enabled"] is True
        assert state["records"][0]["phase"] == "chunk"
