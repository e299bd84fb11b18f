"""Mid-stream request recovery (ISSUE 11): a worker dying mid-decode is
invisible to the caller.

Covers the resume journal (unit), the DYN_TPU_RESUME_* knob clamping, the
EndpointClient resume dispatch over a real mock cluster (deterministic
token engines so byte-equality is provable), the engine-side sampling-state
reconstruction on a real tiny JAX engine (greedy + penalties bitwise equal
to an undisturbed control), the deterministic `cut` fault action, the
TTFT-vs-ITL attribution at the edge, the resume gauges through the worker
and cluster metrics planes, and the chaos acceptance gate: 1-of-3 workers
killed mid-decode under 2x load → zero client-visible failures, every
resumed greedy stream bitwise identical to its control, breaker ejects the
dead worker — while DYN_TPU_RESUME=0 restores exact PR2 pinned behavior
with zero journal overhead.
"""

import asyncio
import time

import pytest

from dynamo_tpu.runtime import faults, resilience
from dynamo_tpu.runtime import distributed as distributed_mod
from dynamo_tpu.runtime.annotated import Annotated
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.engine import AsyncEngine, Context, EngineContext
from dynamo_tpu.runtime.faults import FaultInjector, FaultRule, StreamCut
from dynamo_tpu.runtime.resilience import (
    OPEN,
    ResiliencePolicy,
    StreamJournal,
)
from dynamo_tpu.runtime.rpc import RpcServer
from dynamo_tpu.runtime.statestore import StateStoreServer

from .token_engine import TokenEngine, expected_stream
from .token_engine import payload as _payload

NO_BUS = "127.0.0.1:1"


# -- knob clamping -------------------------------------------------------------


class TestResumeKnobs:
    def test_from_env_table(self, monkeypatch):
        cases = [
            # (DYN_TPU_RESUME, DYN_TPU_RESUME_BUDGET, attempts, budget)
            (None, None, 1, 30.0),          # defaults: resume ON, one recovery
            ("0", None, 0, 30.0),           # 0 is a POLICY: exact old behavior
            ("3", "5", 3, 5.0),
            ("-2", "0", 1, 30.0),           # negative count → default
            ("junk", "junk", 1, 30.0),      # malformed → default
            ("2", "-1", 2, 30.0),           # budget must stay positive
        ]
        for raw_r, raw_b, want_r, want_b in cases:
            if raw_r is None:
                monkeypatch.delenv("DYN_TPU_RESUME", raising=False)
            else:
                monkeypatch.setenv("DYN_TPU_RESUME", raw_r)
            if raw_b is None:
                monkeypatch.delenv("DYN_TPU_RESUME_BUDGET", raising=False)
            else:
                monkeypatch.setenv("DYN_TPU_RESUME_BUDGET", raw_b)
            p = ResiliencePolicy.from_env()
            assert p.resume_attempts == want_r, (raw_r, raw_b)
            assert p.resume_budget_s == pytest.approx(want_b), (raw_r, raw_b)


# -- the journal ---------------------------------------------------------------


class TestStreamJournal:
    def test_viability(self):
        assert StreamJournal(_payload([1, 2, 3])).viable
        assert not StreamJournal({}).viable
        assert not StreamJournal({"token_ids": "abc"}).viable
        assert not StreamJournal({"token_ids": [1, "x"]}).viable

    def test_note_and_resume_request_math(self):
        j = StreamJournal(_payload([1, 2, 3], max_tokens=10, min_tokens=6))
        j.note({"token_ids": [7]})
        j.note({"token_ids": [8, 9]})
        j.note(None)  # annotation payloads are ignored
        r = j.resume_request()
        assert r["token_ids"] == [1, 2, 3, 7, 8, 9]
        assert r["stop_conditions"]["max_tokens"] == 7
        assert r["stop_conditions"]["min_tokens"] == 3
        assert r["resume"] == {"prompt_len": 3, "rng_offset": 3}
        # the original payload is never mutated
        assert j._payload["token_ids"] == [1, 2, 3]
        assert j._payload["stop_conditions"]["max_tokens"] == 10

    def test_min_tokens_floors_at_zero(self):
        j = StreamJournal(_payload([1], max_tokens=10, min_tokens=2))
        j.note({"token_ids": [5, 6, 7]})
        assert j.resume_request()["stop_conditions"]["min_tokens"] == 0

    def test_finish_and_spent_budget_refuse_resume(self):
        j = StreamJournal(_payload([1], max_tokens=2))
        j.note({"token_ids": [5]})
        j.note({"token_ids": [], "finish_reason": "length"})
        assert j.finished and j.resume_request() is None
        j2 = StreamJournal(_payload([1], max_tokens=2))
        j2.note({"token_ids": [5, 6]})  # budget fully spent, finish frame lost
        assert j2.resume_request() is None

    def test_non_token_item_marks_unviable(self):
        j = StreamJournal(_payload([1]))
        j.note({"text": "raw content, no ids"})
        assert not j.viable
        assert j.resume_request() is None


# -- the deterministic `cut` fault ---------------------------------------------


class TestStreamCutFault:
    def test_cut_fires_at_item_index(self, run):
        async def go():
            inj = FaultInjector([FaultRule(
                plane="rpc", point="item", action="cut", after_ops=2,
                max_fires=1,
            )])
            with faults.active(inj):
                await faults.item_gate("rpc", "x:1", 0)
                await faults.item_gate("rpc", "x:1", 1)
                with pytest.raises(StreamCut):
                    await faults.item_gate("rpc", "x:1", 2)
                # max_fires=1: later streams run clean
                await faults.item_gate("rpc", "x:1", 2)
            assert [d.action for d in inj.log] == ["cut"]

        run(go())


# -- mock cluster with deterministic token engines -----------------------------


def _policy(**kw) -> ResiliencePolicy:
    base = dict(
        request_timeout=20.0,
        connect_timeout=1.0,
        max_attempts=4,
        backoff_base=0.01,
        backoff_max=0.05,
        breaker_threshold=2,
        breaker_cooldown=30.0,
        seed=11,
    )
    base.update(kw)
    return ResiliencePolicy(**base)


async def _cluster(n, policy, delay=0.0):
    ss = StateStoreServer(port=0)
    await ss.start()
    rts, infos = [], []
    for i in range(n):
        rt = await DistributedRuntime.create(ss.url, NO_BUS)
        ep = rt.namespace("res").component("w").endpoint("gen")
        infos.append(await ep.serve(TokenEngine(f"w{i}", delay=delay)))
        rts.append(rt)
    fe = await DistributedRuntime.create(ss.url, NO_BUS)
    client = await fe.namespace("res").component("w").endpoint("gen").client(
        "round_robin", policy=policy
    )
    await client.wait_for_instances(n, timeout=10)
    return ss, rts, infos, fe, client


async def _teardown(ss, rts, fe, client):
    await client.close()
    for rt in rts + [fe]:
        await rt.shutdown()
    await ss.stop()


async def _stream(client, prompt, max_tokens):
    """Drive one request; returns (tokens, errors, ctx)."""
    ctx = Context(_payload(prompt, max_tokens=max_tokens))
    toks, errs = [], []
    async for item in client.generate(ctx):
        if item.is_error:
            errs.append(item.error_message())
        elif isinstance(item.data, dict):
            toks.extend(item.data.get("token_ids", []))
    return toks, errs, ctx


def _serve_addr(rt) -> str:
    return f"{rt._rpc_server.host}:{rt._rpc_server.port}"


class TestClientResume:
    def test_mid_stream_cut_resumes_byte_equal(self, run):
        """The tentpole in one scenario: a live stream is cut after 3 items
        (deterministic mid-decode kill), the client re-admits it on a
        sibling as prompt+generated, and the caller sees the full,
        byte-identical token stream with zero error items."""

        async def go():
            resilience.reset_resume_counters()
            ss, rts, infos, fe, client = await _cluster(2, _policy())
            prompt = [3, 5, 7]
            want = expected_stream(prompt, 12)
            inj = FaultInjector([FaultRule(
                plane="rpc", point="item", action="cut", after_ops=3,
                max_fires=1,
            )])
            with faults.active(inj):
                toks, errs, ctx = await _stream(client, prompt, 12)
            assert errs == []
            assert toks == want, "resumed stream must be bitwise identical"
            assert client.stats["resumes"] == 1
            assert client.stats["resume_failures"] == 0
            j = ctx.context.journal
            assert j is not None and j.resumes == 1
            assert j.emitted == want
            assert resilience.resume_counters()[0] >= 1
            await _teardown(ss, rts, fe, client)

        run(go())

    def test_real_worker_death_mid_decode_resumes(self, run):
        """No harness: actually stop the serving worker's RPC server while
        its stream is live — the surviving worker finishes it."""

        async def go():
            ss, rts, infos, fe, client = await _cluster(
                2, _policy(), delay=0.02
            )
            prompt = [11, 13]
            want = expected_stream(prompt, 30)

            async def one():
                return await _stream(client, prompt, 30)

            task = asyncio.create_task(one())
            await asyncio.sleep(0.15)  # a few tokens in
            # the round-robin pick is deterministic only in aggregate; find
            # the worker actually holding the stream via its inflight set
            victim = next(
                (i for i, rt in enumerate(rts)
                 if rt._rpc_server.inflight_count), 0,
            )
            await rts[victim]._rpc_server.stop(drain_timeout=0.01)
            toks, errs, _ = await asyncio.wait_for(task, 20)
            assert errs == []
            assert toks == want
            assert client.stats["resumes"] >= 1
            await _teardown(ss, rts, fe, client)

        run(go())

    def test_resume_off_restores_pinned_in_band_error(self, run, monkeypatch):
        """DYN_TPU_RESUME=0 acceptance: the zero-overhead guard (no
        StreamJournal is ever constructed) AND the exact PR2 behavior (the
        mid-stream failure surfaces in-band as an error envelope)."""

        async def go():
            def _boom(*a, **kw):
                raise AssertionError("StreamJournal constructed with resume off")

            monkeypatch.setattr(distributed_mod, "StreamJournal", _boom)
            ss, rts, infos, fe, client = await _cluster(
                2, _policy(resume_attempts=0)
            )
            inj = FaultInjector([FaultRule(
                plane="rpc", point="item", action="cut", after_ops=2,
                max_fires=1,
            )])
            with faults.active(inj):
                toks, errs, ctx = await _stream(client, [1, 2], 10)
            assert len(errs) == 1 and "mid-stream" in errs[0]
            assert len(toks) == 2  # the delivered prefix, nothing duplicated
            assert ctx.context.journal is None
            assert client.stats["resumes"] == 0
            await _teardown(ss, rts, fe, client)

        run(go())

    def test_non_token_payload_keeps_pinned_behavior(self, run):
        """Requests without token_ids (raw dicts) are not journal-able: the
        mid-stream failure surfaces in-band exactly as before."""

        class RawEngine(AsyncEngine):
            async def generate(self, request: Context):
                for i in range(10):
                    yield Annotated.from_data({"i": i})
                    await asyncio.sleep(0)

        async def go():
            ss = StateStoreServer(port=0)
            await ss.start()
            rts = []
            for i in range(2):
                rt = await DistributedRuntime.create(ss.url, NO_BUS)
                await rt.namespace("res").component("w").endpoint("gen").serve(
                    RawEngine()
                )
                rts.append(rt)
            fe = await DistributedRuntime.create(ss.url, NO_BUS)
            client = await fe.namespace("res").component("w").endpoint(
                "gen"
            ).client("round_robin", policy=_policy())
            await client.wait_for_instances(2, timeout=10)
            inj = FaultInjector([FaultRule(
                plane="rpc", point="item", action="cut", after_ops=2,
                max_fires=1,
            )])
            with faults.active(inj):
                ctx = Context({"no": "tokens"})
                errs = []
                n = 0
                async for item in client.generate(ctx):
                    if item.is_error:
                        errs.append(item.error_message())
                    else:
                        n += 1
            assert len(errs) == 1 and "mid-stream" in errs[0]
            assert ctx.context.journal is None
            assert client.stats["resumes"] == 0
            await _teardown(ss, rts, fe, client)

        run(go())

    def test_resume_attempts_exhausted_surfaces_in_band(self, run):
        """One recovery allowed, two kills delivered: the second cut must
        surface in-band and count a failed resume."""

        async def go():
            resilience.reset_resume_counters()
            ss, rts, infos, fe, client = await _cluster(
                2, _policy(resume_attempts=1)
            )
            inj = FaultInjector([FaultRule(
                plane="rpc", point="item", action="cut", after_ops=2,
                max_fires=2,
            )])
            with faults.active(inj):
                toks, errs, ctx = await _stream(client, [2, 4], 20)
            assert len(errs) == 1 and "mid-stream" in errs[0]
            # first leg delivered 2, resumed leg delivered 2 more before its
            # own cut — and the 4 delivered tokens are the true prefix
            assert toks == expected_stream([2, 4], 20)[: len(toks)]
            assert len(toks) == 4
            assert client.stats["resumes"] == 1
            assert client.stats["resume_failures"] == 1
            ok, bad = resilience.resume_counters()
            assert ok >= 1 and bad >= 1
            await _teardown(ss, rts, fe, client)

        run(go())

    def test_resume_budget_bounds_churn(self, run):
        """A microscopic resume budget admits the first recovery (the
        budget clock starts there) but refuses the second even though the
        attempt knob would allow it."""

        async def go():
            ss, rts, infos, fe, client = await _cluster(
                2, _policy(resume_attempts=5, resume_budget_s=1e-4)
            )
            inj = FaultInjector([FaultRule(
                plane="rpc", point="item", action="cut", after_ops=2,
                max_fires=2,
            )])
            with faults.active(inj):
                toks, errs, ctx = await _stream(client, [6, 9], 20)
            assert len(errs) == 1
            assert client.stats["resumes"] == 1
            await _teardown(ss, rts, fe, client)

        run(go())


# -- chaos acceptance gate -----------------------------------------------------


class TestChaosGate:
    def test_kill_one_of_three_mid_decode_under_load(self, run):
        """ISSUE 11 acceptance: 3 workers, 2x-capacity concurrent streaming
        load, one worker killed for real mid-decode. Zero client-visible
        failures, every stream (resumed or not) bitwise identical to its
        undisturbed control, and the breaker/health plane still ejects the
        dead worker."""

        async def go():
            resilience.reset_resume_counters()
            ss, rts, infos, fe, client = await _cluster(
                3, _policy(resume_attempts=2), delay=0.02
            )
            n_requests = 12  # 2x the worker count in concurrent streams
            max_t = 25
            prompts = [[17 + i, 23 + 2 * i] for i in range(n_requests)]
            controls = [expected_stream(p, max_t) for p in prompts]

            results = [None] * n_requests

            async def one(i):
                results[i] = await _stream(client, prompts[i], max_t)

            tasks = [asyncio.create_task(one(i)) for i in range(n_requests)]
            await asyncio.sleep(0.2)  # everyone is mid-decode
            victim = infos[1]
            victim_inflight = rts[1]._rpc_server.inflight_count
            assert victim_inflight > 0, "load did not reach the victim"
            await rts[1]._rpc_server.stop(drain_timeout=0.01)
            await asyncio.wait_for(asyncio.gather(*tasks), 40)

            failures = [
                (i, errs) for i, (toks, errs, _) in enumerate(results) if errs
            ]
            assert failures == [], f"client-visible failures: {failures}"
            for i, (toks, errs, _) in enumerate(results):
                assert toks == controls[i], (
                    f"stream {i} diverged after resume "
                    f"(got {len(toks)} tokens)"
                )
            # every stream the victim held was resumed (not silently lost)
            assert client.stats["resumes"] >= victim_inflight
            assert client.stats["resume_failures"] == 0
            # the breaker still ejects the dead worker: its streams each
            # recorded a failure, and new dials are refused
            assert client._breaker.state(victim.instance_id) == OPEN
            await _teardown(ss, rts, fe, client)

        run(go())


# -- edge attribution (TTFT vs ITL) -------------------------------------------


class TestEdgeAttribution:
    def test_resumed_first_chunk_feeds_itl_not_ttft(self, monkeypatch):
        from dynamo_tpu.llm.http.metrics import ServiceMetrics
        from dynamo_tpu.runtime import telemetry

        monkeypatch.delenv("DYN_TPU_SLO", raising=False)
        telemetry.configure()
        try:
            m = ServiceMetrics("t_res")
            with m.inflight_guard("m1", "completions", "stream") as g:
                g.mark_resume()
                g.mark_chunk()  # first content chunk arrives AFTER a resume
                g.mark_ok()
            store = telemetry.store()
            assert store.series("ttft_ms", model="m1").window_count(60.0) == 0
            assert store.series("itl_ms", model="m1").window_count(60.0) == 1
            # the frontend resume counter renders
            text = m.render()
            assert 't_res_resume_total{model="m1"} 1' in text
            # and the frontend TTFT histogram saw nothing for this request
            assert not m.ttft.snapshot()
        finally:
            telemetry.configure()

    def test_unresumed_request_feeds_ttft(self, monkeypatch):
        from dynamo_tpu.llm.http.metrics import ServiceMetrics
        from dynamo_tpu.runtime import telemetry

        monkeypatch.delenv("DYN_TPU_SLO", raising=False)
        telemetry.configure()
        try:
            m = ServiceMetrics("t_res2")
            with m.inflight_guard("m1", "completions", "stream") as g:
                g.mark_chunk()
                g.mark_ok()
            store = telemetry.store()
            assert store.series("ttft_ms", model="m1").window_count(60.0) == 1
            assert store.series("itl_ms", model="m1").window_count(60.0) == 0
        finally:
            telemetry.configure()

    def test_http_edge_counts_resume_from_journal(self, run):
        """The HTTP streaming loop reads EngineContext.journal: an engine
        whose journal grows its resume count mid-stream bumps the frontend
        resume counter and reclassifies the first chunk's latency."""
        from aiohttp import ClientSession

        from dynamo_tpu.llm.http.service import HttpService, ModelManager

        class ResumingEngine(AsyncEngine):
            async def generate(self, request: Context):
                j = StreamJournal(_payload([1, 2], max_tokens=4))
                request.context.journal = j
                j.resumes = 1  # "a recovery happened before first content"
                for i in range(3):
                    yield Annotated.from_data({
                        "id": "cmpl-x", "object": "text_completion",
                        "created": 1, "model": "m1",
                        "choices": [{"index": 0, "text": f"t{i}",
                                     "finish_reason": None}],
                    })

        async def go():
            mgr = ModelManager()
            mgr.add_completions_model("m1", ResumingEngine())
            svc = HttpService(mgr, host="127.0.0.1", port=0)
            port = await svc.start()
            try:
                async with ClientSession() as http:
                    resp = await http.post(
                        f"http://127.0.0.1:{port}/v1/completions",
                        json={"model": "m1", "prompt": "x", "stream": True},
                    )
                    body = await resp.text()
                    assert resp.status == 200
                    assert "t0" in body and "t2" in body
                assert svc.metrics.resumed.render()
                text = svc.metrics.render()
                assert 'dynamo_frontend_resume_total{model="m1"} 1' in text
            finally:
                await svc.stop()

        run(go())


# -- gauges through the metrics planes -----------------------------------------


class TestResumeGauges:
    def test_forward_pass_metrics_round_trip(self):
        from dynamo_tpu.kv_router.protocols import ForwardPassMetrics

        m = ForwardPassMetrics(resume_total=4, resume_failed_total=1)
        d = m.to_dict()
        assert d["resume_total"] == 4 and d["resume_failed_total"] == 1
        back = ForwardPassMetrics.from_dict(d)
        assert back.resume_total == 4 and back.resume_failed_total == 1
        # pre-resume wire dicts still parse (fields default 0)
        old = {k: v for k, v in d.items()
               if not k.startswith("resume_")}
        assert ForwardPassMetrics.from_dict(old).resume_total == 0

    def test_worker_and_cluster_gauges_render(self):
        from dynamo_tpu.components.metrics import MetricsAggregator
        from dynamo_tpu.components.mock_worker import MockWorkerStats
        from dynamo_tpu.components.telemetry_aggregator import ClusterTelemetry

        from .promtext import parse_prometheus_text

        stats = MockWorkerStats(seed=1, resume_total=7, resume_failed=2)
        stats.tick(requests=3)
        m = stats.metrics("m1")
        assert m.resume_total == 7 and m.resume_failed_total == 2

        agg = MetricsAggregator("ns1")
        agg.update("w0", m)
        text = agg.render()
        parsed = parse_prometheus_text(text)
        assert "dynamo_worker_resume_total" in parsed
        assert "dynamo_worker_resume_failed_total" in parsed

        ct = ClusterTelemetry("ns1", clock=lambda: 100.0)
        ct.ingest("w0", m)
        ct.ingest("w1", MockWorkerStats(
            seed=2, resume_total=3, resume_failed=0
        ).metrics("m1"))
        roll = ct.rollup()
        assert roll["models"]["m1"]["resume_total"] == 10
        assert roll["models"]["m1"]["resume_failed_total"] == 2
        ctext = ct.render_prometheus()
        cparsed = parse_prometheus_text(ctext)
        assert "dynamo_cluster_resume_total" in cparsed
        assert "dynamo_cluster_resume_failed_total" in cparsed

    def test_publish_loop_carries_process_counters(self, run):
        """attach_kv_publishing stamps the process-global resume counters
        onto every snapshot it publishes."""
        from dynamo_tpu.runtime.bus import MessageBusServer

        class SnapEngine:
            def metrics_snapshot(self):
                return {"request_active_slots": 0, "request_total_slots": 1}

        async def go():
            resilience.reset_resume_counters()
            resilience.note_resume()
            resilience.note_resume()
            resilience.note_resume(failed=True)
            ss = StateStoreServer(port=0)
            await ss.start()
            bus = MessageBusServer(port=0)
            await bus.start()
            rt = await DistributedRuntime.create(ss.url, bus.url)
            ns = rt.namespace("resg")
            got = asyncio.Event()
            seen = {}

            async def consume():
                sub = await ns.subscribe("kv_metrics")
                async for raw in sub:
                    import json as _json

                    seen.update(_json.loads(raw))
                    got.set()
                    return

            task = asyncio.create_task(consume())
            await asyncio.sleep(0.1)
            ep = rt.namespace("resg").component("w").endpoint("gen")
            await ep.serve(TokenEngine("w"))
            from dynamo_tpu.runtime.distributed import attach_kv_publishing

            await attach_kv_publishing(ep, SnapEngine(), interval=0.05)
            await asyncio.wait_for(got.wait(), 5)
            task.cancel()
            m = seen["metrics"]
            assert m["resume_total"] == 2
            assert m["resume_failed_total"] == 1
            await rt.shutdown()
            await bus.stop()
            await ss.stop()
            resilience.reset_resume_counters()

        run(go())


# -- engine-side sampling-state reconstruction ---------------------------------


@pytest.fixture(scope="module")
def tiny():
    import dataclasses

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models.llama import LLAMA_PRESETS, init_params

    cfg = dataclasses.replace(LLAMA_PRESETS["tiny"], dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(tiny, **kw):
    from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine

    cfg, params = tiny
    base = dict(max_slots=2, kv_block_size=8, max_model_len=128)
    base.update(kw)
    return JaxServingEngine(cfg, params, EngineConfig(**base))


async def _engine_collect(engine, token_ids, max_tokens, resume=None,
                          freq_pen=0.0, pres_pen=0.0):
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    req = PreprocessedRequest(
        token_ids=list(token_ids),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(
            temperature=0.0, frequency_penalty=freq_pen,
            presence_penalty=pres_pen,
        ),
        resume=resume,
    )
    toks = []
    async for item in engine.generate(Context(req)):
        if item.is_error:
            raise AssertionError(item.error_message())
        toks.extend((item.data or {}).get("token_ids", []))
    return toks


class TestEngineResume:
    def test_seq_reconstruction_unit(self, tiny):
        from dynamo_tpu.engine_jax.engine import _Seq
        from dynamo_tpu.llm.protocols.common import PreprocessedRequest

        class _Loop:
            def is_closed(self):
                return False

        req = PreprocessedRequest(
            token_ids=[1, 2, 3, 9, 9], resume={"prompt_len": 3},
        )
        seq = _Seq(Context(req), req, _Loop())
        assert seq.resumed
        assert seq.out_tokens == [9, 9]  # emitted history → penalty rebuild
        assert seq.prompt == [1, 2, 3, 9, 9]  # full recompute as prompt
        # clamping: nonsense markers are ignored, exact old behavior
        for bad in ({"prompt_len": 0}, {"prompt_len": -4},
                    {"prompt_len": 99}, {"prompt_len": "x"}, "junk"):
            r = PreprocessedRequest(token_ids=[1, 2, 3], resume=bad
                                    if isinstance(bad, dict) else None)
            s = _Seq(Context(r), r, _Loop())
            assert not s.resumed and s.out_tokens == []

    def test_greedy_resume_bitwise_equal(self, tiny, run):
        async def go():
            control = _engine(tiny)
            prompt = list(range(3, 23))
            golden = await _engine_collect(control, prompt, 12)
            control.close()
            assert len(golden) == 12

            for k in (1, 5, 11):
                eng = _engine(tiny)
                got = await _engine_collect(
                    eng, prompt + golden[:k], 12 - k,
                    resume={"prompt_len": len(prompt), "rng_offset": k},
                )
                assert eng.resumed_requests == 1
                assert eng.metrics_snapshot()["resumed_requests"] == 1
                eng.close()
                assert got == golden[k:], f"diverged resuming at token {k}"

        run(go())

    def test_penalized_resume_rebuilds_counts_exactly(self, tiny, run):
        """Frequency/presence penalties depend on every emitted token; the
        resume marker seeds out_tokens with the emitted suffix so the
        device count rebuild continues the dead stream's exact penalty
        state."""

        async def go():
            control = _engine(tiny)
            prompt = list(range(5, 25))
            golden = await _engine_collect(
                control, prompt, 12, freq_pen=1.1, pres_pen=0.5
            )
            control.close()

            eng = _engine(tiny)
            k = 6
            got = await _engine_collect(
                eng, prompt + golden[:k], 12 - k,
                resume={"prompt_len": len(prompt), "rng_offset": k},
                freq_pen=1.1, pres_pen=0.5,
            )
            eng.close()
            assert got == golden[k:]

        run(go())

    def test_resume_reprefill_hits_prefix_cache(self, tiny, run):
        """The re-prefill is cheap where it matters: a worker that already
        cached the prompt serves the resumed re-admission from its prefix
        cache instead of recomputing the whole history."""

        async def go():
            eng = _engine(tiny)
            prompt = list(range(7, 47))  # 40 tokens = 5 full blocks
            golden = await _engine_collect(eng, prompt, 8)
            hit_before = eng.allocator.hit_tokens
            k = 4
            got = await _engine_collect(
                eng, prompt + golden[:k], 8 - k,
                resume={"prompt_len": len(prompt), "rng_offset": k},
            )
            assert got == golden[k:]
            assert eng.allocator.hit_tokens > hit_before, (
                "resumed re-prefill did not reuse the cached prefix"
            )
            eng.close()

        run(go())


# -- journal rides the EngineContext -------------------------------------------


class TestContextPlumbing:
    def test_enginecontext_journal_slot_defaults_none(self):
        ctx = EngineContext()
        assert ctx.journal is None
