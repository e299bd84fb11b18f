"""OpenAI-compatible HTTP frontend (aiohttp).

Routes: POST /v1/chat/completions, POST /v1/completions, GET /v1/models,
GET /metrics, GET /health, GET /live. The engine is always called streaming;
non-streaming requests fold the chunk stream through the aggregators. Client
disconnects kill the engine context.

Reference parity: HttpService/HttpServiceConfig (lib/llm/src/http/service/
service_v2.rs:24-130), handlers + monitor_for_disconnects (openai.rs:132-418).
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import logging
import time
from typing import Optional

from aiohttp import web

from ...runtime import profiling, tracing
from ...runtime.admission import OVERLOAD_ERROR, OverloadedError
from ...runtime.annotated import Annotated
from ...runtime.engine import AsyncEngine, Context
from ...runtime.resilience import (
    DEADLINE_ERROR,
    AllInstancesFailed,
    DeadlineExceeded,
    NoHealthyInstances,
)
from ..protocols.openai import (
    ChatCompletionRequest,
    CompletionRequest,
    ModelInfo,
    ModelList,
    aggregate_chat_chunks,
    aggregate_completion_chunks,
)
from ..protocols.sse import DONE_SENTINEL, SseMessage
from .metrics import ServiceMetrics

logger = logging.getLogger(__name__)


from ..protocols.common import HttpError  # noqa: E402  (canonical home; re-exported here)


class ModelManager:
    """Registry of model name → engine, per endpoint type.

    Engines registered here speak OpenAI request in, Annotated[chunk dict] out
    (i.e. a full preprocessor→backend→worker pipeline or an in-process engine).
    Reference: ModelManager in service_v2.rs.
    """

    def __init__(self) -> None:
        self._chat: dict[str, AsyncEngine] = {}
        self._completions: dict[str, AsyncEngine] = {}

    def add_chat_model(self, name: str, engine: AsyncEngine) -> None:
        self._chat[name] = engine

    def add_completions_model(self, name: str, engine: AsyncEngine) -> None:
        self._completions[name] = engine

    def remove_chat_model(self, name: str) -> None:
        self._chat.pop(name, None)

    def remove_completions_model(self, name: str) -> None:
        self._completions.pop(name, None)

    def chat_engine(self, name: str) -> AsyncEngine:
        try:
            return self._chat[name]
        except KeyError:
            raise HttpError(404, f"model {name!r} not found") from None

    def completions_engine(self, name: str) -> AsyncEngine:
        try:
            return self._completions[name]
        except KeyError:
            raise HttpError(404, f"model {name!r} not found") from None

    def model_names(self) -> list[str]:
        return sorted(set(self._chat) | set(self._completions))

    def engines_by_model(self) -> dict[str, list[AsyncEngine]]:
        """name → engines serving it across endpoint kinds (health rollup)."""
        out: dict[str, list[AsyncEngine]] = {}
        for table in (self._chat, self._completions):
            for name, engine in table.items():
                engines = out.setdefault(name, [])
                if engine not in engines:
                    engines.append(engine)
        return out


class HttpService:
    def __init__(
        self,
        manager: Optional[ModelManager] = None,
        host: str = "0.0.0.0",
        port: int = 8080,
        metrics_prefix: str = "dynamo_frontend",
        qos=None,
        engine=None,
    ):
        self.manager = manager or ModelManager()
        # in-process core engine (in=http out=jax): its metrics_snapshot()
        # is served at /debug/engine — the one-process counterpart of the
        # stats endpoint a distributed worker registers
        self._engine = engine
        self.host = host
        self.port = port
        self.metrics = ServiceMetrics(metrics_prefix)
        # multi-tenant QoS (runtime/qos.py): tenant identity is extracted
        # here (x-tenant-id header / API-key map) and rides the engine
        # context + RPC header. The edge enforces the same token-bucket
        # rate limits the worker admission gate does, so in-process
        # engines (no RPC hop) get tenant isolation too. No DYN_TPU_TENANT_*
        # knobs ⇒ both stay None and the handler pays one None-check.
        from ...runtime import qos as qos_mod

        self.qos = qos if qos is not None else qos_mod.maybe_from_env()
        self.tenant_limiter = (
            qos_mod.TenantRateLimiter(self.qos)
            if self.qos is not None and self.qos.rate_rps > 0
            else None
        )
        self._runner: Optional[web.AppRunner] = None
        # performance attribution plane (runtime/profiling.py): with
        # DYN_TPU_PROFILE armed, the stream loop attributes per-token CPU
        # to serialize/transport-write and an event-loop lag sampler runs
        # beside the server. None/off costs one None-check per chunk (the
        # zero-overhead guard in tests/test_profiling.py).
        self._fcpu = (
            profiling.frontend_cpu() if profiling.enabled() else None
        )
        self._lag_sampler = None
        self.app = web.Application()
        self.app.add_routes(
            [
                web.post("/v1/chat/completions", self._chat_completions),
                web.post("/v1/completions", self._completions),
                web.get("/v1/models", self._models),
                web.get("/metrics", self._metrics),
                web.get("/health", self._health),
                web.get("/live", self._live),
                web.get("/debug/traces", self._debug_traces),
                web.get("/debug/slo", self._debug_slo),
                web.get("/debug/profile", self._debug_profile),
                web.get("/debug/engine", self._debug_engine),
            ]
        )

    # -- lifecycle ---------------------------------------------------------

    async def start(self) -> int:
        """Start serving; returns the bound port."""
        self._runner = web.AppRunner(self.app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        # resolve ephemeral port
        for sock in site._server.sockets:  # type: ignore[union-attr]
            self.port = sock.getsockname()[1]
            break
        logger.info("HTTP service listening on %s:%d", self.host, self.port)
        profiling.setup_done()  # a start-up that was timed ends here
        if self._fcpu is not None and self._lag_sampler is None:
            # event-loop lag: the direct saturation signal of a frontend
            # process (docs/observability.md §Profiling); one sampler per
            # process, shared by co-hosted services on the same loop
            self._lag_sampler = profiling.lag_sampler()
            self._lag_sampler.start()
        return self.port

    async def stop(self) -> None:
        if self._lag_sampler is not None:
            self._lag_sampler.stop()
            self._lag_sampler = None
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None

    async def run(self, cancel_event: Optional[asyncio.Event] = None) -> None:
        await self.start()
        try:
            if cancel_event is None:
                while True:
                    await asyncio.sleep(3600)
            else:
                await cancel_event.wait()
        finally:
            await self.stop()

    # -- handlers ----------------------------------------------------------

    async def _health(self, _request: web.Request) -> web.Response:
        """Real readiness, not a hardcoded string: per-model status derived
        from discovery + instance health. A served model with ZERO
        non-draining healthy instances makes the whole edge ``unhealthy``
        (503) so load balancers stop sending it traffic; impaired-but-
        serving models report ``degraded``. In-process engines (no
        discovery) count as healthy — process liveness is ``GET /live``."""
        overall = "healthy"
        models: dict = {}
        for name, engines in self.manager.engines_by_model().items():
            entry: dict = {"status": "healthy"}
            for engine in engines:
                summary_fn = getattr(engine, "health_summary", None)
                if summary_fn is None:
                    continue  # in-process engine: no instance plane
                summary = summary_fn()
                # SUM across a model's engines (chat vs completions may be
                # distinct clients) — a later summary must not clobber the
                # counts that justified an earlier engine's verdict
                for k in ("instances", "serving", "draining", "unhealthy",
                          "stale"):
                    entry[k] = entry.get(k, 0) + int(summary.get(k, 0))
                if summary.get("serving", 0) == 0:
                    # ANY engine with zero serving instances means some
                    # endpoint kind of this model is dead
                    entry["status"] = "unhealthy"
                elif (
                    summary.get("unhealthy", 0) or summary.get("draining", 0)
                ) and entry["status"] == "healthy":
                    entry["status"] = "degraded"
            models[name] = entry
            if entry["status"] == "unhealthy":
                overall = "unhealthy"
            elif entry["status"] == "degraded" and overall == "healthy":
                overall = "degraded"
        # control-plane view (docs/resilience.md §Control-plane blackout):
        # surfaced but NEVER a readiness failure by itself — a frontend
        # serving from stale discovery is degraded observability, not a
        # dead data plane, and load balancers must keep sending traffic
        from dynamo_tpu.runtime import control_plane

        cp = control_plane.snapshot()
        if overall == "healthy" and cp["state"] != "connected":
            overall = "degraded"
        return web.json_response(
            {"status": overall, "models": models, "control_plane": cp},
            status=503 if overall == "unhealthy" else 200,
        )

    async def _live(self, _request: web.Request) -> web.Response:
        """Pure process liveness (the container restart signal) — never
        coupled to upstream health, or a dead worker fleet would make the
        orchestrator restart a perfectly good frontend."""
        return web.json_response({"live": True})

    async def _metrics(self, _request: web.Request) -> web.Response:
        return web.Response(text=self.metrics.render(), content_type="text/plain")

    async def _debug_traces(self, request: web.Request) -> web.Response:
        """Flight-recorder export: one JSON object per line per trace
        (``?limit=N`` keeps the newest N, ``?trace_id=...`` one trace,
        ``?errored=1`` only traces with a non-ok span).
        Frontend-local spans only — worker traces come via ``llmctl trace``
        against the worker's RPC port (docs/observability.md)."""
        try:
            limit = int(request.query.get("limit", "0"))
        except ValueError:
            limit = 0
        errored = request.query.get("errored", "") not in ("", "0", "false")
        body = tracing.recorder().dump_jsonl(
            limit=limit, trace_id=request.query.get("trace_id"),
            errored=errored,
        )
        return web.Response(text=body + ("\n" if body else ""),
                            content_type="application/jsonl")

    async def _debug_profile(self, request: web.Request) -> web.Response:
        """Performance-attribution export (docs/observability.md
        §Profiling): the process's dispatch timeline summary, frontend
        per-token CPU split, and event-loop lag gauges. ``?trace=1``
        returns the same window as a Perfetto-loadable Chrome-trace JSON
        (one track per engine phase, one for the event loop);
        ``?seconds=N`` restricts to the last N seconds. Works — with
        empty sections — even when ``DYN_TPU_PROFILE`` is off, so a
        dashboard probing the wrong process gets an explicit
        ``enabled: false`` instead of a 404."""
        try:
            since = float(request.query.get("seconds", "0")) or None
        except ValueError:
            since = None
        state = profiling.dump_state(since)
        if request.query.get("trace", "") not in ("", "0", "false"):
            trace = profiling.to_chrome_trace([(
                "frontend", state.get("records", []),
                state.get("events", []),
            )])
            return web.json_response(trace)
        state.pop("records", None)  # summary view: keep the payload small
        state.pop("events", None)
        return web.json_response(state)

    async def _debug_engine(self, _request: web.Request) -> web.Response:
        """The in-process engine's ``metrics_snapshot()`` (404 on a frontend
        whose engines are remote: ask the worker's stats endpoint)."""
        if self._engine is None:
            raise web.HTTPNotFound(text="no in-process engine")
        import jax  # the in-process engine has long since imported it

        snap = self._engine.metrics_snapshot()
        # per-device allocator counters (peak_bytes_in_use …), where the
        # backend reports them: only the process holding the chip can ask
        snap["device_memory"] = [
            dict(d.memory_stats() or {}, id=d.id) for d in jax.local_devices()
        ]
        return web.json_response(snap)

    async def _debug_slo(self, _request: web.Request) -> web.Response:
        """SLO / burn-rate report: the edge's own objectives (fed from the
        request metrics this process serves) plus — when a cluster
        telemetry aggregator is co-hosted — the cluster rollup and cluster
        SLOs (docs/observability.md §Cluster telemetry & SLOs)."""
        from ...runtime import telemetry

        return web.json_response(telemetry.dump_state())

    async def _models(self, _request: web.Request) -> web.Response:
        listing = ModelList(data=[ModelInfo(id=n) for n in self.manager.model_names()])
        return web.json_response(listing.model_dump())

    async def _chat_completions(self, request: web.Request) -> web.StreamResponse:
        return await self._handle_openai(request, chat=True)

    async def _completions(self, request: web.Request) -> web.StreamResponse:
        return await self._handle_openai(request, chat=False)

    async def _handle_openai(self, request: web.Request, chat: bool) -> web.StreamResponse:
        endpoint = "chat/completions" if chat else "completions"
        try:
            body = await request.json()
        except (json.JSONDecodeError, UnicodeDecodeError):
            return _error_response(400, "invalid JSON body")

        try:
            oai_req = (
                ChatCompletionRequest.model_validate(body)
                if chat
                else CompletionRequest.model_validate(body)
            )
        except Exception as e:  # pydantic.ValidationError
            return _error_response(400, f"invalid request: {e}")

        try:
            engine = (
                self.manager.chat_engine(oai_req.model)
                if chat
                else self.manager.completions_engine(oai_req.model)
            )
        except HttpError as e:
            return _error_response(e.status, e.message)

        streaming = bool(oai_req.stream)
        ctx = Context(oai_req)
        # tenant identity (docs/qos.md): the AUTHENTICATED API-key binding
        # wins over the client-supplied x-tenant-id header (a spoofed
        # header must not bill another tenant's quota), undeclared ids
        # optionally collapse into the default tenant
        # (DYN_TPU_TENANT_UNMAPPED=shared), and anonymous traffic becomes
        # the shared default tenant — it must not bypass the rate gates.
        # With QoS off, a bare header still rides the context for tracing.
        tenant = request.headers.get("x-tenant-id")
        tenant_class = None
        if self.qos is not None:
            tenant = self.qos.resolve_tenant(
                tenant, request.headers.get("authorization")
            )
            if tenant:
                # bounded-cardinality CLASS (never the raw id) labels the
                # per-tenant SLO rows on /debug/slo (docs/qos.md)
                tenant_class = self.qos.class_name_of(tenant)
        if tenant:
            ctx.context.tenant = tenant
        if self.tenant_limiter is not None:
            wait_s = self.tenant_limiter.take(tenant)
            if wait_s > 0:
                # per-tenant 429 before any engine work: the Retry-After
                # is THIS tenant's bucket refill, not a global hint
                with self.metrics.inflight_guard(
                    oai_req.model, endpoint,
                    "stream" if streaming else "unary",
                    tenant_class=tenant_class,
                ) as g:
                    g.mark_shed()
                    return _overloaded_response(
                        f"{OVERLOAD_ERROR}: tenant {tenant!r} over rate quota",
                        # same 60 s cap as the worker gate: one policy
                        # knob must yield one client backoff contract
                        # wherever the request is shed
                        retry_after_ms=min(int(wait_s * 1000) + 1, 60_000),
                    )
        # edge span: the trace's root for locally-originated requests, or a
        # child of the caller's context when an (optional) W3C traceparent
        # header arrives — malformed headers just start a fresh root. The
        # span rides ctx.context.trace into the engine/router layers; the
        # contextvars make every log line in this handler carry the ids.
        attrs = {"model": oai_req.model, "endpoint": endpoint,
                 "stream": streaming, "request_id": ctx.id}
        if tenant:
            attrs["tenant"] = tenant
        edge = tracing.start_span(
            "http.edge",
            parent=tracing.parse_traceparent(request.headers.get("traceparent")),
            attributes=attrs,
        )
        tokens = None
        if edge is not None:
            ctx.context.trace = edge
            tokens = (tracing.set_current(edge), tracing.set_request_id(ctx.id))
        guard = self.metrics.inflight_guard(
            oai_req.model, endpoint, "stream" if streaming else "unary",
            tenant_class=tenant_class,
        )
        try:
            with guard:
                if streaming:
                    return await self._stream_response(request, engine, ctx, guard, chat)
                return await self._unary_response(engine, ctx, guard, chat)
        finally:
            if edge is not None:
                edge.end(_EDGE_STATUS.get(guard.status, guard.status))
            if tokens is not None:
                tracing.reset_current(tokens[0])
                tracing.reset_request_id(tokens[1])

    async def _stream_response(
        self,
        request: web.Request,
        engine: AsyncEngine,
        ctx: Context,
        guard,
        chat: bool,
    ) -> web.StreamResponse:
        # pull the first item BEFORE sending headers, so validation errors
        # (e.g. over-length prompts) still surface as proper HTTP status codes
        stream = engine.generate(ctx)
        if hasattr(stream, "__await__"):
            stream = await stream
        it = stream.__aiter__()
        try:
            first_item = await it.__anext__()
        except StopAsyncIteration:
            first_item = None
        except HttpError as e:
            return _error_response(e.status, e.message)
        except DeadlineExceeded as e:
            return _error_response(504, str(e) or DEADLINE_ERROR)
        except OverloadedError as e:
            guard.mark_shed()
            return _overloaded_response(str(e), e.retry_after_ms)
        except (NoHealthyInstances, AllInstancesFailed, ConnectionError, OSError) as e:
            return _error_response(502, f"upstream failure: {e}")

        # an upstream that failed before producing anything is an HTTP error,
        # not a 200 stream carrying an error payload
        if (
            isinstance(first_item, Annotated)
            and first_item.is_error
        ):
            msg = first_item.error_message() or "upstream failure"
            status = _upstream_status(msg)
            if status == 429:
                guard.mark_shed()
                return _overloaded_response(msg)
            return _error_response(status, msg)

        resp = web.StreamResponse(
            status=200,
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "Connection": "keep-alive",
            },
        )
        await resp.prepare(request)

        async def _rest():
            if first_item is not None:
                yield first_item
            async for i in it:
                yield i

        tmpl = _SseTemplate()
        envelope: Optional[dict] = None  # id/object/created/model of the stream
        # mid-stream resume visibility (docs/resilience.md): the routing
        # client's journal rides the SAME EngineContext; when its resume
        # count grows, attribute the next first-chunk wait to inter_token
        # instead of TTFT. None on non-resumable paths = one check total.
        journal = getattr(ctx.context, "journal", None)
        seen_resumes = 0
        try:
            async for item in _rest():
                seen_resumes = guard.sync_resumes(journal, seen_resumes)
                if isinstance(item, Annotated):
                    if item.is_error:
                        # headers already sent: error goes in-band, followed
                        # by a WELL-FORMED final chunk (finish_reason
                        # "error") + [DONE] so clients aren't left dangling
                        msg = SseMessage(event="error", data=json.dumps({"message": item.error_message()}))
                        await resp.write((msg.encode() + "\n\n").encode())
                        await _write_error_finish(resp, envelope, chat)
                        break
                    if item.data is None:
                        # annotation/comment event
                        await resp.write((SseMessage.from_annotated(item).encode() + "\n\n").encode())
                        continue
                    payload = item.data
                else:
                    payload = item
                if isinstance(payload, dict) and envelope is None:
                    envelope = {
                        k: payload[k]
                        for k in ("id", "object", "created", "model")
                        if k in payload
                    }
                has_content = _chunk_has_content(payload)
                if has_content:
                    guard.mark_chunk()  # TTFT on first, inter-token gap after
                    guard.count_tokens()
                if self._fcpu is None:
                    fast = tmpl.encode(payload)
                    if fast is not None:
                        await resp.write(fast)
                    else:
                        await resp.write((f"data: {json.dumps(payload)}\n\n").encode())
                else:
                    # per-token CPU attribution (profiling plane): split
                    # the SSE hot path into serialize vs transport-write
                    # so the µs/token residue decomposes
                    t0 = time.perf_counter()
                    data = tmpl.encode(payload)
                    if data is None:
                        data = (f"data: {json.dumps(payload)}\n\n").encode()
                    t1 = time.perf_counter()
                    await resp.write(data)
                    t2 = time.perf_counter()
                    self._fcpu.note(
                        "serialize", (t1 - t0) * 1e6,
                        tokens=1 if has_content else 0,
                    )
                    self._fcpu.note(
                        "transport_write", (t2 - t1) * 1e6,
                        tokens=1 if has_content else 0,
                    )
                    if tracing.enabled():
                        tracing.observe_phase("serialize", t1 - t0)
            else:
                guard.mark_ok()
            await resp.write(f"data: {DONE_SENTINEL}\n\n".encode())
        except (ConnectionResetError, asyncio.CancelledError):
            # client went away: kill the engine context so the worker stops
            ctx.context.kill()
            logger.info("client disconnected, killed request %s", ctx.id)
            raise
        except Exception as e:  # headers already sent: error must go in-band
            logger.exception("engine error mid-stream for request %s", ctx.id)
            ctx.context.kill()
            msg = SseMessage(event="error", data=json.dumps({"message": str(e)}))
            with contextlib.suppress(ConnectionError):
                await resp.write((msg.encode() + "\n\n").encode())
                await _write_error_finish(resp, envelope, chat)
                await resp.write(f"data: {DONE_SENTINEL}\n\n".encode())
        finally:
            with contextlib.suppress(ConnectionError):
                await resp.write_eof()
        return resp

    async def _unary_response(
        self, engine: AsyncEngine, ctx: Context, guard, chat: bool
    ) -> web.Response:
        chunks: list[dict] = []
        n_tokens = 0
        seen_resumes = 0
        try:
            async for item in engine.generate(ctx):
                seen_resumes = guard.sync_resumes(
                    getattr(ctx.context, "journal", None), seen_resumes
                )
                if isinstance(item, Annotated):
                    if item.is_error:
                        msg = item.error_message() or "engine error"
                        if not chunks:
                            # upstream failed before producing anything:
                            # 429/502/504, not a generic server error
                            status = _upstream_status(msg)
                            if status == 429:
                                guard.mark_shed()
                                return _overloaded_response(msg)
                            return _error_response(status, msg)
                        return _error_response(500, msg)
                    if item.data is None:
                        continue
                    chunks.append(item.data)
                else:
                    chunks.append(item)
                if _chunk_has_content(chunks[-1]):
                    guard.mark_first_token()
                    n_tokens += 1
        except HttpError as e:
            return _error_response(e.status, e.message)
        except DeadlineExceeded as e:
            return _error_response(504, str(e) or DEADLINE_ERROR)
        except OverloadedError as e:
            guard.mark_shed()
            return _overloaded_response(str(e), e.retry_after_ms)
        except (NoHealthyInstances, AllInstancesFailed, ConnectionError, OSError) as e:
            return _error_response(502, f"upstream failure: {e}")
        if not chunks:
            return _error_response(500, "engine produced no response")
        full = aggregate_chat_chunks(chunks) if chat else aggregate_completion_chunks(chunks)
        if (
            chat
            and getattr(ctx.data, "tools", None)
            and getattr(ctx.data, "tool_choice", None) != "none"
        ):
            _extract_tool_calls(full)
        guard.mark_ok()
        guard.count_tokens(n_tokens)
        return web.json_response(full.model_dump(exclude_none=True))


# InflightGuard status label → edge-span terminal status (the recorder pins
# "overloaded"/"error"; plain "success" maps to the span-model "ok")
_EDGE_STATUS = {"success": "ok", "overloaded": "overloaded", "error": "error"}


def _extract_tool_calls(full) -> None:
    """Best-effort function-call detection on a folded chat response.

    When the request carried ``tools`` and the model answered with a bare
    JSON object of the common ``{"name": ..., "arguments"|"parameters": ...}``
    shape (the format llama-3/qwen-style templates train), surface it as an
    OpenAI ``tool_calls`` entry with finish_reason "tool_calls". Models whose
    templates emit other wrappers stream through as plain text (parity with
    the reference, which delegates parsing to its engines).
    """
    import uuid as _uuid

    for choice in full.choices:
        content = choice.message.content
        if not content:
            continue
        text = content.strip()
        if not (text.startswith("{") and text.endswith("}")):
            continue
        try:
            obj = json.loads(text)
        except ValueError:
            continue
        if not isinstance(obj, dict) or "name" not in obj:
            continue
        args = obj.get("arguments", obj.get("parameters"))
        if args is None:
            continue
        choice.message.tool_calls = [
            {
                "id": f"call_{_uuid.uuid4().hex[:24]}",
                "type": "function",
                "function": {
                    "name": obj["name"],
                    "arguments": json.dumps(args) if not isinstance(args, str) else args,
                },
            }
        ]
        choice.message.content = None
        choice.finish_reason = "tool_calls"


def _chunk_has_content(payload) -> bool:
    """True if this chunk carries generated content (a token), not just a
    role/finish frame — keeps output-token metrics and TTFT honest."""
    if not isinstance(payload, dict):
        return False
    for choice in payload.get("choices", []):
        if (choice.get("delta") or {}).get("content"):
            return True
        if choice.get("text"):
            return True
    return False


class _SseTemplate:
    """Per-request fast path for the dominant SSE frame shape.

    Every streamed chat/completions chunk in a request differs ONLY in the
    token text: id/object/created/model repeat verbatim. json.dumps of the
    nested dict was the frontend's hot spot on another machine (a record of
    2026-07; not measured on the current one); splicing the
    escaped token into a pre-encoded prefix/suffix removes the per-token
    tree walk. Any chunk that doesn't match the plain content-delta shape
    (logprobs, finish frames, tool calls, n>1) falls back to json.dumps —
    byte-identical output either way (templates are built FROM a dumps of
    the first matching chunk)."""

    __slots__ = ("prefix", "suffix", "key")

    def __init__(self):
        self.prefix: Optional[bytes] = None
        self.suffix: Optional[bytes] = None
        self.key = None

    _MARK = "@DYN_TPU_TOK@"

    def encode(self, payload) -> Optional[bytes]:
        try:
            # unknown top-level fields (usage from a custom engine, ...)
            # would be frozen into the template: fall back on anything
            # beyond the standard chunk envelope
            if set(payload) - {"id", "object", "created", "model", "choices"}:
                return None
            choices = payload["choices"]
            if len(choices) != 1:
                return None
            ch = choices[0]
            if ch.get("finish_reason") is not None or ch.get("logprobs"):
                return None
            delta = ch.get("delta")
            if delta is not None:
                if set(ch) - {"index", "delta", "finish_reason", "logprobs"}:
                    return None
                if set(delta) != {"content"} or not isinstance(
                    delta["content"], str
                ):
                    return None
                tok = delta["content"]
            else:
                if set(ch) - {"index", "text", "finish_reason", "logprobs"} \
                        or not isinstance(ch.get("text"), str):
                    return None
                tok = ch["text"]
            # the choice index is IN the key: n>1 requests stream as
            # interleaved single-choice chunks with identical id/created —
            # without it, choice 1's tokens would reuse choice 0's template
            key = (
                payload.get("id"), payload.get("created"),
                ch.get("index"), delta is None,
            )
        except (TypeError, KeyError, AttributeError):
            return None
        if key != self.key or self.prefix is None:
            # build the template from a real dumps of THIS chunk with a
            # marker token — output stays byte-identical to the slow path
            probe = json.loads(json.dumps(payload))
            if delta is not None:
                probe["choices"][0]["delta"]["content"] = self._MARK
            else:
                probe["choices"][0]["text"] = self._MARK
            enc = json.dumps(probe)
            mark = json.dumps(self._MARK)[1:-1]
            i = enc.find(mark)
            if i < 0:
                return None
            self.prefix = ("data: " + enc[:i]).encode()
            self.suffix = (enc[i + len(mark):] + "\n\n").encode()
            self.key = key
        # token text goes through the same escaping rules as dumps
        return self.prefix + json.dumps(tok)[1:-1].encode() + self.suffix


def _upstream_status(message: str) -> int:
    """Pre-first-token upstream failures: 504 when the request's deadline
    expired, 429 when every instance shed it as overloaded (the canonical
    message prefixes cross process boundaries in the error envelope), 502
    for everything else upstream."""
    if message.startswith(DEADLINE_ERROR):
        return 504
    if message.startswith(OVERLOAD_ERROR):
        return 429
    return 502


def _overloaded_response(message: str, retry_after_ms: int = 0) -> web.Response:
    """429 with ``Retry-After`` (whole seconds, minimum 1) and an
    OpenAI-error-schema body: overload is the one upstream failure where
    the right client behavior is *back off and retry the same edge*, so it
    gets its own status + hint instead of the generic 502."""
    retry_after_s = max(1, -(-int(retry_after_ms) // 1000)) if retry_after_ms else 1
    return web.json_response(
        {
            "error": {
                "message": message,
                "type": "overloaded_error",
                "param": None,
                "code": "overloaded",
            }
        },
        status=429,
        headers={"Retry-After": str(retry_after_s)},
    )


async def _write_error_finish(resp: web.StreamResponse, envelope: Optional[dict],
                              chat: bool) -> None:
    """Emit a well-formed final SSE chunk with ``finish_reason: "error"`` so
    streaming clients see a terminated choice instead of a dangling stream."""
    chunk: dict = dict(envelope or {})
    choice: dict = {"index": 0, "finish_reason": "error"}
    if chat:
        choice["delta"] = {}
    else:
        choice["text"] = ""
    chunk["choices"] = [choice]
    await resp.write((f"data: {json.dumps(chunk)}\n\n").encode())


def _error_response(status: int, message: str) -> web.Response:
    return web.json_response(
        {"error": {"message": message, "type": "invalid_request_error" if status < 500 else "internal_error"}},
        status=status,
    )
