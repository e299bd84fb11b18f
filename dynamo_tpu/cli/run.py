"""Single-binary launcher: `python -m dynamo_tpu.cli.run in=<src> out=<engine> [flags]`.

Input frontends:
  in=http            OpenAI HTTP frontend (default)
  in=text            interactive REPL
  in=batch:FILE      offline JSONL benchmark with TTFT/ITL stats
  in=dyn://ns.comp.ep  register as a distributed worker endpoint
  in=prefill:NS      disagg prefill worker consuming namespace NS's queue
Output engines:
  out=echo_full      OpenAI-level echo (no model files needed)
  out=echo_core      token-level echo through the preprocessor pipeline
  out=jax            the JAX TPU engine (requires --model-path)
  out=dyn://ns.comp.ep  forward to a remote distributed endpoint

``--wire token`` moves preprocessing to the frontend: workers serve the
CORE token engine and PreprocessedRequest token streams cross the RPC
wire, which is what enables mid-stream resume (a worker dying mid-decode
is re-admitted on a sibling — docs/resilience.md §Mid-stream resume) and
KV-prefix routing over real token ids. Both sides must pass the flag.

Reference parity: launch/dynamo-run (main.rs:220, lib.rs:84-494, opt.rs, flags.rs).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import sys
import time
from typing import Optional

# DYN_TPU_PLATFORM=cpu lets auxiliary processes (frontends, prefill workers on
# a host without a free chip) run on CPU on a host whose JAX defaults to the
# TPU. Must be applied before any model/engine import touches jax.
from dynamo_tpu.runtime.envknobs import env_raw

_platform = env_raw("DYN_TPU_PLATFORM")
if _platform:
    import jax

    jax.config.update("jax_platforms", _platform)

from ..llm.engines import EchoEngineCore, EchoEngineFull
from ..llm.http.service import HttpService, ModelManager
from ..llm.model_card import ModelDeploymentCard
from ..llm.preprocessor import (
    ChatPreprocessorOperator,
    DetokenizeOperator,
    OpenAIPreprocessor,
)
from ..llm.protocols.openai import ChatCompletionRequest
from ..runtime import Context, Pipeline, profiling
from ..runtime.logging_util import init as init_logging

logger = logging.getLogger(__name__)


def _resolve_model_path(spec):
    """--model-path accepts a local dir/.gguf OR a hub repo id (org/name):
    repo ids resolve via the fixture hub / HF cache / download
    (llm/model_card.py resolve_repo; reference hub.rs)."""
    from dynamo_tpu.llm.model_card import looks_like_repo_id, resolve_repo

    if spec and looks_like_repo_id(spec):
        return resolve_repo(spec)
    return spec


def _load_card(flags):
    """Build the model card from --model-path, resolving hub repo ids; a
    repo id also becomes the served model name (unless --model-name)."""
    from dynamo_tpu.llm.model_card import looks_like_repo_id

    spec = flags.model_path
    name = flags.model_name
    if name is None and spec and looks_like_repo_id(spec):
        name = spec
    return ModelDeploymentCard.from_local_path(_resolve_model_path(spec), name)


def parse_io(args: list[str]) -> tuple[str, str, list[str]]:
    """Extract in=/out= positional specs (reference: opt.rs:23-217)."""
    in_spec, out_spec, rest = "http", "echo_full", []
    for a in args:
        if a.startswith("in="):
            in_spec = a[3:]
        elif a.startswith("out="):
            out_spec = a[4:]
        else:
            rest.append(a)
    return in_spec, out_spec, rest


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="dynamo-run", description="dynamo_tpu single-binary launcher"
    )
    p.add_argument("--model-path", default=None, help="HF-layout model directory")
    p.add_argument("--model-name", default=None, help="served model name")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8080)
    p.add_argument("--tensor-parallel-size", type=int, default=1)
    p.add_argument("--pipeline-parallel-size", type=int, default=1,
                   help="GPipe layer stages over the pp mesh axis")
    p.add_argument("--context-parallel-size", type=int, default=1,
                   help="ring-attention sequence shards over the sp mesh axis")
    # multi-host meshes (reference MultiNodeConfig, engines.rs:41-59): all
    # hosts run the same command with their own --node-rank; jax.distributed
    # joins them into one global device mesh over ICI/DCN
    p.add_argument("--num-nodes", type=int, default=1)
    p.add_argument("--node-rank", type=int, default=0)
    p.add_argument("--coordinator-addr", default=None,
                   help="host:port of node 0's jax.distributed coordinator")
    p.add_argument("--max-batch-size", type=int, default=8)
    p.add_argument("--kv-block-size", type=int, default=16)
    p.add_argument("--max-model-len", type=int, default=None)
    p.add_argument("--host-cache-blocks", type=int, default=0,
                   help="host-RAM KV tier size in blocks (0 = disabled)")
    p.add_argument("--router-mode", default="random",
                   help="random | round_robin | kv | load (least-loaded) | "
                        "direct:<instance_id>")
    p.add_argument("--namespace", default="dynamo",
                   help="registry namespace for out=discover model watching")
    p.add_argument("--statestore", default=None, help="statestore url for distributed mode")
    p.add_argument("--bus", default=None, help="message bus url for distributed mode")
    p.add_argument("--wait-workers-timeout", type=float, default=60.0)
    p.add_argument("--extra-engine-args", default=None, help="JSON file of engine kwargs")
    p.add_argument(
        "--wire", choices=["openai", "token"], default="openai",
        help="RPC payload level between frontend and workers: 'openai' "
             "(worker-side preprocessing, default) or 'token' (the frontend "
             "preprocesses and PreprocessedRequest token streams cross the "
             "wire — KV-prefix routing sees real token ids, and a worker "
             "dying mid-decode is absorbed by mid-stream resume, "
             "docs/resilience.md). Both sides of a deployment must agree.")
    p.add_argument("--disagg", choices=["none", "decode"], default="none",
                   help="decode: enqueue long prefills to remote prefill workers")
    p.add_argument("--max-local-prefill-length", type=int, default=1000)
    p.add_argument("--max-prefill-queue-size", type=int, default=2)
    p.add_argument(
        "--engine-isolation", choices=["subprocess", "inprocess"],
        default="subprocess",
        help="pystr:/pytok: engines run as a crash-isolated child process "
             "(default) or imported in-process",
    )
    return p


class DispatchEngine:
    """Routes an OpenAI request to the chat or completions pipeline by shape.

    Used by distributed workers, whose single endpoint receives both kinds
    (reference: the worker-side pipeline in input/endpoint.rs:35-118).
    """

    def __init__(self, chat_engine, completions_engine):
        self._chat = chat_engine
        self._completions = completions_engine

    def generate(self, request):
        data = request.data
        is_chat = hasattr(data, "messages") or (
            isinstance(data, dict) and "messages" in data
        )
        if isinstance(data, dict):
            # requests arriving over RPC are plain dicts: revalidate
            from ..llm.protocols.openai import ChatCompletionRequest, CompletionRequest

            model = ChatCompletionRequest if is_chat else CompletionRequest
            request = request.transfer(model.model_validate(data))
        engine = self._chat if is_chat else self._completions
        return engine.generate(request)


class _TokenWireEngine:
    """Parse PreprocessedRequest wire dicts for token-level cores that
    expect the typed request (``--wire token`` workers; the JAX engine
    parses dicts itself and is served directly)."""

    def __init__(self, inner):
        self._inner = inner

    def generate(self, request):
        from ..llm.protocols.common import PreprocessedRequest

        if isinstance(request.data, dict):
            request = request.transfer(
                PreprocessedRequest.from_dict(request.data)
            )
        return self._inner.generate(request)


def _token_pipelines(card: ModelDeploymentCard, make_core):
    """(chat, completions) pipelines sharing one preprocessor/tokenizer."""
    pre = OpenAIPreprocessor(card)

    def build(chat: bool):
        return (
            Pipeline()
            .link(ChatPreprocessorOperator(pre, chat=chat))
            .link(DetokenizeOperator(card, pre.tokenizer))
            .link_engine(make_core())
        )

    return build(True), build(False)


def _load_user_engine(path: str, isolation: str = "subprocess"):
    """Build a bring-your-own-engine from a user python file.

    ``isolation="subprocess"`` (default, reference parity: engines run as
    crash-isolated children — lib/engines/sglang/src/worker.rs:784) hosts it
    in a child process behind :class:`SubprocessEngine`: a segfaulting or
    leaking engine cannot take the worker down, its logs are scraped, and it
    restarts on crash. ``isolation="inprocess"`` imports it directly.
    """
    if isolation == "subprocess":
        from ..llm.subprocess_engine import SubprocessEngine

        return SubprocessEngine(path)
    from ..llm.subprocess_engine import load_user_engine

    try:
        return load_user_engine(path)
    except RuntimeError as e:
        raise SystemExit(str(e))


def build_engine(out_spec: str, flags: argparse.Namespace):
    """Build the OpenAI-level engines for `out=<spec>`.

    Returns (chat_engine, completions_engine, model_name). Engines take OpenAI
    requests and yield Annotated chunk dicts; either may be None if the backend
    doesn't support that endpoint.
    """
    card: Optional[ModelDeploymentCard] = None
    if flags.model_path:
        card = _load_card(flags)
    model_name = flags.model_name or (card.display_name if card else out_spec)

    if out_spec == "echo_full":
        engine = EchoEngineFull()
        return engine, engine, model_name, None

    if out_spec.startswith(("pystr:", "pytok:")):
        # bring-your-own-engine: a user python file provides the engine
        # (reference lib/engines/python: same two integration levels)
        scheme, _, path = out_spec.partition(":")
        user_engine = _load_user_engine(
            path, getattr(flags, "engine_isolation", "subprocess")
        )
        if scheme == "pystr":
            # OpenAI-request level: the user engine sees plain request dicts
            # (the reference hands its python engines JSON, not typed models)
            from ..runtime.engine import AsyncEngine

            class _DictRequests(AsyncEngine):
                async def generate(self, request):
                    data = request.data
                    if hasattr(data, "model_dump"):
                        data = data.model_dump(exclude_none=True)
                    async for item in user_engine.generate(request.transfer(data)):
                        yield item

            eng = _DictRequests()
            return eng, eng, model_name, None
        # token level: wrap in the preprocessor/detokenizer pipelines
        if card is None:
            raise SystemExit("out=pytok: requires --model-path (tokenizer needed)")
        chat_eng, comp_eng = _token_pipelines(card, lambda: user_engine)
        return chat_eng, comp_eng, model_name, user_engine

    if out_spec == "echo_core":
        if card is None:
            raise SystemExit("out=echo_core requires --model-path (tokenizer needed)")
        if getattr(flags, "wire", "openai") == "token":
            # token-wire drills without a real model: serve the core echo
            # engine directly (same contract as out=jax --wire token)
            core = _TokenWireEngine(EchoEngineCore())
            return core, core, model_name, None
        chat_eng, comp_eng = _token_pipelines(card, EchoEngineCore)
        return chat_eng, comp_eng, model_name, None

    if out_spec == "jax":
        if card is None:
            raise SystemExit("out=jax requires --model-path")
        try:
            from ..engine_jax import build_jax_serving_engine
        except ImportError as e:
            raise SystemExit(f"out=jax unavailable: {e}")

        extra = {}
        if flags.extra_engine_args:
            with open(flags.extra_engine_args) as f:
                extra = json.load(f)
        # start-up by phase (`setup_phase_s` of /debug/engine): `devices`
        # runs from `amain` to the line that names the device
        setup = profiling.setup_clock()
        from ..engine_jax.compile_cache import enable_compile_cache

        cache_dir = enable_compile_cache()
        import jax

        # the process that holds the chip names it: a driver that must stay
        # off JAX while this server runs reads the device from this line
        dev = jax.devices()[0]
        logger.info("device %s", json.dumps({
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()), "compile_cache": cache_dir,
            # the chip a launcher handed this process (runtime/chips.py)
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        }))
        core = build_jax_serving_engine(
            card,
            max_batch_size=flags.max_batch_size,
            kv_block_size=flags.kv_block_size,
            max_model_len=flags.max_model_len,
            tensor_parallel_size=flags.tensor_parallel_size,
            pipeline_parallel_size=flags.pipeline_parallel_size,
            context_parallel_size=flags.context_parallel_size,
            host_cache_blocks=flags.host_cache_blocks,
            **extra,
        )
        # compile the step functions off the request path
        logger.info("warmup %s", json.dumps(core.warmup()))
        setup.switch(profiling.S_HTTP)  # ends where the port answers
        if getattr(flags, "wire", "openai") == "token":
            # token wire: the CORE engine serves the endpoint directly
            # (PreprocessedRequest dicts in, LLMEngineOutput dicts out);
            # the frontend runs the preprocessor/detokenizer around its
            # remote client (out=dyn:// --wire token --model-path)
            return core, core, model_name, core
        chat_eng, comp_eng = _token_pipelines(card, lambda: core)
        return chat_eng, comp_eng, model_name, core

    if out_spec.startswith("dyn://"):
        raise SystemExit("internal: dyn:// engines are built in amain")  # async path

    raise SystemExit(f"unknown out= engine: {out_spec!r}")


async def build_remote_client(out_spec: str, flags: argparse.Namespace):
    """out=dyn://ns.comp.ep → EndpointClient routing across live workers."""
    from ..runtime.distributed import DistributedRuntime, parse_endpoint_path

    ns, comp, ep = parse_endpoint_path(out_spec)
    drt = await DistributedRuntime.create(
        statestore_url=flags.statestore, bus_url=flags.bus
    )
    # KV-aware routing needs token ids at the frontend; raw OpenAI dicts don't
    # carry them, so (given a tokenizer) render+tokenize just for routing —
    # the reference tokenizes frontend-side before its KV router (SURVEY §3.4)
    route_token_fn = None
    if flags.router_mode == "kv" and flags.model_path:
        card = _load_card(flags)
        pre = OpenAIPreprocessor(card)
        route_token_fn = pre.route_token_ids
    from ..runtime.resilience import ResiliencePolicy

    client = await drt.namespace(ns).component(comp).endpoint(ep).client(
        flags.router_mode,
        kv_block_size=flags.kv_block_size,
        route_token_fn=route_token_fn,
        policy=ResiliencePolicy.from_env(),
    )
    await client.wait_for_instances(1, timeout=flags.wait_workers_timeout)
    return client, drt


async def run_http(chat_engine, completions_engine, model_name: str,
                   flags: argparse.Namespace, core_engine=None) -> None:
    manager = ModelManager()
    if chat_engine is not None:
        manager.add_chat_model(model_name, chat_engine)
    if completions_engine is not None:
        manager.add_completions_model(model_name, completions_engine)
    service = HttpService(
        manager, host=flags.host, port=flags.port, engine=core_engine
    )
    logger.info("serving model %r on port %d", model_name, flags.port)
    await service.run()


async def run_http_discover(flags: argparse.Namespace) -> None:
    """in=http out=discover: frontend whose model set tracks the registry.

    Workers that register models (Endpoint.serve model_entry / llmctl) appear
    and disappear live — no frontend restart. Reference: the standalone
    `http` component binary (components/http/src/main.rs:50-104).
    """
    from ..llm.http.discovery import ModelWatcher
    from ..runtime.distributed import DistributedRuntime

    drt = await DistributedRuntime.create(
        statestore_url=flags.statestore, bus_url=flags.bus
    )
    manager = ModelManager()
    watcher = ModelWatcher(
        drt, flags.namespace, manager,
        router_mode=flags.router_mode, kv_block_size=flags.kv_block_size,
    )
    watcher.start()
    service = HttpService(manager, host=flags.host, port=flags.port)
    logger.info(
        "discovery frontend on port %d (watching %s)", flags.port, watcher.prefix
    )
    try:
        await service.run()
    finally:
        await watcher.close()


async def run_text(engine, model_name: str) -> None:
    """Interactive REPL (reference: input/text.rs)."""
    print(f"dynamo_tpu REPL — model {model_name!r}. Ctrl-D to exit.")
    loop = asyncio.get_running_loop()
    history: list[dict] = []
    while True:
        try:
            line = await loop.run_in_executor(None, lambda: input("user> "))
        except EOFError:
            print()
            return
        if not line.strip():
            continue
        history.append({"role": "user", "content": line})
        req = ChatCompletionRequest.model_validate(
            {"model": model_name, "messages": history, "stream": True}
        )
        text_out = []
        sys.stdout.write("assistant> ")
        async for item in engine.generate(Context(req)):
            data = item.data if hasattr(item, "data") else item
            if not data:
                continue
            for choice in data.get("choices", []):
                piece = (choice.get("delta") or {}).get("content")
                if piece:
                    text_out.append(piece)
                    sys.stdout.write(piece)
                    sys.stdout.flush()
        print()
        history.append({"role": "assistant", "content": "".join(text_out)})


async def run_batch(engine, model_name: str, batch_file: str) -> None:
    """Offline benchmark: JSONL prompts in, TTFT/ITL/throughput stats out.

    Reference: input/batch.rs:289.
    """
    def _read_prompts() -> list:
        out = []
        with open(batch_file) as f:
            for line in f:
                line = line.strip()
                if line:
                    out.append(json.loads(line))
        return out

    prompts = await asyncio.to_thread(_read_prompts)

    ttfts, itls, counts = [], [], []
    t_start = time.perf_counter()
    for p in prompts:
        text = p.get("text") or p.get("prompt") or ""
        max_tokens = p.get("max_tokens")
        req = ChatCompletionRequest.model_validate(
            {
                "model": model_name,
                "messages": [{"role": "user", "content": text}],
                "stream": True,
                **({"max_tokens": max_tokens} if max_tokens else {}),
            }
        )
        t0 = time.perf_counter()
        first = None
        last = None
        n = 0
        async for item in engine.generate(Context(req)):
            data = item.data if hasattr(item, "data") else item
            if not data:
                continue
            now = time.perf_counter()
            if first is None:
                first = now
            else:
                itls.append(now - last)
            last = now
            n += 1
        if first is not None:
            ttfts.append(first - t0)
        counts.append(n)
    elapsed = time.perf_counter() - t_start

    def pct(xs, q):
        if not xs:
            return 0.0
        xs = sorted(xs)
        return xs[min(len(xs) - 1, int(q * len(xs)))]

    stats = {
        "requests": len(prompts),
        "elapsed_s": round(elapsed, 3),
        "total_chunks": sum(counts),
        "ttft_p50_ms": round(pct(ttfts, 0.5) * 1e3, 2),
        "ttft_p95_ms": round(pct(ttfts, 0.95) * 1e3, 2),
        "itl_p50_ms": round(pct(itls, 0.5) * 1e3, 2),
        "itl_p95_ms": round(pct(itls, 0.95) * 1e3, 2),
        "chunks_per_s": round(sum(counts) / elapsed, 2) if elapsed else 0.0,
    }
    print(json.dumps(stats))


async def run_endpoint(chat_engine, completions_engine, model_name: str, in_spec: str,
                       flags: argparse.Namespace, core_engine=None) -> None:
    """Register as a distributed worker on dyn://ns.comp.ep (serves both
    chat and completions requests via shape dispatch). Engines with a KV
    allocator also publish KV events + load metrics for KV-aware routing."""
    from ..runtime.distributed import (
        DistributedRuntime,
        attach_kv_publishing,
        parse_endpoint_path,
    )

    wire = getattr(flags, "wire", "openai")
    # token wire: the endpoint speaks PreprocessedRequest dicts directly
    # (no OpenAI shape dispatch — the frontend already lowered the request)
    engine = (
        chat_engine if wire == "token"
        else DispatchEngine(chat_engine, completions_engine)
    )
    ns, comp, ep = parse_endpoint_path(in_spec)
    drt = await DistributedRuntime.create(
        statestore_url=flags.statestore, bus_url=flags.bus
    )
    component = drt.namespace(ns).component(comp)
    await component.create_service()
    endpoint = component.endpoint(ep)
    model_entry = {"name": model_name, "kinds": ["chat", "completions"]}
    if wire != "openai":
        # advertised so raw-dict frontends (out=discover) skip this worker
        # instead of feeding it OpenAI dicts it cannot parse
        model_entry["wire"] = wire
    info = await endpoint.serve(engine, model_entry=model_entry)
    if core_engine is not None and hasattr(core_engine, "metrics_snapshot"):
        from ..runtime.distributed import serve_stats_endpoint

        await attach_kv_publishing(endpoint, core_engine)
        await serve_stats_endpoint(endpoint, core_engine)  # pull/scrape plane
        logger.info("kv events + metrics publishing enabled (worker key %s)", drt.worker_id)
    transfer_server = None
    if flags.disagg == "decode" and core_engine is not None:
        if not hasattr(core_engine, "set_remote_prefill_policy"):
            raise SystemExit(
                "--disagg decode needs an engine with remote-prefill support "
                f"(out=jax); {type(core_engine).__name__} has none"
            )
        from ..disagg.protocols import DisaggConfig
        from ..disagg.serving import enable_disagg_decode

        transfer_server = await enable_disagg_decode(
            endpoint, core_engine, info.instance_id,
            config=DisaggConfig(
                max_local_prefill_length=flags.max_local_prefill_length,
                max_prefill_queue_size=flags.max_prefill_queue_size,
            ),
            # identity = card checksum, NOT the served alias (--model-name):
            # prefill and decode workers loading the same weights must agree
            model=(
                ModelDeploymentCard.from_local_path(_resolve_model_path(flags.model_path)).mdcsum or ""
                if flags.model_path
                else ""
            ),
        )
    if core_engine is not None and hasattr(core_engine, "stage_migration"):
        # live in-flight migration (docs/resilience.md §Live migration):
        # drains migrate this worker's decode streams to siblings over the
        # transfer plane. Reuses the disagg transfer server when one exists
        # (same rendezvous key); DYN_TPU_MIGRATE=0 ⇒ attach_migration
        # returns None without constructing anything (old drain semantics).
        from ..disagg.migration import attach_migration

        coord = await attach_migration(
            endpoint, core_engine, transfer_server=transfer_server
        )
        if coord is not None:
            logger.info(
                "live migration enabled for worker %s (drain deadline %.0fs)",
                drt.worker_id, coord.policy.drain_deadline,
            )
    logger.info("worker %s serving %s at %s", info.worker_id, in_spec, info.address)
    from ..runtime.worker import serve_until_shutdown

    # SIGTERM → deregister, drain in-flight RPC, close engine; exit 911 on
    # overrun (runtime/worker.py documents the codes)
    await serve_until_shutdown(drt, engine=core_engine)


async def run_prefill_worker_main(out_spec: str, in_spec: str, flags: argparse.Namespace) -> None:
    """in=prefill:<namespace>: consume the prefill work queue (disagg)."""
    from ..disagg.prefill_worker import PrefillEngine, run_prefill_worker
    from ..engine_jax.weights import config_from_card, load_params
    from ..runtime.distributed import DistributedRuntime

    namespace = in_spec.split(":", 1)[1] if ":" in in_spec else "dynamo"
    if not flags.model_path:
        raise SystemExit("prefill worker requires --model-path")
    card = _load_card(flags)
    model_config = config_from_card(card)
    params = load_params(card, model_config)
    engine = PrefillEngine(
        model_config, params,
        max_model_len=flags.max_model_len or min(card.context_length, 4096),
        block_size=flags.kv_block_size,
        model=card.mdcsum or "",
    )
    drt = await DistributedRuntime.create(
        statestore_url=flags.statestore, bus_url=flags.bus
    )
    await run_prefill_worker(drt, namespace, engine)


def init_multihost(flags) -> None:
    """Join this process into a multi-host JAX runtime (no-op single-node).

    After initialize(), jax.devices() spans every node's chips and meshes
    built from it ride ICI within a slice and DCN across slices — the TPU
    analogue of the reference's Ray/torch.distributed multinode bring-up
    (vllm0_7 ray.rs:66-170, sglang leader/follower)."""
    if flags.num_nodes <= 1:
        return
    if not flags.coordinator_addr:
        raise SystemExit("--num-nodes > 1 requires --coordinator-addr")
    import jax

    jax.distributed.initialize(
        coordinator_address=flags.coordinator_addr,
        num_processes=flags.num_nodes,
        process_id=flags.node_rank,
    )
    logger.info(
        "joined multi-host runtime: node %d/%d, %d global devices",
        flags.node_rank, flags.num_nodes, jax.device_count(),
    )


async def amain(argv: list[str]) -> None:
    setup = profiling.setup_clock()
    setup.credit(profiling.S_BEFORE_MAIN, profiling.process_age_us() or 0.0)
    setup.switch(profiling.S_DEVICES)
    init_logging()
    in_spec, out_spec, rest = parse_io(argv)
    flags = build_parser().parse_args(rest)
    init_multihost(flags)
    if in_spec.startswith("prefill"):
        await run_prefill_worker_main(out_spec, in_spec, flags)
        return

    core_engine = None
    if out_spec == "discover":
        if in_spec != "http":
            raise SystemExit("out=discover requires in=http")
        await run_http_discover(flags)
        return
    if out_spec.startswith("dyn://"):
        client, _drt = await build_remote_client(out_spec, flags)
        if flags.wire == "token":
            # frontend-side preprocessing: OpenAI → PreprocessedRequest →
            # remote token engine → detokenize. Token ids cross the wire,
            # so the routing client can journal them — a worker dying
            # mid-decode resumes on a sibling (docs/resilience.md)
            if not flags.model_path:
                raise SystemExit(
                    "--wire token requires --model-path (the frontend "
                    "tokenizes; workers serve the core engine)"
                )
            card = _load_card(flags)
            chat_engine, completions_engine = _token_pipelines(
                card, lambda: client
            )
            model_name = flags.model_name or card.display_name
        else:
            chat_engine = completions_engine = client
            model_name = flags.model_name or out_spec
    else:
        chat_engine, completions_engine, model_name, core_engine = build_engine(out_spec, flags)

    # multi-host serving: after the lockstep warmup, followers execute the
    # leader's broadcast dispatch stream; only the leader serves a frontend
    # (parallel/multihost_serving.py; flags: --num-nodes N --node-rank R
    # --coordinator-addr host:port, same on every host)
    if flags.num_nodes > 1 and core_engine is not None and getattr(core_engine, "mesh", None) is not None:
        import jax as _jax

        from ..parallel.multihost_serving import LeaderBroadcaster, follower_serve

        if _jax.process_index() != 0:
            logger.info("node %d: following the leader's dispatch stream", flags.node_rank)
            await asyncio.to_thread(
                follower_serve,
                core_engine.model_config, core_engine.params,
                core_engine.config, core_engine.mesh, engine=core_engine,
            )
            return
        hook = LeaderBroadcaster(core_engine)
        core_engine._dispatch_hook = hook
        try:
            await _serve_frontend(
                in_spec, chat_engine, completions_engine, model_name, flags,
                core_engine,
            )
        finally:
            # release the followers: without the shutdown opcode every
            # non-zero rank blocks forever in broadcast_one_to_all
            core_engine.close()
            hook.shutdown()
        return

    await _serve_frontend(
        in_spec, chat_engine, completions_engine, model_name, flags, core_engine
    )


async def _serve_frontend(in_spec, chat_engine, completions_engine, model_name,
                          flags, core_engine) -> None:
    if in_spec == "http":
        await run_http(chat_engine, completions_engine, model_name, flags,
                       core_engine=core_engine)
    elif in_spec == "text":
        await run_text(chat_engine, model_name)
    elif in_spec.startswith("batch:"):
        await run_batch(chat_engine, model_name, in_spec[len("batch:"):])
    elif in_spec.startswith("dyn://"):
        await run_endpoint(chat_engine, completions_engine, model_name, in_spec, flags,
                           core_engine=core_engine)
    elif in_spec == "none":
        await asyncio.Event().wait()
    else:
        raise SystemExit(f"unknown in= frontend: {in_spec!r}")


def main() -> None:
    try:
        asyncio.run(amain(sys.argv[1:]))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
