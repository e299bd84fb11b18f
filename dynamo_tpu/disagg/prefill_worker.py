"""Batched prefill engine + the prefill worker loop.

A prefill worker pops RemotePrefillRequests from the shared work queue,
computes the prompt KV, samples the first output token with the request's
sampling params, and ships the *uncached-suffix* pages to the decode
worker's transfer server.

Two things make it cheap on repeat traffic:

- **Batched, chunked prefill**: requests run through a full
  :class:`~dynamo_tpu.engine_jax.engine.JaxServingEngine` capped at one
  output token, so N concurrent remote prefills share [slots, chunk]
  dispatches (and the engine's own prefix cache) instead of running
  batch-1 sequentially.
- **Prefix read-back**: when the decode worker already holds the prompt's
  prefix KV (multi-turn), the worker READS those pages over the transfer
  plane (``read_blocks``) and seeds them into the engine's prefix cache,
  so only the suffix is computed — matching the reference's
  ``computed_block_ids`` + NIXL ``read_blocks`` semantics
  (vllm_v0.7.2 patch remote_prefill.py / nixl.py:1067-1467).

Reference parity: PrefillWorker (examples/llm/components/prefill_worker.py:
34-181) — re-designed around the serving engine instead of a patched vLLM.
"""

from __future__ import annotations

import asyncio
import json
import logging
import math
import uuid
from typing import Dict, List, Optional, Tuple

from dynamo_tpu.disagg.protocols import (
    PREFILL_QUEUE,
    TRANSFER_KEY_PREFIX,
    RemotePrefillRequest,
)
from dynamo_tpu.disagg.transfer import KvTransferClient, _engine_call
from dynamo_tpu.kv import pages as kv_pages
from dynamo_tpu.kv.pages import KvDtypeMismatch
from dynamo_tpu.runtime import tracing

logger = logging.getLogger(__name__)


class PrefillEngine:
    """Prefill-only wrapper over the batched serving engine.

    Each prefill is a max_tokens=1 request whose pages are parked on finish
    (engine hold_pages) and extracted for shipping; concurrent prefills
    batch into shared chunk dispatches.
    """

    def __init__(self, model_config, params, max_model_len: int = 2048,
                 block_size: int = 16, min_bucket: int = 16, model: str = "",
                 slots: int = 4, prefill_chunk: int = 256):
        from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine

        del min_bucket  # kept for constructor compatibility (bucketed v1 engine)
        self.model_config = model_config
        self.block_size = block_size
        self.model = model
        self.max_model_len = max_model_len
        self.engine = JaxServingEngine(
            model_config, params,
            EngineConfig(
                max_slots=slots,
                kv_block_size=block_size,
                max_model_len=max_model_len,
                decode_steps=1,
                prefill_chunk=min(prefill_chunk, max_model_len),
            ),
        )
        # tokens actually computed: per-request (keyed until returned) and
        # the most recent value (tests assert delta-only computation)
        self._computed: Dict[str, int] = {}
        self.last_computed_tokens: int = -1

    def warmup(self) -> None:
        self.engine.warmup()

    def close(self) -> None:
        self.engine.close()

    async def prefill_request(
        self,
        token_ids: List[int],
        cached_tokens: int,
        sampling: dict,
        prefix_kv: Optional[kv_pages.Pages] = None,
        as_device: bool = False,
    ) -> Tuple[int, kv_pages.Pages, int]:
        """Compute the prompt KV; return (first_token, pages,
        computed_tokens), the pages covering blocks from
        ``cached_tokens // block_size`` onward.

        ``prefix_kv`` = the pages of the full blocks of
        ``token_ids[:cached_tokens]`` read from the decode worker: they are
        seeded into the engine's prefix cache first, so the engine computes
        only the suffix. ``as_device=True`` returns jax arrays (same-host
        device path)."""
        from dynamo_tpu.llm.protocols.common import (
            PreprocessedRequest,
            SamplingOptions,
            StopConditions,
        )
        from dynamo_tpu.runtime.engine import Context

        n = len(token_ids)
        if n > self.max_model_len - 1:
            raise ValueError(
                f"prompt {n} exceeds prefill max_model_len {self.max_model_len}"
            )
        if prefix_kv is not None and cached_tokens % self.block_size == 0:
            try:
                seeded = await _engine_call(
                    self.engine,
                    lambda: self.engine.seed_external_prefix(
                        token_ids[:cached_tokens], prefix_kv
                    ),
                )
            except KvDtypeMismatch as e:
                # decode and prefill pools disagree on the page layout
                # (rolling upgrade / per-process DYN_TPU_KV_DTYPE skew): the
                # read-back pages are unusable HERE, but the prompt is not —
                # recompute it in full, exactly like a stale prefix read.
                # Failing the whole remote prefill would silently disable
                # disaggregation for every prefix-hit request.
                logger.warning(
                    "decode-worker prefix pages unusable (%s); "
                    "recomputing full prompt", e,
                )
                seeded = 0
            if seeded:
                logger.debug("seeded %d prefix blocks from decode worker", seeded)

        req = PreprocessedRequest(
            token_ids=list(token_ids),
            stop_conditions=StopConditions(max_tokens=1, ignore_eos=True),
            sampling_options=SamplingOptions(
                temperature=sampling.get("temperature"),
                top_k=sampling.get("top_k"),
                top_p=sampling.get("top_p"),
                seed=sampling.get("seed"),
            ),
        )
        ctx = Context(req, request_id=f"prefill-{uuid.uuid4().hex}")
        self.engine.hold_pages(ctx.id)
        first_token: Optional[int] = None
        try:
            async for item in self.engine.generate(ctx):
                if item.event == "error":
                    raise RuntimeError(
                        f"prefill engine error: {'; '.join(item.comment)}"
                    )
                d = item.data or {}
                ids = d.get("token_ids") or []
                if ids and first_token is None:
                    first_token = int(ids[0])
            if first_token is None:
                raise RuntimeError("prefill produced no token")
            first_block = cached_tokens // self.block_size
            n_blocks = math.ceil(n / self.block_size)

            def extract():
                alloc = self.engine._held_allocs.get(ctx.id)
                if alloc is not None:
                    computed = n - alloc.cached_tokens
                    self._computed[ctx.id] = computed
                    # concurrent requests each get their own count from the
                    # returned tuple; this field is the LAST finished one
                    # (sync-path and test convenience only)
                    self.last_computed_tokens = computed
                return self.engine.take_held_pages(
                    ctx.id, first_block, n_blocks, as_device=as_device
                )

            pages = await _engine_call(self.engine, extract)
            return first_token, pages, self._computed.pop(ctx.id, -1)
        except BaseException:
            self.engine.post(lambda: self.engine.release_held(ctx.id))
            raise

    def prefill(
        self, token_ids: List[int], cached_tokens: int, sampling: dict,
        as_device: bool = False,
    ) -> Tuple[int, kv_pages.Pages]:
        """Synchronous convenience wrapper (no prefix read-back). Safe to
        call with or without a running event loop —
        inside one, the request runs on a private loop in a worker thread
        (and blocks the caller, like any sync compute would)."""
        coro = self.prefill_request(
            token_ids, cached_tokens, sampling, as_device=as_device
        )
        try:
            asyncio.get_running_loop()
        except RuntimeError:
            return asyncio.run(coro)[:2]
        import concurrent.futures

        with concurrent.futures.ThreadPoolExecutor(1) as ex:
            return ex.submit(asyncio.run, coro).result()[:2]


def _validate_request(req, engine: "PrefillEngine") -> None:
    """Shared decode↔prefill compatibility checks (both transfer paths)."""
    if req.block_size and req.block_size != engine.block_size:
        raise ValueError(
            f"block_size mismatch: decode worker uses {req.block_size}, "
            f"this prefill worker uses {engine.block_size}"
        )
    if req.model and engine.model and req.model != engine.model:
        raise ValueError(
            f"model mismatch: decode worker serves {req.model!r}, "
            f"this prefill worker loaded {engine.model!r}"
        )


def _validate_pages(req, pages) -> None:
    if kv_pages.count(pages) != len(req.block_ids):
        raise ValueError(
            f"page count mismatch: computed {kv_pages.count(pages)}, decode "
            f"expects {len(req.block_ids)} (block_size skew?)"
        )


async def run_prefill_worker(
    runtime, namespace: str, engine: PrefillEngine, policy=None
) -> None:
    """Pop → prefill → ship, forever. Multiple prefill workers share the
    queue; within one worker, up to the engine's slot count of requests run
    concurrently (they batch into shared chunk dispatches).

    ``policy`` (a :class:`~dynamo_tpu.runtime.resilience.ResiliencePolicy`,
    env-derived by default) drives the retry/backoff behavior of the two
    network interactions on this path: resolving the decode worker's
    transfer address (which races re-registration after lease loss) and
    shipping the computed pages (which can hit a decode worker mid-bounce)."""
    if runtime.bus is None:
        raise RuntimeError("prefill worker needs the message bus")
    from dynamo_tpu.disagg.device_transfer import make_device_plane
    from dynamo_tpu.runtime.distributed import attach_kv_publishing
    from dynamo_tpu.runtime.resilience import ResiliencePolicy

    policy = policy or ResiliencePolicy.from_env()
    backoff_rng = policy.rng()
    client = KvTransferClient(device_plane=make_device_plane())
    addr_cache: Dict[str, str] = {}
    queue = f"{namespace}.{PREFILL_QUEUE}"
    sem = asyncio.Semaphore(engine.engine.config.max_slots)
    tasks: set = set()
    # publish role-tagged ForwardPassMetrics (capacity, phase latencies,
    # KV events) like every decode worker does: the cluster rollup's
    # `prefill` pool — what the planner resizes — is fed by REAL prefill
    # workers, not just mock fleets (ROADMAP item-4 remainder). The
    # endpoint handle only anchors namespace + worker identity; prefill
    # workers still consume the bus queue rather than serving RPC.
    try:
        if engine.model and not getattr(engine.engine, "model_name", None):
            engine.engine.model_name = engine.model  # cluster attribution
        # bind_admission/bind_events off: a co-hosted decode RPC server
        # keeps its own capacity probe, and prefill-only blocks must not
        # enter the router's prefix radix tree as routable decode hits
        await attach_kv_publishing(
            runtime.namespace(namespace).component("prefill").endpoint("stats"),
            engine.engine, role="prefill", bind_admission=False,
            bind_events=False,
        )
    except Exception:
        # metrics must never keep a prefill worker from serving
        logger.warning("prefill metrics publishing unavailable", exc_info=True)
    logger.info("prefill worker consuming %s", queue)

    async def handle(req: RemotePrefillRequest) -> None:
        # the request's trace context rode the queue (RemotePrefillRequest.
        # traceparent): this worker's spans — remote prefill + kv transfer —
        # join the decode request's trace, so a disaggregated request reads
        # as ONE trace end to end. set_current: the transfer plane's
        # kv_transfer spans nest under this one via the contextvar.
        with tracing.span(
            "disagg.remote_prefill",
            parent=tracing.parse_traceparent(req.traceparent),
            attributes={"request_id": req.request_id,
                        "prompt_tokens": len(req.token_ids),
                        "cached_tokens": req.cached_tokens},
            set_current=True,
        ) as pspan:
            await _handle_inner(req, pspan)

    async def _handle_inner(
        req: RemotePrefillRequest, pspan=None
    ) -> None:
        # same-process decode engine → device path: pages stay jax arrays
        # and land on the decode mesh via device_put, no host staging
        from dynamo_tpu.disagg.serving import LOCAL_DECODE_ENGINES
        from dynamo_tpu.disagg.transfer import LocalKvTransfer

        local_engine = LOCAL_DECODE_ENGINES.get(req.engine_id)
        if local_engine is not None:
            transfer = LocalKvTransfer(local_engine)
            addr = ""
        else:
            addr = addr_cache.get(req.engine_id)
            if addr is None:
                key = f"{namespace}/{TRANSFER_KEY_PREFIX}{req.engine_id}"
                raw_addr = None
                # re-registration races: exponential backoff, with enough
                # attempts that the cumulative wait (~3s at defaults) covers
                # a lease-loss re-registration window
                for attempt in range(max(policy.max_attempts, 6) + 1):
                    if attempt:
                        await asyncio.sleep(policy.backoff(attempt, backoff_rng))
                    raw_addr = await runtime.store.get(key)
                    if raw_addr is not None:
                        break
                if raw_addr is None:
                    # can't reach the decode worker to report failure either;
                    # its engine-side remote_prefill_timeout falls the request
                    # back to local prefill
                    logger.error(
                        "no transfer address for engine %s; dropping %s "
                        "(decode worker will fall back after timeout)",
                        req.engine_id, req.request_id,
                    )
                    return
                addr = raw_addr.decode()
                addr_cache[req.engine_id] = addr
            transfer = client

        try:
            _validate_request(req, engine)
            # decode worker holds the prompt's prefix KV: read it instead of
            # recomputing the shared history (multi-turn's flagship win).
            # Every page's registered hash must equal the hash chain of the
            # prefix tokens: a request that sat in the queue past the decode
            # side's fallback can find its pages freed and REUSED, and
            # seeding those would poison this engine's prefix cache with
            # wrong KV under correct hashes.
            prefix_kv = None
            if req.cached_tokens > 0 and req.prefix_block_ids:
                try:
                    read, got_hashes = await transfer.read_blocks(
                        addr, req.prefix_block_ids
                    )
                    from dynamo_tpu.kv.tokens import compute_block_hashes_for_seq

                    # the registered hashes chain from the DECODE side's
                    # salt (carried on the request) — using a local default
                    # here would make every check fail under a salted
                    # deployment, silently disabling the prefix read
                    expect = compute_block_hashes_for_seq(
                        req.token_ids[: req.cached_tokens], engine.block_size,
                        salt=bytes.fromhex(req.salt_hex) if req.salt_hex else None,
                    )
                    if list(got_hashes) == list(expect):
                        prefix_kv = read
                    else:
                        logger.warning(
                            "prefix pages for %s changed since enqueue "
                            "(stale read); recomputing full prompt",
                            req.request_id,
                        )
                except Exception:
                    logger.warning(
                        "prefix read_blocks failed for %s; recomputing full "
                        "prompt", req.request_id, exc_info=True,
                    )
            tok, pages, computed = await engine.prefill_request(
                req.token_ids, req.cached_tokens, req.sampling,
                prefix_kv=prefix_kv, as_device=local_engine is not None,
            )
            _validate_pages(req, pages)
            # the decode worker can be mid-bounce exactly when the pages are
            # ready: retry transport failures within the policy budget,
            # RE-RESOLVING the transfer address each time — a restarted
            # decode worker re-registers on a fresh ephemeral port, so
            # redialing the stale address could never succeed
            for attempt in range(1, policy.max_attempts + 1):
                try:
                    await transfer.send_blocks(
                        addr, req.request_id, tok, req.block_ids, pages
                    )
                    break
                except (ConnectionError, OSError, asyncio.IncompleteReadError):
                    # IncompleteReadError too: a decode worker that closes
                    # gracefully between our write and the ack read raises a
                    # clean EOF (EOFError, not OSError) — same mid-bounce
                    # case this retry exists for. send_blocks already
                    # evicted its own failed conn (identity-guarded); here
                    # we only invalidate the address mapping so the retry
                    # can re-resolve it
                    addr_cache.pop(req.engine_id, None)
                    if attempt >= policy.max_attempts:
                        raise
                    logger.warning(
                        "send_blocks to %s failed (attempt %d/%d); retrying",
                        addr, attempt, policy.max_attempts,
                    )
                    await asyncio.sleep(policy.backoff(attempt, backoff_rng))
                    if local_engine is None:
                        fresh = await runtime.store.get(
                            f"{namespace}/{TRANSFER_KEY_PREFIX}{req.engine_id}"
                        )
                        if fresh is not None:
                            addr = fresh.decode()
                            addr_cache[req.engine_id] = addr
            if pspan is not None:
                pspan.set_attribute("computed_tokens", computed)
                pspan.set_attribute(
                    "path", "local" if local_engine is not None else "tcp"
                )
            logger.info(
                "prefilled %s%s (%d tokens, computed %d → %d pages)",
                req.request_id,
                " locally via device path" if local_engine is not None else "",
                len(req.token_ids), computed, kv_pages.count(pages),
            )
        except Exception as e:
            # the failure is reported in-band (send_failure / local
            # fallback), so it never escapes to the span CM — mark the
            # span here or the trace would read as a healthy prefill
            if pspan is not None:
                pspan.set_attribute("error", f"{type(e).__name__}: {e}")
                pspan.status = "error"
            logger.exception("prefill failed for %s", req.request_id)
            if local_engine is not None:
                local_engine.fail_remote_prefill(req.request_id, str(e))
                return
            addr_cache.pop(req.engine_id, None)
            try:
                await client.send_failure(addr, req.request_id, str(e))
            except (ConnectionError, OSError):
                logger.warning(
                    "could not report prefill failure for %s", req.request_id
                )

    try:
        while True:
            # ack-mode pop (at-least-once): the item stays in-flight on the
            # bus until this worker finishes handling it — a worker crash or
            # a bus bounce mid-prefill redelivers instead of dropping the
            # request (NATS JetStream work-queue semantics,
            # examples/llm/utils/nats_queue.py:155)
            popped = await runtime.bus.queue_pop_acked(queue, block=True)
            if popped is None:
                continue
            raw, msg_id = popped
            req = RemotePrefillRequest.from_dict(json.loads(raw))
            await sem.acquire()

            async def run_one(r=req, mid=msg_id):
                try:
                    await handle(r)
                finally:
                    # ack on every handled outcome — handle() reports its
                    # own failures to the requesting engine, which also has
                    # a remote-prefill timeout sweep. Only worker/bus DEATH
                    # leaves the item unacked, and that is exactly the case
                    # redelivery is for (a poison request must not redeliver
                    # forever).
                    try:
                        await runtime.bus.queue_ack(mid)
                    except (ConnectionError, RuntimeError, OSError):
                        pass  # bus gone: the item redelivers, by design
                    sem.release()

            t = asyncio.create_task(run_one())
            tasks.add(t)
            t.add_done_callback(tasks.discard)
    finally:
        # cancelling the worker must stop in-flight prefills too (the
        # sequential loop this replaced stopped everything on cancel);
        # otherwise they race the engine teardown that usually follows
        for t in list(tasks):
            t.cancel()
