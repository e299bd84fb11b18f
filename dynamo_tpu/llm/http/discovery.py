"""Frontend model discovery: keep the ModelManager in sync with the registry.

Workers register ``{ns}/models/{kind}/{name}`` entries (lease-attached) when
they serve an endpoint; ``llmctl`` writes the same entries by hand. The
frontend watches the prefix and adds/removes models live — a worker started
AFTER the frontend appears without a restart, and a dead worker's lease
expiry removes its model.

Re-designed from the reference's etcd watcher
(`lib/llm/src/http/service/discovery.rs:38-171`, consumed by
`components/http/src/main.rs:50-104`): same key layout and lifecycle, but
the client pipeline is this framework's direct-dial EndpointClient instead
of a NATS push router.
"""

from __future__ import annotations

import asyncio
import json
import logging
import uuid
from typing import Dict, Optional

from dynamo_tpu.llm.http.service import ModelManager
from dynamo_tpu.runtime import control_plane

logger = logging.getLogger(__name__)


class ModelWatcher:
    """Watches ``{namespace}/models/`` and maintains manager + clients."""

    def __init__(
        self,
        drt,
        namespace: str,
        manager: ModelManager,
        router_mode: str = "round_robin",
        kv_block_size: int = 16,
        policy=None,
    ):
        from dynamo_tpu.runtime.resilience import ResiliencePolicy

        self.drt = drt
        self.namespace = namespace
        self.manager = manager
        self.router_mode = router_mode
        self.kv_block_size = kv_block_size
        # one resilience policy shared by every discovered model's client;
        # defaults come from the environment so operators can tune the
        # frontend's failover/deadline behavior without code changes
        self.policy = policy or ResiliencePolicy.from_env()
        # entries are per-worker-instance ({kind}/{name}:{instance}); a model
        # is served by ONE client per (kind, name) and removed only when its
        # last entry disappears
        self._entry_model: Dict[str, tuple] = {}  # key → (kind, name)
        self._model_keys: Dict[tuple, set] = {}  # (kind, name) → entry keys
        self._clients: Dict[tuple, object] = {}  # (kind, name) → EndpointClient
        self._endpoint_paths: Dict[tuple, str] = {}  # (kind, name) → dyn path
        self._task: Optional[asyncio.Task] = None
        self._closed = False
        # control-plane blackout tolerance (docs/resilience.md): entries
        # the store stopped vouching for are HELD (stale, purge-deadline)
        # instead of removed — a statestore that restarted empty must not
        # strip every model off the frontend while the workers are alive
        # and mid-rejoin. The disk cache (when enabled) lets a frontend
        # restarted mid-outage cold-start its model list.
        self._cp = control_plane.ControlPlanePolicy.from_env()
        self._cache = control_plane.maybe_cache(self._cp)
        self._cache_dirty = False
        self._raw: Dict[str, bytes] = {}  # key → last raw entry bytes
        self._stale_keys: Dict[str, float] = {}  # key → purge deadline
        self._cp_id = f"models-{uuid.uuid4().hex[:8]}"
        self._purge_task: Optional[asyncio.Task] = None

    @property
    def prefix(self) -> str:
        return f"{self.namespace}/models/"

    def start(self) -> None:
        self._task = asyncio.create_task(self._run())
        self._purge_task = asyncio.create_task(self._purge_loop())

    async def close(self) -> None:
        self._closed = True
        control_plane.state().forget_consumer(self._cp_id)
        for t in (self._task, self._purge_task):
            if t is None:
                continue
            t.cancel()
            try:
                await t
            except asyncio.CancelledError:
                pass
        for key in list(self._entry_model):
            await self._remove(key)

    async def _run(self) -> None:
        backoff = 0.5
        seeded = False
        while not self._closed:
            try:
                watcher = await self.drt.store.watch_prefix(
                    self.prefix, include_existing=True
                )
                backoff = 0.5
                async for ev in watcher:
                    if ev.type == "put":
                        self._mark_fresh(ev.key)
                        await self._add(ev.key, ev.value)
                    elif ev.type == "delete" and ev.resync and (
                        self._cp.stale_serve and ev.key in self._entry_model
                    ):
                        # the (possibly restarted-empty) store no longer
                        # vouches for this entry, but nothing positively
                        # observed its deletion: hold the model as stale
                        self._mark_stale(ev.key)
                    elif ev.type == "delete":
                        await self._remove(ev.key)
            except asyncio.CancelledError:
                raise
            except Exception:
                logger.exception("model watch error; reconnecting")
            if self._closed:
                return
            if not seeded and not self._entry_model:
                # cold start against a DEAD statestore: serve from the disk
                # cache (entries marked stale) while the reconnect loop
                # below keeps dialing; without a cache this keeps retrying —
                # the runtime's create() already failed fast for the
                # no-cache, never-connected case
                seeded = True
                await self._seed_from_cache()
            # watch ended: statestore connection lost. Models stay registered
            # (workers may still be fine) until the fresh snapshot replaces
            # the state; entries absent from it are then held as stale
            # (purged after the grace window) — or removed immediately with
            # stale-serve off (the pre-blackout behavior).
            try:
                try:
                    await self.drt.store.get("__ping__")
                except (ConnectionError, RuntimeError):
                    await self.drt.reconnect_store()
                snapshot = await self.drt.store.get_prefix(self.prefix)
                for key in list(self._entry_model):
                    if key not in snapshot:
                        if self._cp.stale_serve:
                            self._mark_stale(key)
                        else:
                            await self._remove(key)
            except asyncio.CancelledError:
                raise
            except Exception as e:
                logger.warning(
                    "model registry resync failed (%s); retrying in %.1fs",
                    e, backoff,
                )
                await asyncio.sleep(backoff)
                backoff = min(backoff * 2, 10.0)

    # -- stale hold + disk cache (control_plane) ---------------------------

    def _mark_stale(self, key: str) -> None:
        if key not in self._stale_keys:
            self._stale_keys[key] = (
                asyncio.get_running_loop().time() + self._cp.stale_grace
            )
            control_plane.state().note_stale_entries(
                self._cp_id, len(self._stale_keys)
            )
            logger.warning(
                "model entry %s no longer vouched for by the store — "
                "holding it stale for %.0fs", key, self._cp.stale_grace,
            )

    def _mark_fresh(self, key: str) -> None:
        if self._stale_keys.pop(key, None) is not None:
            control_plane.state().note_stale_entries(
                self._cp_id, len(self._stale_keys)
            )

    async def _purge_loop(self) -> None:
        """Drop stale-held entries whose grace expired — but only while the
        store is CONNECTED: with the store down there is no fresh authority
        to justify removing anything (unlike instances, model entries have
        no probe plane of their own; their EndpointClients do)."""
        interval = max(min(self._cp.stale_grace / 4.0, 1.0), 0.05)
        while not self._closed:
            await asyncio.sleep(interval)
            await self._flush_cache()
            if not self._stale_keys:
                continue
            if not getattr(self.drt.store, "connected", True):
                continue
            now = asyncio.get_running_loop().time()
            for key, deadline in list(self._stale_keys.items()):
                if deadline <= now:
                    self._mark_fresh(key)
                    await self._remove(key)

    async def _seed_from_cache(self) -> bool:
        if self._cache is None:
            return False
        try:
            entries = await asyncio.to_thread(self._cache.load, self.prefix)
        except asyncio.CancelledError:
            raise
        except Exception:
            return False
        if not entries:
            return False
        control_plane.state().note_cache_serve()
        for key in sorted(entries):
            await self._add(key, entries[key])
            if key in self._entry_model:
                # only entries _add actually registered are held stale —
                # a cached token-wire entry it declined must not inflate
                # the stale gauge (it would degrade /health until purge)
                self._mark_stale(key)
        logger.warning(
            "cold-started model registry from the discovery cache: "
            "%d entr%s, marked stale until the store confirms them",
            len(entries), "y" if len(entries) == 1 else "ies",
        )
        return bool(self._entry_model)

    async def _flush_cache(self) -> None:
        """Persist the confirmed (non-stale) entry set for cold starts."""
        if self._cache is None or not self._cache_dirty or self._stale_keys:
            return
        self._cache_dirty = False
        entries = dict(self._raw)
        try:
            await asyncio.to_thread(self._cache.save, self.prefix, entries)
        except asyncio.CancelledError:
            raise
        except Exception:
            self._cache_dirty = True
            logger.debug("model cache write failed", exc_info=True)

    def _parse_key(self, key: str) -> Optional[tuple]:
        # {ns}/models/{kind}/{name}[@{instance}] — the instance suffix makes
        # entries per-worker; llmctl writes suffix-less entries. '@' (not ':')
        # so ollama-style model names like "llama3:8b" survive intact.
        tail = key[len(self.prefix):]
        if "/" not in tail:
            return None
        kind, name = tail.split("/", 1)
        name = name.rsplit("@", 1)[0] if "@" in name else name
        return kind, name

    async def _add(self, key: str, value: bytes) -> None:
        parsed = self._parse_key(key)
        if parsed is None:
            return
        kind, name = parsed
        try:
            entry = json.loads(value)
            endpoint_path = entry["endpoint"]
        except (ValueError, KeyError):
            logger.warning("malformed model entry at %s", key)
            return
        # remember the raw entry for the disk discovery cache (cold starts
        # replay exactly what the store last said)
        self._raw[key] = value
        self._cache_dirty = True
        if entry.get("wire", "openai") != "openai":
            # token-wire worker (cli/run --wire token): it speaks
            # PreprocessedRequest dicts, and this frontend has no tokenizer
            # to lower OpenAI requests — feeding it raw dicts would error
            # every request. Serve those fleets with
            # `in=http out=dyn://... --wire token --model-path ...`.
            logger.warning(
                "model %r at %s uses wire=%s; out=discover only routes "
                "openai-wire workers — skipping this entry",
                name, key, entry.get("wire"),
            )
            return
        if key in self._entry_model:
            return  # entry refresh for a model we already serve

        if parsed in self._clients:
            # another worker's entry for an already-served model: refcount it.
            # Traffic flows through the FIRST entry's endpoint path — if this
            # entry points somewhere else, its worker will never see requests
            # for this model name; surface that instead of silently dropping
            # it (endpoint-path divergence was invisible once).
            known = self._endpoint_paths.get(parsed)
            if known is not None and endpoint_path != known:
                logger.warning(
                    "model %s/%s registered at %r by %s, but traffic is "
                    "routed to %r (first registration wins; align the "
                    "endpoint paths or use a distinct model name)",
                    kind, name, endpoint_path, key, known,
                )
            self._entry_model[key] = parsed
            self._model_keys[parsed].add(key)
            return

        from dynamo_tpu.runtime.distributed import parse_endpoint_path

        # a single bad entry must not crash the watch loop (the reconnect
        # path re-delivers existing keys, so a raise here would tear down
        # and re-dial every healthy model's client forever)
        try:
            ns, comp, ep = parse_endpoint_path(endpoint_path)
            client = await (
                self.drt.namespace(ns).component(comp).endpoint(ep).client(
                    self.router_mode, kv_block_size=self.kv_block_size,
                    policy=self.policy,
                )
            )
        except (ValueError, KeyError):
            logger.warning("unusable model entry at %s: %r", key, endpoint_path)
            return
        if kind == "chat":
            self.manager.add_chat_model(name, client)
        elif kind == "completions":
            self.manager.add_completions_model(name, client)
        else:
            logger.warning("unknown model kind %r at %s", kind, key)
            await client.close()
            return
        self._clients[parsed] = client
        self._endpoint_paths[parsed] = endpoint_path
        self._entry_model[key] = parsed
        self._model_keys[parsed] = {key}
        logger.info("model %r (%s) added via %s", name, kind, endpoint_path)

    async def _remove(self, key: str) -> None:
        self._raw.pop(key, None)
        self._cache_dirty = True
        parsed = self._entry_model.pop(key, None)
        if parsed is None:
            return
        keys = self._model_keys.get(parsed)
        if keys is not None:
            keys.discard(key)
            if keys:
                return  # other workers still serve this model
            del self._model_keys[parsed]
        client = self._clients.pop(parsed, None)
        self._endpoint_paths.pop(parsed, None)
        if client is not None:
            try:
                await client.close()
            except asyncio.CancelledError:
                raise
            except Exception:
                logger.debug("closing client for %s failed", key, exc_info=True)
        kind, name = parsed
        if kind == "chat":
            self.manager.remove_chat_model(name)
        elif kind == "completions":
            self.manager.remove_completions_model(name)
        logger.info("model %r (%s) removed", name, kind)
