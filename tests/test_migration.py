"""Live in-flight request migration (ISSUE 13).

A draining worker hands its decode streams — KV pages and all — to healthy
siblings over the disagg transfer plane (docs/resilience.md §Live
migration). Coverage:

- knob clamp tables + the DYN_TPU_MIGRATE=0 zero-overhead guard
  (monkeypatched coordinator constructor: nothing is ever built);
- engine stage/adopt units on REAL tiny engines: bitwise-equal greedy
  continuation with **zero recomputed prefill tokens**, typed rejections
  (target OOM, block-size mismatch, dtype skew) that never tear a page
  set, staged-TTL sweep, unfreeze on undrain;
- the transfer plane's atomic ``migrate`` frame (server+client round trip
  and typed nack);
- client re-home end to end: drain a served worker mid-stream → in-band
  marker → directed attach at the target → byte-equal stream, no resume
  budget consumed;
- failure fallback: a refused transfer degrades the stream to the
  ordinary resume path (recompute, still byte-equal);
- THE chaos gate: 3 real workers rolling-restarted sequentially under 2x
  load → zero client-visible failures, zero recomputed prefill tokens,
  byte-equal streams, each drain completes within the deadline — and the
  resume-only control leg recomputes > 0;
- composition regression (ISSUE 13 satellite): a mid-decode worker cut
  *during* a control-plane blackout — resume picks a sibling from the
  stale-but-safe discovery view with zero client-visible failures;
- ``llmctl worker drain --wait`` exit codes + JSON envelope;
- migration counters worker → aggregator → cluster (promtext-parsed) and
  the edge's ITL-not-TTFT attribution.
"""

import asyncio
import concurrent.futures
import json

import pytest

from dynamo_tpu.disagg import migration as mig_mod
from dynamo_tpu.disagg.migration import MigrationPolicy, attach_migration
from dynamo_tpu.kv import pages as kv_pages
from dynamo_tpu.runtime import faults, resilience
from dynamo_tpu.runtime.annotated import Annotated
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.engine import AsyncEngine, Context
from dynamo_tpu.runtime.faults import FaultInjector, FaultRule
from dynamo_tpu.runtime.resilience import ResiliencePolicy, StreamJournal
from dynamo_tpu.runtime.statestore import StateStoreServer

from .fixtures import engines_held_back

NO_BUS = "127.0.0.1:1"


# -- knobs ---------------------------------------------------------------------


class TestMigrationKnobs:
    def test_from_env_table(self, monkeypatch):
        cases = [
            ({}, MigrationPolicy()),
            ({"DYN_TPU_MIGRATE": "0"}, MigrationPolicy(enabled=False)),
            ({"DYN_TPU_MIGRATE": "off"}, MigrationPolicy(enabled=False)),
            ({"DYN_TPU_MIGRATE": "1"}, MigrationPolicy(enabled=True)),
            # clamps: malformed/non-positive → defaults; out of range → edge
            ({"DYN_TPU_DRAIN_DEADLINE": "junk"}, MigrationPolicy()),
            ({"DYN_TPU_DRAIN_DEADLINE": "-3"}, MigrationPolicy()),
            ({"DYN_TPU_DRAIN_DEADLINE": "0.2"},
             MigrationPolicy(drain_deadline=1.0)),
            ({"DYN_TPU_DRAIN_DEADLINE": "9000"},
             MigrationPolicy(drain_deadline=600.0)),
            ({"DYN_TPU_MIGRATE_TIMEOUT": "0.1"},
             MigrationPolicy(migrate_timeout=0.5)),
            ({"DYN_TPU_MIGRATE_TTL": "7"}, MigrationPolicy(staged_ttl=7.0)),
        ]
        for env, want in cases:
            for k in ("DYN_TPU_MIGRATE", "DYN_TPU_DRAIN_DEADLINE",
                      "DYN_TPU_MIGRATE_TIMEOUT", "DYN_TPU_MIGRATE_TTL"):
                monkeypatch.delenv(k, raising=False)
            for k, v in env.items():
                monkeypatch.setenv(k, v)
            assert MigrationPolicy.from_env() == want, env


# -- zero-overhead guard -------------------------------------------------------


class _Echo(AsyncEngine):
    async def generate(self, request: Context):
        yield Annotated.from_data({"ok": True})


class TestZeroOverheadGuard:
    def test_migrate_off_constructs_nothing(self, run, monkeypatch):
        """DYN_TPU_MIGRATE=0 acceptance: attach_migration returns None and
        no MigrationCoordinator (or transfer server) is ever constructed —
        drain behavior is exactly pre-migration."""
        monkeypatch.setenv("DYN_TPU_MIGRATE", "0")

        def _boom(*a, **kw):
            raise AssertionError("constructed with migration off")

        monkeypatch.setattr(mig_mod, "MigrationCoordinator", _boom)

        async def go():
            ss = StateStoreServer(port=0)
            await ss.start()
            rt = await DistributedRuntime.create(ss.url, NO_BUS)
            ep = rt.namespace("zg").component("w").endpoint("gen")
            await ep.serve(_Echo())
            assert await attach_migration(ep, _Echo()) is None
            assert rt._migrator is None
            # drain still works exactly as before (no migrator hook fires)
            rt.set_draining(True)
            assert rt.draining
            rt.set_draining(False)
            await rt.shutdown()
            await ss.stop()

        run(go())


# -- real tiny engines ---------------------------------------------------------


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
    base = dict(max_slots=2, kv_block_size=8, max_model_len=256)
    base.update(kw)
    return JaxServingEngine(cfg, params, EngineConfig(**base))


def _call(engine, fn, timeout=60):
    """Run fn on the engine thread from the test (sync)."""
    fut = concurrent.futures.Future()

    def wrap():
        try:
            fut.set_result(fn())
        except Exception as e:  # delivered to the caller
            fut.set_exception(e)

    engine.post(wrap)
    return fut.result(timeout=timeout)


def _payload(toks, max_tokens, resume=None, migrate=None):
    p = {
        "token_ids": list(toks),
        "stop_conditions": {"max_tokens": max_tokens, "ignore_eos": True},
        "sampling_options": {"temperature": 0.0},
    }
    if resume is not None:
        p["resume"] = resume
    if migrate is not None:
        p["migrate"] = migrate
    return p


async def _collect(engine, toks, max_tokens, **kw):
    out = []
    async for item in engine.generate(Context(_payload(toks, max_tokens, **kw))):
        if item.is_error:
            raise AssertionError(item.error_message())
        out.extend((item.data or {}).get("token_ids", []))
    return out


async def _freeze_mid_stream(engine, prompt, max_tokens, k):
    """Drive a live stream to ≥k emitted tokens, then freeze+export it.
    Returns (checkpoint, delivered_tokens, generator)."""
    ctx = Context(_payload(prompt, max_tokens))
    gen = engine.generate(ctx)
    got = []
    with engines_held_back():
        async for item in gen:
            got.extend((item.data or {}).get("token_ids", []))
            if len(got) >= k:
                break
        cps = _call(engine, engine.export_migratable)
    assert len(cps) == 1, f"expected 1 migratable stream, got {len(cps)}"
    return cps[0], got, gen


async def _drain_marker(gen):
    """Read the rest of a frozen stream; returns (tokens, marker)."""
    marker = None
    toks = []
    async for item in gen:
        d = item.data or {}
        if "migrating" in d:
            marker = d["migrating"]
            continue
        toks.extend(d.get("token_ids", []))
    return toks, marker


class TestEngineStageAdopt:
    def test_migrated_stream_bitwise_equal_zero_recompute(self, tiny, run):
        """The tentpole at engine level: freeze mid-decode, ship pages,
        stage on a sibling, attach — the continuation is bitwise identical
        to an undisturbed control and recomputes ZERO prefill positions."""

        async def go():
            control = _engine(tiny)
            prompt = list(range(3, 29))  # 26 tokens: full + partial blocks
            golden = await _collect(control, prompt, 14)
            control.close()

            src = _engine(tiny)
            cp, got, gen = await _freeze_mid_stream(src, prompt, 14, 5)
            emitted = cp["token_ids"][len(prompt):]
            assert emitted == golden[:len(emitted)]
            pages, _ = _call(src, lambda: src.extract_for_migration(
                cp["request_id"]
            ))

            tgt = _engine(tiny)
            meta = {k: cp[k] for k in
                    ("mid", "request_id", "token_ids", "emitted", "tenant",
                     "level")}
            staged = _call(tgt, lambda: tgt.stage_migration(
                meta, pages,
            ))
            assert staged["cached_tokens"] == len(cp["token_ids"]) - 1
            _call(src, lambda: src.finish_migrated(
                cp["request_id"], "tgt-iid", "tgt-wid", cp["mid"]
            ))
            rest, marker = await _drain_marker(gen)
            assert marker is not None and marker["mid"] == cp["mid"]
            assert marker["instance"] == "tgt-iid"
            # source freed its pages and counted the migrate-out
            assert src.migrated_out_requests == 1
            assert src.live_request_count() == 0

            out = await _collect(
                tgt, cp["token_ids"], 14 - len(emitted),
                resume={"prompt_len": len(prompt),
                        "rng_offset": len(emitted)},
                migrate=cp["mid"],
            )
            assert emitted + out == golden, "migrated stream diverged"
            snap = tgt.metrics_snapshot()
            assert snap["migrated_in_requests"] == 1
            assert snap["resume_recompute_tokens"] == 0, (
                "a migrated admission must recompute NOTHING"
            )
            assert snap["migrate_staged"] == 0  # consumed by the attach
            src.close()
            tgt.close()

        run(go())

    def test_penalized_migration_continues_counts(self, tiny, run):
        """Penalty state continues exactly: the resume marker's out_tokens
        rebuild rides the same machinery, with the staged KV underneath."""

        async def go():
            control = _engine(tiny)
            prompt = list(range(5, 31))
            golden = []
            req = _payload(prompt, 12)
            req["sampling_options"]["frequency_penalty"] = 1.1
            req["sampling_options"]["presence_penalty"] = 0.5
            async for item in control.generate(Context(dict(req))):
                golden.extend((item.data or {}).get("token_ids", []))
            control.close()

            src = _engine(tiny)
            ctx = Context(dict(req))
            gen = src.generate(ctx)
            got = []
            with engines_held_back():
                async for item in gen:
                    got.extend((item.data or {}).get("token_ids", []))
                    if len(got) >= 4:
                        break
                cp = _call(src, src.export_migratable)[0]
            emitted = cp["token_ids"][len(prompt):]
            pages, _ = _call(src, lambda: src.extract_for_migration(
                cp["request_id"]
            ))
            tgt = _engine(tiny)
            _call(tgt, lambda: tgt.stage_migration(
                {k: cp[k] for k in ("mid", "request_id", "token_ids",
                                    "emitted", "tenant", "level")},
                pages,
            ))
            _call(src, lambda: src.finish_migrated(
                cp["request_id"], "i", "w", cp["mid"]
            ))
            await _drain_marker(gen)

            attach = _payload(
                cp["token_ids"], 12 - len(emitted),
                resume={"prompt_len": len(prompt),
                        "rng_offset": len(emitted)},
                migrate=cp["mid"],
            )
            attach["sampling_options"]["frequency_penalty"] = 1.1
            attach["sampling_options"]["presence_penalty"] = 0.5
            out = []
            async for item in tgt.generate(Context(attach)):
                out.extend((item.data or {}).get("token_ids", []))
            assert emitted + out == golden
            assert tgt.metrics_snapshot()["resume_recompute_tokens"] == 0
            src.close()
            tgt.close()

        run(go())

    def test_stage_rejections_are_typed_and_atomic(self, tiny, run):
        """Target OOM, page-set/block-size mismatch, dtype skew: every
        rejection is typed and leaves the target pool untouched — never a
        torn page set."""
        from dynamo_tpu.kv.pages import KvDtypeMismatch, MigrationRejected

        async def go():
            src = _engine(tiny)
            prompt = list(range(7, 27))
            cp, got, gen = await _freeze_mid_stream(src, prompt, 10, 3)
            pages, _ = _call(src, lambda: src.extract_for_migration(
                cp["request_id"]
            ))
            meta = {k: cp[k] for k in ("mid", "request_id", "token_ids",
                                       "emitted", "tenant", "level")}

            # target OOM: a pool too small for the history
            oom = _engine(tiny, num_kv_blocks=2)
            free0 = oom.allocator.free_blocks
            with pytest.raises(MigrationRejected):
                _call(oom, lambda: oom.stage_migration(
                    meta, pages,
                ))
            assert oom.allocator.free_blocks == free0, "torn OOM stage"
            oom.close()

            # block-size mismatch
            bs = _engine(tiny, kv_block_size=16)
            with pytest.raises(MigrationRejected):
                _call(bs, lambda: bs.stage_migration(
                    meta, pages,
                ))
            bs.close()

            # page-count mismatch (truncated page set = torn frame)
            tr = _engine(tiny)
            with pytest.raises(MigrationRejected):
                _call(tr, lambda: tr.stage_migration(
                    meta, kv_pages.select(pages, slice(0, 1))
                ))
            tr.close()

            # dtype skew: native pages into an int8 pool
            q = _engine(tiny, kv_dtype="int8")
            with pytest.raises(KvDtypeMismatch):
                _call(q, lambda: q.stage_migration(
                    meta, {m: pages[m] for m in ("k", "v")}
                ))
            q.close()

            # history too short
            ok = _engine(tiny)
            with pytest.raises(MigrationRejected):
                _call(ok, lambda: ok.stage_migration(
                    dict(meta, token_ids=[1]), pages,
                ))
            ok.close()

            _call(src, lambda: src.abort_migration(cp["request_id"], "test"))
            toks, marker = await _drain_marker(gen)
            assert marker is not None and marker.get("resume") is True
            assert src.migrations_failed == 1
            src.close()

        run(go())

    def test_staged_ttl_sweep_frees_blocks(self, tiny, run, monkeypatch):
        monkeypatch.setenv("DYN_TPU_MIGRATE_TTL", "1")

        async def go():
            src = _engine(tiny)
            prompt = list(range(11, 31))
            cp, got, gen = await _freeze_mid_stream(src, prompt, 10, 3)
            pages, _ = _call(src, lambda: src.extract_for_migration(
                cp["request_id"]
            ))
            tgt = _engine(tiny)
            free0 = tgt.allocator.free_blocks
            _call(tgt, lambda: tgt.stage_migration(
                {k: cp[k] for k in ("mid", "request_id", "token_ids",
                                    "emitted", "tenant", "level")},
                pages,
            ))
            assert len(tgt._staged_migrations) == 1
            deadline = asyncio.get_running_loop().time() + 8.0
            while (tgt._staged_migrations
                   and asyncio.get_running_loop().time() < deadline):
                await asyncio.sleep(0.2)
            assert not tgt._staged_migrations, "staged entry never expired"
            # staged blocks returned to the pool (cached/reusable count as
            # free); the attach now misses and recomputes (still correct)
            assert tgt.allocator.free_blocks == free0
            _call(src, lambda: src.abort_migration(cp["request_id"]))
            await _drain_marker(gen)
            emitted = cp["token_ids"][len(prompt):]
            golden = await _goldens(tiny, [prompt], 10)
            out = await _collect(
                tgt, cp["token_ids"], 10 - len(emitted),
                resume={"prompt_len": len(prompt),
                        "rng_offset": len(emitted)},
                migrate=cp["mid"],  # expired: falls through to recompute
            )
            assert emitted + out == golden[0]
            snap = tgt.metrics_snapshot()
            assert snap["migrated_in_requests"] == 0
            # even an EXPIRED stage keeps paying: its sealed blocks stayed
            # in the prefix cache, so the recompute covers only the
            # non-block-aligned tail of the history (0 when N-1 is a block
            # multiple)
            n = len(cp["token_ids"])
            bs = tgt.config.kv_block_size
            assert snap["resume_recompute_tokens"] == (
                (n - 1) - ((n - 1) // bs) * bs
            )
            src.close()
            tgt.close()

        run(go())

    def test_unfreeze_resumes_locally_byte_equal(self, tiny):
        """An undrain mid-migration un-freezes the stream: it re-enters the
        decode batch where it stopped and finishes byte-equal locally.

        Driven one host step at a time on the test's own thread, as
        tests/test_chunk_rows.py drives it (no engine thread): since a chunk
        and a decode dispatch share a host step, a 12-token stream on the
        engine's thread can end before a test on another posts its freeze."""
        from .step_programs import answer, run_out, step, submit

        prompt = list(range(13, 33))
        control = _engine(tiny)
        whole = submit(control, prompt, 12, temperature=0.0)
        run_out(control)
        golden = answer(whole)[0]
        control.close()
        assert len(golden) == 12

        eng = _engine(tiny)
        seq = submit(eng, prompt, 12, temperature=0.0)
        got = []
        while len(got) < 4:
            step(eng)
            got += answer(seq)[0]
        cps = eng.export_migratable()  # commits what is in flight, then freezes
        got += answer(seq)[0]
        assert len(cps) == 1 and len(got) < 12
        step(eng)  # frozen: it holds its pages and decodes nothing
        assert answer(seq)[0] == []
        assert eng.unfreeze_migrations() == 1
        run_out(eng)
        assert got + answer(seq)[0] == golden
        eng.close()

    def test_cut_for_resume_emits_directives(self, tiny, run):
        async def go():
            eng = _engine(tiny)
            ctx = Context(_payload(list(range(3, 19)), 20))
            gen = eng.generate(ctx)
            got = []
            async for item in gen:
                got.extend((item.data or {}).get("token_ids", []))
                if len(got) >= 2:
                    break
            assert _call(eng, eng.cut_for_resume) == 1
            toks, marker = await _drain_marker(gen)
            assert marker is not None and marker.get("resume") is True
            assert eng.live_request_count() == 0
            eng.close()

        run(go())


# -- transfer plane ------------------------------------------------------------


class TestTransferMigrateOp:
    def test_migrate_frame_round_trip_and_nack(self, tiny, run):
        from dynamo_tpu.disagg.transfer import (
            KvTransferClient,
            KvTransferServer,
        )
        from dynamo_tpu.kv.pages import MigrationRejected

        async def go():
            control = _engine(tiny)
            prompt = list(range(17, 43))
            golden = await _collect(control, prompt, 10)
            control.close()

            src = _engine(tiny)
            cp, got, gen = await _freeze_mid_stream(src, prompt, 10, 4)
            emitted = cp["token_ids"][len(prompt):]
            pages, _ = _call(src, lambda: src.extract_for_migration(
                cp["request_id"]
            ))
            tgt = _engine(tiny)
            server = KvTransferServer(tgt, host="127.0.0.1", port=0)
            await server.start()
            client = KvTransferClient()
            addr = f"127.0.0.1:{server.port}"
            meta = {k: cp[k] for k in ("mid", "request_id", "token_ids",
                                       "emitted", "tenant", "level")}
            staged = await client.migrate(
                addr, meta, pages,
            )
            assert staged["cached_tokens"] == len(cp["token_ids"]) - 1
            assert len(tgt._staged_migrations) == 1

            # typed nack: malformed checkpoint never tears the stream or
            # the connection (the same conn carries the next frame fine)
            with pytest.raises(MigrationRejected):
                await client.migrate(
                    addr, dict(meta, mid="bad", token_ids=[1]),
                    pages,
                )
            assert len(tgt._staged_migrations) == 1  # only the good one

            _call(src, lambda: src.finish_migrated(
                cp["request_id"], "i", "w", cp["mid"]
            ))
            await _drain_marker(gen)
            out = await _collect(
                tgt, cp["token_ids"], 10 - len(emitted),
                resume={"prompt_len": len(prompt),
                        "rng_offset": len(emitted)},
                migrate=cp["mid"],
            )
            assert emitted + out == golden
            assert tgt.metrics_snapshot()["resume_recompute_tokens"] == 0
            await client.close()
            await server.stop()
            src.close()
            tgt.close()

        run(go())

    def test_quarantine_latch_mid_migration_aborts_ship(self, tiny, run):
        """Composition regression (ISSUE 19 satellite, first surfaced by
        the chaos matrix's quarantine×drain pairing): a quarantine latch
        landing while a migration is in flight must abort the ship TO the
        quarantined target with a typed error — adopting a stream into a
        suspect KV pool would hand corrupt pages a clean lineage. The
        check is receiver-side because the source's routing snapshot can
        be a beat stale; clearing the latch restores service on the SAME
        connection (no teardown)."""
        from dynamo_tpu.disagg.transfer import (
            KvTransferClient,
            KvTransferServer,
        )
        from dynamo_tpu.kv.pages import MigrationRejected
        from dynamo_tpu.runtime import integrity

        async def go():
            src = _engine(tiny)
            prompt = list(range(17, 43))
            cp, got, gen = await _freeze_mid_stream(src, prompt, 10, 4)
            pages, _ = _call(src, lambda: src.extract_for_migration(
                cp["request_id"]
            ))
            tgt = _engine(tiny)
            server = KvTransferServer(tgt, host="127.0.0.1", port=0)
            await server.start()
            client = KvTransferClient()
            addr = f"127.0.0.1:{server.port}"
            meta = {k: cp[k] for k in ("mid", "request_id", "token_ids",
                                       "emitted", "tenant", "level")}

            # the latch lands between freeze and ship — the in-flight
            # migration must die with the typed rejection, not stage
            integrity.tracker().quarantine(
                source="store", reason="operator order mid-migration"
            )
            with pytest.raises(MigrationRejected, match="quarantined"):
                await client.migrate(addr, meta, pages)
            assert len(tgt._staged_migrations) == 0

            # unquarantine: the SAME client connection ships it clean
            integrity.clear_quarantine(None)
            staged = await client.migrate(
                addr, meta, pages
            )
            assert staged["cached_tokens"] == len(cp["token_ids"]) - 1
            assert len(tgt._staged_migrations) == 1

            _call(src, src.cut_for_resume)
            await gen.aclose()
            await client.close()
            await server.stop()
            src.close()
            tgt.close()

        run(go())


# -- client re-home over real served workers -----------------------------------


def _policy(**kw) -> ResiliencePolicy:
    base = dict(
        request_timeout=120.0,
        connect_timeout=2.0,
        max_attempts=4,
        backoff_base=0.01,
        backoff_max=0.05,
        breaker_threshold=2,
        breaker_cooldown=30.0,
        resume_attempts=1,
        seed=7,
    )
    base.update(kw)
    return ResiliencePolicy(**base)


async def _mig_cluster(tiny, n=2, policy=None, migrate=True, **ekw):
    ss = StateStoreServer(port=0)
    await ss.start()
    rts, engines, coords = [], [], []
    for _ in range(n):
        rt = await DistributedRuntime.create(ss.url, NO_BUS)
        eng = _engine(tiny, **ekw)
        ep = rt.namespace("mig").component("w").endpoint("gen")
        await ep.serve(eng)
        coords.append(await attach_migration(ep, eng) if migrate else None)
        rts.append(rt)
        engines.append(eng)
    fe = await DistributedRuntime.create(ss.url, NO_BUS)
    client = await fe.namespace("mig").component("w").endpoint("gen").client(
        "round_robin", policy=policy or _policy()
    )
    await client.wait_for_instances(n, timeout=10)
    return ss, rts, engines, coords, fe, client


async def _teardown(ss, rts, engines, fe, client):
    await client.close()
    for rt in rts + [fe]:
        await rt.shutdown()
    for eng in engines:
        eng.close()
    await ss.stop()


async def _stream(client, prompt, max_tokens):
    ctx = Context(_payload(prompt, max_tokens))
    toks, errs = [], []
    async for item in client.generate(ctx):
        if item.is_error:
            errs.append(item.error_message())
        elif isinstance(item.data, dict):
            toks.extend(item.data.get("token_ids", []))
    return toks, errs, ctx


async def _goldens(tiny, prompts, max_tokens):
    eng = _engine(tiny, max_slots=4)
    out = []
    for p in prompts:
        out.append(await _collect(eng, p, max_tokens))
    eng.close()
    return out


def _victim_of(rts, engines):
    """Index of a worker actually holding live streams."""
    for i, eng in enumerate(engines):
        if eng.live_request_count():
            return i
    return 0


async def _wait_drained(rts, engines, i, timeout=30.0):
    t0 = asyncio.get_running_loop().time()
    while engines[i].live_request_count():
        if asyncio.get_running_loop().time() - t0 > timeout:
            raise AssertionError(
                f"worker {i} still has {engines[i].live_request_count()} "
                f"live streams after {timeout}s of drain"
            )
        await asyncio.sleep(0.05)
    return asyncio.get_running_loop().time() - t0


class TestClientReHome:
    def test_drain_migrates_stream_byte_equal(self, tiny, run):
        """End to end over real planes: drain the serving worker mid-stream
        → in-band marker → the client attaches at the target where the
        staged KV makes the re-admission recompute-free; no resume budget
        is consumed."""

        async def go():
            mig_mod.reset_migration_counters()
            ss, rts, engines, coords, fe, client = await _mig_cluster(tiny)
            [golden] = await _goldens(tiny, [list(range(3, 27))], 24)

            task = asyncio.create_task(
                _stream(client, list(range(3, 27)), 24)
            )
            # a few tokens in, drain whichever worker holds the stream
            while not any(e.live_request_count() for e in engines):
                await asyncio.sleep(0.02)
            await asyncio.sleep(0.25)
            victim = _victim_of(rts, engines)
            rts[victim].set_draining(True)
            toks, errs, ctx = await asyncio.wait_for(task, 60)
            assert errs == []
            assert toks == golden, "migrated stream diverged"
            j = ctx.context.journal
            assert j is not None and j.migrations == 1 and j.resumes == 0
            assert client.stats["migrations"] == 1
            assert client.stats["migration_resumes"] == 0
            assert client.stats["resumes"] == 0
            # zero recompute on the target; counters flowed
            other = 1 - victim
            snap = engines[other].metrics_snapshot()
            assert snap["migrated_in_requests"] == 1
            assert snap["resume_recompute_tokens"] == 0
            m_ok, m_bad, m_blocks = mig_mod.migration_counters()
            assert m_ok == 1 and m_bad == 0 and m_blocks > 0
            assert coords[victim].last_drain.get("migrated") == 1
            await _wait_drained(rts, engines, victim, timeout=10)
            await _teardown(ss, rts, engines, fe, client)

        run(go())

    def test_transfer_failure_degrades_to_resume(self, tiny, run):
        """Any migration failure (here: the target's transfer dial refused)
        degrades that stream to the ordinary resume path — recompute on a
        sibling, still byte-equal, typed all the way."""

        async def go():
            mig_mod.reset_migration_counters()
            ss, rts, engines, coords, fe, client = await _mig_cluster(tiny)
            [golden] = await _goldens(tiny, [list(range(5, 29))], 24)

            inj = FaultInjector([FaultRule(
                plane="transfer", point="connect", action="refuse",
            )])
            with faults.active(inj):
                task = asyncio.create_task(
                    _stream(client, list(range(5, 29)), 24)
                )
                while not any(e.live_request_count() for e in engines):
                    await asyncio.sleep(0.02)
                await asyncio.sleep(0.25)
                victim = _victim_of(rts, engines)
                rts[victim].set_draining(True)
                toks, errs, ctx = await asyncio.wait_for(task, 60)
            assert errs == []
            assert toks == golden
            j = ctx.context.journal
            # the drain directive degraded to resume — planned, so it rides
            # journal.migrations (no failure-resume budget consumed)
            assert j is not None and j.migrations == 1 and j.resumes == 0
            assert client.stats["migration_resumes"] == 1
            assert client.stats["migrations"] == 0
            other = 1 - victim
            assert (
                engines[other].metrics_snapshot()["resume_recompute_tokens"]
                > 0
            ), "the fallback leg must recompute (that's what migration saves)"
            m_ok, m_bad, _ = mig_mod.migration_counters()
            assert m_bad >= 1
            assert engines[victim].migrations_failed >= 1
            await _teardown(ss, rts, engines, fe, client)

        run(go())

    def test_migrate_stall_fault_times_out_to_resume(self, tiny, run,
                                                     monkeypatch):
        """The migrate_stall fault action: the coordinator's per-stream
        timeout fires and the stream degrades to resume."""
        monkeypatch.setenv("DYN_TPU_MIGRATE_TIMEOUT", "0.5")

        async def go():
            ss, rts, engines, coords, fe, client = await _mig_cluster(tiny)
            [golden] = await _goldens(tiny, [list(range(9, 33))], 24)
            inj = FaultInjector([FaultRule(
                plane="transfer", point="migrate", action="migrate_stall",
            )])
            with faults.active(inj):
                task = asyncio.create_task(
                    _stream(client, list(range(9, 33)), 24)
                )
                while not any(e.live_request_count() for e in engines):
                    await asyncio.sleep(0.02)
                await asyncio.sleep(0.25)
                victim = _victim_of(rts, engines)
                rts[victim].set_draining(True)
                toks, errs, _ = await asyncio.wait_for(task, 60)
            assert errs == []
            assert toks == golden
            assert client.stats["migration_resumes"] == 1
            await _teardown(ss, rts, engines, fe, client)

        run(go())


class TestCorruptDuringDrain:
    def test_corrupt_pages_mid_drain_degrade_to_resume_untorn(
        self, tiny, run, monkeypatch
    ):
        """ISSUE 14 satellite: the ``corrupt`` fault fired DURING a PR12
        drain — the in-flight migration must abort with the typed
        KvIntegrityError, degrade to resume, stay byte-equal, and leave NO
        torn staged entry on the target (its pool is untouched)."""
        from dynamo_tpu.runtime import integrity

        # keep the quarantine latch out of this focused regression: the
        # trip threshold is a separate concern (tests/test_integrity.py)
        monkeypatch.setenv("DYN_TPU_INTEGRITY_TRIPS", "1000")

        async def go():
            integrity.reset_for_tests()
            mig_mod.reset_migration_counters()
            ss, rts, engines, coords, fe, client = await _mig_cluster(tiny)
            [golden] = await _goldens(tiny, [list(range(6, 30))], 24)
            target_free = {
                i: engines[i].allocator.free_blocks for i in range(2)
            }
            inj = FaultInjector([FaultRule(
                plane="transfer", point="pages", action="corrupt",
            )])
            with faults.active(inj):
                task = asyncio.create_task(
                    _stream(client, list(range(6, 30)), 24)
                )
                while not any(e.live_request_count() for e in engines):
                    await asyncio.sleep(0.02)
                await asyncio.sleep(0.25)
                victim = _victim_of(rts, engines)
                rts[victim].set_draining(True)
                toks, errs, ctx = await asyncio.wait_for(task, 60)
            assert errs == []
            assert toks == golden, "corrupt bytes reached the client"
            # planned degradation: rides journal.migrations, typed all the way
            j = ctx.context.journal
            assert j is not None and j.migrations == 1 and j.resumes == 0
            assert client.stats["migration_resumes"] == 1
            assert client.stats["migrations"] == 0
            m_ok, m_bad, _ = mig_mod.migration_counters()
            assert m_ok == 0 and m_bad >= 1
            # the SOURCE counted the trip against itself (nack teaches it)
            assert integrity.counters()["kv_integrity_failures_total"] >= 1
            # no torn staged entry: the target staged nothing, its pool is
            # exactly where it started once the stream finished
            other = 1 - victim
            snap = engines[other].metrics_snapshot()
            assert snap["migrate_staged"] == 0
            assert snap["migrated_in_requests"] == 0
            await _wait_drained(rts, engines, victim, timeout=10)
            deadline = asyncio.get_running_loop().time() + 10.0
            while (engines[other].live_request_count()
                   and asyncio.get_running_loop().time() < deadline):
                await asyncio.sleep(0.05)
            # nothing left hard-held: no leaked staged allocation anywhere
            assert engines[other].allocator.active_blocks == 0
            assert target_free[other] > 0  # sanity: the pool existed
            await _teardown(ss, rts, engines, fe, client)
            integrity.reset_for_tests()

        run(go())


# -- THE chaos gate ------------------------------------------------------------


class TestChaosGate:
    def test_rolling_restart_all_workers_under_2x_load(self, tiny, run):
        """ISSUE 13 acceptance: 3 real workers, 12 concurrent streams (2x
        the fleet's 6 decode slots), all 3 workers drained+restarted
        sequentially. Zero client-visible failures, zero recomputed
        prefill tokens on migrated streams, every stream byte-equal to an
        undisturbed control, every drain completes within the deadline."""

        async def go():
            mig_mod.reset_migration_counters()
            resilience.reset_resume_counters()
            ss, rts, engines, coords, fe, client = await _mig_cluster(
                tiny, n=3, max_slots=2,
                policy=_policy(resume_attempts=2),
            )
            n_requests, max_t = 12, 64
            prompts = [[17 + i, 23 + 2 * i, 5 + 3 * i] for i in
                       range(n_requests)]
            controls = await _goldens(tiny, prompts, max_t)

            results = [None] * n_requests

            async def one(i):
                results[i] = await _stream(client, prompts[i], max_t)

            tasks = [asyncio.create_task(one(i)) for i in range(n_requests)]
            while sum(e.live_request_count() for e in engines) < 6:
                await asyncio.sleep(0.02)
            await asyncio.sleep(0.2)

            ns = "mig"
            drain_walls = []
            for i in range(3):
                if all(r is not None for r in results):
                    break  # load finished early; restarts below still ran
                rts[i].set_draining(True)
                drain_walls.append(
                    await _wait_drained(rts, engines, i, timeout=30.0)
                )
                await rts[i].shutdown()  # lease revoked: instance drops
                # "restart": a fresh runtime serving the same engine (a
                # fresh process in production; the engine object is reused
                # here to keep the gate inside the CI compile budget —
                # migration correctness never depends on the replacement's
                # cache state)
                rt2 = await DistributedRuntime.create(ss.url, NO_BUS)
                ep2 = rt2.namespace(ns).component("w").endpoint("gen")
                info2 = await ep2.serve(engines[i])
                coords[i] = await attach_migration(ep2, engines[i])
                rts[i] = rt2
                # converge the CLIENT's view before the next drain: the
                # fresh instance discovered AND the dead one's key gone
                deadline = asyncio.get_running_loop().time() + 10.0
                while asyncio.get_running_loop().time() < deadline:
                    ids = client.instance_ids()
                    if info2.instance_id in ids and len(ids) == 3:
                        break
                    await asyncio.sleep(0.05)

            await asyncio.wait_for(asyncio.gather(*tasks), 120)

            failures = [
                (i, errs) for i, (toks, errs, _) in enumerate(results)
                if errs
            ]
            assert failures == [], f"client-visible failures: {failures}"
            for i, (toks, errs, _) in enumerate(results):
                assert toks == controls[i], (
                    f"stream {i} diverged after migration "
                    f"(got {len(toks)}/{len(controls[i])} tokens)"
                )
            # streams were actually migrated — and with ZERO recompute:
            # every re-home attached to staged KV (no resume fallbacks, no
            # failure-resumes, no recomputed positions anywhere)
            assert client.stats["migrations"] >= 1, "nothing migrated"
            assert client.stats["migration_resumes"] == 0
            assert client.stats["resumes"] == 0
            recompute = sum(
                e.metrics_snapshot()["resume_recompute_tokens"]
                for e in engines
            )
            assert recompute == 0, (
                f"migrated streams recomputed {recompute} prefill tokens"
            )
            m_ok, m_bad, m_blocks = mig_mod.migration_counters()
            assert m_ok == client.stats["migrations"] and m_bad == 0
            assert m_blocks > 0
            # each drain beat the (default 30s) deadline by construction of
            # _wait_drained; record that they were all prompt
            assert all(w < 30.0 for w in drain_walls), drain_walls
            await _teardown(ss, rts, engines, fe, client)

        run(go())

    def test_resume_only_control_leg_recomputes(self, tiny, run):
        """The control leg the tentpole is measured against: the same
        mid-decode break handled by the PR10 resume path (a deterministic
        `cut` = worker death after the 6th item, no migration involved) —
        streams still finish byte-equal, but the sibling recomputes the
        whole history. That recompute is exactly what the migrate leg's
        zero proves away."""

        async def go():
            resilience.reset_resume_counters()
            ss, rts, engines, coords, fe, client = await _mig_cluster(
                tiny, n=3, max_slots=2, migrate=False,
                policy=_policy(resume_attempts=2),
            )
            n_requests, max_t = 6, 48
            prompts = [[19 + i, 29 + 2 * i, 7 + 3 * i] for i in
                       range(n_requests)]
            controls = await _goldens(tiny, prompts, max_t)
            results = [None] * n_requests

            async def one(i):
                results[i] = await _stream(client, prompts[i], max_t)

            inj = FaultInjector([FaultRule(
                plane="rpc", point="item", action="cut", after_ops=6,
                max_fires=1,
            )])
            with faults.active(inj):
                tasks = [asyncio.create_task(one(i))
                         for i in range(n_requests)]
                await asyncio.wait_for(asyncio.gather(*tasks), 120)
            failures = [(i, errs) for i, (t, errs, _) in enumerate(results)
                        if errs]
            assert failures == [], failures
            for i, (toks, _, _) in enumerate(results):
                assert toks == controls[i]
            assert client.stats["resumes"] >= 1
            recompute = sum(
                e.metrics_snapshot()["resume_recompute_tokens"]
                for e in engines
            )
            assert recompute > 0, (
                "the resume control leg is supposed to recompute — "
                "otherwise the migration gate proves nothing"
            )
            await _teardown(ss, rts, engines, fe, client)

        run(go())


# -- composition regression: cut DURING a control-plane blackout ---------------


class TestBlackoutCutComposition:
    def test_cut_during_blackout_resumes_from_stale_view(self, run):
        """ISSUE 13 satellite: the PR10 `cut` fault fired WHILE the PR11
        control-plane blackout is in progress. The resume dispatch must
        pick a sibling from the stale-but-safe discovery view (the store
        can vouch for nothing) with zero client-visible failures and
        byte-equal streams — the two chaos modes composed, which neither
        gate previously exercised together."""
        from .token_engine import TokenEngine, expected_stream

        async def go():
            resilience.reset_resume_counters()
            ss = StateStoreServer(port=0)
            await ss.start()
            rts = []
            for i in range(3):
                rt = await DistributedRuntime.create(ss.url, NO_BUS)
                ep = rt.namespace("bc").component("w").endpoint("gen")
                await ep.serve(TokenEngine(f"w{i}", delay=0.02))
                rts.append(rt)
            from dynamo_tpu.runtime.health import HealthPolicy

            fe = await DistributedRuntime.create(ss.url, NO_BUS)
            # fast probe cadence: the probe tick is what marks instances
            # stale while the store connection is down — the cut must land
            # while streams are still live
            client = await fe.namespace("bc").component("w").endpoint(
                "gen"
            ).client(
                "round_robin", policy=_policy(resume_attempts=2),
                health_policy=HealthPolicy(probe_idle=0.3),
            )
            await client.wait_for_instances(3, timeout=10)

            n_requests, max_t = 6, 120
            prompts = [[41 + i, 53 + 2 * i] for i in range(n_requests)]
            controls = [expected_stream(p, max_t) for p in prompts]
            results = [None] * n_requests

            async def one(i):
                ctx = Context({
                    "token_ids": prompts[i],
                    "stop_conditions": {"max_tokens": max_t},
                    "sampling_options": {"temperature": 0.0},
                })
                toks, errs = [], []
                async for item in client.generate(ctx):
                    if item.is_error:
                        errs.append(item.error_message())
                    elif isinstance(item.data, dict):
                        toks.extend(item.data.get("token_ids", []))
                results[i] = (toks, errs)

            inj = FaultInjector([])
            with faults.active(inj):
                tasks = [asyncio.create_task(one(i))
                         for i in range(n_requests)]
                await asyncio.sleep(0.2)  # streams mid-decode
                # phase 1: the control plane dies (statestore refused +
                # live conns reset) — discovery freezes stale-but-safe
                inj.begin_blackout()

                # a parked watch read only notices the outage on its next
                # op: nudge the frontend's store conn the way production
                # traffic (keepalives, load reports) would. Fire-and-forget:
                # the client's transparent retry PARKS the call for its
                # whole reconnect window — the write's injected reset (which
                # breaks the shared conn and ends the watch) happens
                # immediately regardless.
                async def _nudge():
                    try:
                        await fe.store.get("__ping__")
                    except Exception:
                        pass

                nudge = asyncio.create_task(_nudge())
                deadline = asyncio.get_running_loop().time() + 10.0
                while (not client._stale
                       and asyncio.get_running_loop().time() < deadline):
                    await asyncio.sleep(0.05)
                assert client._stale, (
                    "client never entered stale-serve under the blackout"
                )
                # phase 2: a worker dies mid-decode DURING the blackout
                inj.add_rule(FaultRule(
                    plane="rpc", point="item", action="cut", max_fires=1,
                ))
                await asyncio.wait_for(asyncio.gather(*tasks), 60)
                nudge.cancel()

            failures = [(i, errs) for i, (t, errs) in enumerate(results)
                        if errs]
            assert failures == [], f"client-visible failures: {failures}"
            for i, (toks, _) in enumerate(results):
                assert toks == controls[i], f"stream {i} diverged"
            assert client.stats["resumes"] >= 1, (
                "the cut never forced a resume"
            )
            assert client.stats["resume_failures"] == 0
            await client.close()
            for rt in rts + [fe]:
                await rt.shutdown()
            await ss.stop()

        run(go())


# -- llmctl worker drain --wait ------------------------------------------------


class TestLlmctlDrainWait:
    def test_wait_exit_codes_and_json(self, run, monkeypatch, capsys):
        from .token_engine import TokenEngine

        from dynamo_tpu.cli import llmctl

        monkeypatch.setenv("DYN_TPU_LOAD_REPORT_INTERVAL", "0.1")

        async def go():
            ss = StateStoreServer(port=0)
            await ss.start()
            rt = await DistributedRuntime.create(ss.url, NO_BUS)
            ep = rt.namespace("dw").component("w").endpoint("gen")
            await ep.serve(TokenEngine("w0", delay=0.05))
            fe = await DistributedRuntime.create(ss.url, NO_BUS)
            client = await fe.namespace("dw").component("w").endpoint(
                "gen"
            ).client("round_robin", policy=_policy())
            await client.wait_for_instances(1, timeout=10)

            # a long stream keeps the worker busy through the first --wait
            ctx = Context({
                "token_ids": [3, 5],
                "stop_conditions": {"max_tokens": 60},
                "sampling_options": {"temperature": 0.0},
            })

            async def consume():
                async for item in client.generate(ctx):
                    assert not item.is_error, item.error_message()

            task = asyncio.create_task(consume())
            await asyncio.sleep(0.3)
            capsys.readouterr()
            rc = await llmctl.amain([
                "--statestore", ss.url, "worker", "drain",
                "dyn://dw.w.gen", rt.worker_id,
                "--wait", "--timeout", "0.5", "--json",
            ])
            out = capsys.readouterr().out
            assert rc == 2, out  # still busy at the deadline
            env = json.loads(out)
            assert env["drained"] is False
            assert env["instances"] and not env["instances"][0]["idle"]
            assert rt.draining  # the key DID land and the worker drained

            # once the in-flight stream finishes, --wait succeeds
            await asyncio.wait_for(task, 30)
            rc = await llmctl.amain([
                "--statestore", ss.url, "worker", "drain",
                "dyn://dw.w.gen", rt.worker_id,
                "--wait", "--timeout", "20", "--json",
            ])
            out = capsys.readouterr().out
            assert rc == 0, out
            env = json.loads(out)
            assert env["drained"] is True
            assert all(r["idle"] for r in env["instances"])

            # undrain still round-trips
            rc = await llmctl.amain([
                "--statestore", ss.url, "worker", "undrain",
                "dyn://dw.w.gen", rt.worker_id,
            ])
            assert rc == 0
            deadline = asyncio.get_running_loop().time() + 5.0
            while (rt.draining
                   and asyncio.get_running_loop().time() < deadline):
                await asyncio.sleep(0.05)
            assert not rt.draining

            await client.close()
            await rt.shutdown()
            await fe.shutdown()
            await ss.stop()

        run(go())


# -- gauges through the metrics planes -----------------------------------------


class TestMigrationGauges:
    def test_forward_pass_metrics_round_trip(self):
        from dynamo_tpu.kv_router.protocols import ForwardPassMetrics

        m = ForwardPassMetrics(
            migrations_total=4, migrations_failed_total=1,
            migrate_kv_blocks_moved_total=32,
        )
        d = m.to_dict()
        back = ForwardPassMetrics.from_dict(d)
        assert back.migrations_total == 4
        assert back.migrations_failed_total == 1
        assert back.migrate_kv_blocks_moved_total == 32
        # pre-migration wire dicts still parse (fields default 0)
        old = {k: v for k, v in d.items() if "migrat" not in k}
        assert ForwardPassMetrics.from_dict(old).migrations_total == 0

    def test_worker_and_cluster_gauges_render(self):
        from dynamo_tpu.components.metrics import MetricsAggregator
        from dynamo_tpu.components.mock_worker import MockWorkerStats
        from dynamo_tpu.components.telemetry_aggregator import (
            ClusterTelemetry,
        )

        from .promtext import parse_prometheus_text

        stats = MockWorkerStats(
            seed=1, migrations_total=5, migrations_failed=1,
            migrate_kv_blocks_moved=40,
        )
        stats.tick(requests=3)
        m = stats.metrics("m1")
        assert m.migrations_total == 5
        assert m.migrate_kv_blocks_moved_total == 40

        agg = MetricsAggregator("ns1")
        agg.update("w0", m)
        parsed = parse_prometheus_text(agg.render())
        assert "dynamo_worker_migrations_total" in parsed
        assert "dynamo_worker_migrations_failed_total" in parsed
        assert "dynamo_worker_migrate_kv_blocks_moved_total" in parsed

        ct = ClusterTelemetry("ns1", clock=lambda: 100.0)
        ct.ingest("w0", m)
        ct.ingest("w1", MockWorkerStats(
            seed=2, migrations_total=2, migrate_kv_blocks_moved=16,
        ).metrics("m1"))
        roll = ct.rollup()
        assert roll["models"]["m1"]["migrations_total"] == 7
        assert roll["models"]["m1"]["migrations_failed_total"] == 1
        assert roll["models"]["m1"]["migrate_kv_blocks_moved_total"] == 56
        cparsed = parse_prometheus_text(ct.render_prometheus())
        assert "dynamo_cluster_migrations_total" in cparsed
        assert "dynamo_cluster_migrations_failed_total" in cparsed
        assert "dynamo_cluster_migrate_kv_blocks_moved_total" in cparsed

    def test_publish_loop_carries_process_counters(self, run):
        """attach_kv_publishing stamps the process-global migration
        counters onto every snapshot (the lazy sys.modules path — this
        test file has imported the module)."""
        from dynamo_tpu.runtime.bus import MessageBusServer
        from dynamo_tpu.runtime.distributed import attach_kv_publishing

        class SnapEngine:
            def metrics_snapshot(self):
                return {"request_active_slots": 0, "request_total_slots": 1}

        async def go():
            mig_mod.reset_migration_counters()
            mig_mod.note_migration(blocks=5)
            mig_mod.note_migration(blocks=3)
            mig_mod.note_migration(failed=True)
            ss = StateStoreServer(port=0)
            await ss.start()
            bus = MessageBusServer(port=0)
            await bus.start()
            rt = await DistributedRuntime.create(ss.url, bus.url)
            ns = rt.namespace("migg")
            got = asyncio.Event()
            seen = {}

            async def consume():
                sub = await ns.subscribe("kv_metrics")
                async for raw in sub:
                    seen.update(json.loads(raw))
                    got.set()
                    return

            task = asyncio.create_task(consume())
            await asyncio.sleep(0.1)
            ep = rt.namespace("migg").component("w").endpoint("gen")
            await ep.serve(_Echo())
            await attach_kv_publishing(ep, SnapEngine(), interval=0.05)
            await asyncio.wait_for(got.wait(), 5)
            task.cancel()
            m = seen["metrics"]
            assert m["migrations_total"] == 2
            assert m["migrations_failed_total"] == 1
            assert m["migrate_kv_blocks_moved_total"] == 8
            await rt.shutdown()
            await bus.stop()
            await ss.stop()
            mig_mod.reset_migration_counters()

        run(go())


# -- edge attribution (ITL, never TTFT) ----------------------------------------


class TestEdgeAttribution:
    def test_migrated_first_chunk_feeds_itl_not_ttft(self, monkeypatch):
        from dynamo_tpu.llm.http.metrics import ServiceMetrics
        from dynamo_tpu.runtime import telemetry

        monkeypatch.delenv("DYN_TPU_SLO", raising=False)
        telemetry.configure()
        try:
            m = ServiceMetrics("t_mig")
            with m.inflight_guard("m1", "completions", "stream") as g:
                g.mark_migration()
                g.mark_chunk()  # first content chunk AFTER the re-home
                g.mark_ok()
            store = telemetry.store()
            assert store.series("ttft_ms", model="m1").window_count(60.0) == 0
            assert store.series("itl_ms", model="m1").window_count(60.0) == 1
            text = m.render()
            assert 't_mig_migrations_total{model="m1"} 1' in text
            assert not m.ttft.snapshot()
        finally:
            telemetry.configure()

    def test_sync_resumes_splits_kinds(self, monkeypatch):
        """One journal carrying both a resume and a migration lands one
        event in each frontend counter — and a later resume still counts
        (per-kind watermarks, no misattribution)."""
        from dynamo_tpu.llm.http.metrics import ServiceMetrics
        from dynamo_tpu.runtime import telemetry

        monkeypatch.delenv("DYN_TPU_SLO", raising=False)
        telemetry.configure()
        try:
            m = ServiceMetrics("t_mig2")
            j = StreamJournal({"token_ids": [1, 2]})
            with m.inflight_guard("m1", "completions", "stream") as g:
                seen = 0
                j.migrations = 1
                seen = g.sync_resumes(j, seen)
                assert seen == 1
                j.resumes = 1
                seen = g.sync_resumes(j, seen)
                assert seen == 2
                j.migrations = 2
                seen = g.sync_resumes(j, seen)
                assert seen == 3
                g.mark_chunk()
                g.mark_ok()
            text = m.render()
            assert 't_mig2_migrations_total{model="m1"} 2' in text
            assert 't_mig2_resume_total{model="m1"} 1' in text
        finally:
            telemetry.configure()
