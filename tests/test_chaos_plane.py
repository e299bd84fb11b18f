"""Composition chaos plane (ISSUE 19).

Coverage:

- ``DYN_TPU_CHAOS_*`` knob clamp tables and the knob-off zero-overhead
  guard (monkeypatched observer constructor: nothing is ever built);
- schedule generation units: seed determinism (byte-identical canonical
  JSON), weight and composition-constraint honoring across many seeds,
  serialization round-trip;
- shrink: greedy event dropping is monotonic and 1-minimal, and refuses a
  schedule that does not violate;
- fault-determinism audit (satellite): two same-seed injectors driven
  through corrupt/slow/probability draws produce identical decision logs
  — seq, draw order, and recorded draw details — and identical outputs;
- invariant-suite units with injected violations: every invariant fires
  on a hand-built context that breaks exactly it, and stays quiet on a
  clean one;
- the deliberately disabled ``DYN_TPU_KV_INTEGRITY`` leg: a corrupt page
  ships, gets adopted, and the wrong-bytes invariant CATCHES the
  divergence; the artifact set round-trips and a replay from the dumped
  schedule reproduces the identical wrong bytes;
- ``llmctl cluster chaos`` rendering: exit 0/2/1 + ``--json`` envelope;
- THE fixed-seed pairwise smoke: 9 compositions over {kill, slow,
  corrupt, blackout, drain, quarantine} on 3 real tiny engines under 2x
  load — zero invariant violations;
- mock-fleet runner plumbing and the ``-m slow`` generated-seed soak.
"""

import asyncio
import concurrent.futures
import json
import os
import time

import pytest

from dynamo_tpu.runtime import chaos, faults
from dynamo_tpu.runtime.chaos import (
    ChaosContext,
    ChaosEvent,
    ChaosPolicy,
    ChaosReport,
    ChaosRunner,
    ChaosSchedule,
    DEFAULT_WEIGHTS,
    DISABLING,
    InvariantSuite,
    KINDS,
    StreamResult,
    Violation,
    mock_expected_stream,
    shrink_schedule,
)
from dynamo_tpu.runtime.faults import FaultInjector, FaultRule

from .fixtures import engines_held_back


# -- knobs + zero overhead -----------------------------------------------------


class TestChaosKnobs:
    def test_defaults(self, monkeypatch):
        for k in ("DYN_TPU_CHAOS", "DYN_TPU_CHAOS_SEED",
                  "DYN_TPU_CHAOS_DURATION", "DYN_TPU_CHAOS_EVENTS",
                  "DYN_TPU_CHAOS_WEIGHTS"):
            monkeypatch.delenv(k, raising=False)
        pol = ChaosPolicy.from_env()
        assert pol.enabled is False
        assert pol.seed == 0
        assert pol.duration == 8.0
        assert pol.max_events == 12
        assert pol.weights == DEFAULT_WEIGHTS

    def test_clamps(self, monkeypatch):
        monkeypatch.setenv("DYN_TPU_CHAOS", "1")
        monkeypatch.setenv("DYN_TPU_CHAOS_DURATION", "0.25")
        monkeypatch.setenv("DYN_TPU_CHAOS_EVENTS", "99999")
        pol = ChaosPolicy.from_env()
        assert pol.enabled is True
        assert pol.duration == 1.0          # in-range values clamp...
        assert pol.max_events == 500
        monkeypatch.setenv("DYN_TPU_CHAOS_DURATION", "1e9")
        monkeypatch.setenv("DYN_TPU_CHAOS_EVENTS", "0")
        pol = ChaosPolicy.from_env()
        assert pol.duration == 3600.0
        assert pol.max_events == 12         # ...non-positive falls back
        monkeypatch.setenv("DYN_TPU_CHAOS_DURATION", "banana")
        assert ChaosPolicy.from_env().duration == 8.0

    def test_weights_parsing(self, monkeypatch):
        monkeypatch.setenv(
            "DYN_TPU_CHAOS_WEIGHTS",
            '{"kill": 5, "nonsense": 9, "drain": -3, "slow": "x"}',
        )
        w = ChaosPolicy.from_env().weights
        assert w["kill"] == 5.0
        assert "nonsense" not in w
        assert w["drain"] == 0.0          # negative clamps to 0
        assert w["slow"] == DEFAULT_WEIGHTS["slow"]  # non-numeric ignored
        monkeypatch.setenv("DYN_TPU_CHAOS_WEIGHTS", "not json")
        assert ChaosPolicy.from_env().weights == DEFAULT_WEIGHTS
        monkeypatch.setenv("DYN_TPU_CHAOS_WEIGHTS", "[1,2]")
        assert ChaosPolicy.from_env().weights == DEFAULT_WEIGHTS

    def test_knob_off_constructs_nothing(self, monkeypatch):
        """THE zero-overhead guard (PR13/14/18 pattern): with DYN_TPU_CHAOS
        unset, the serving-path hook must never construct a chaos object —
        a booby-trapped constructor proves it."""
        monkeypatch.delenv("DYN_TPU_CHAOS", raising=False)
        chaos.reset_for_tests()

        def boom(self, *a, **k):
            raise AssertionError("ChaosObserver constructed with knob off")

        monkeypatch.setattr(chaos.ChaosObserver, "__init__", boom)
        chaos.note_event("migration", ok=True)   # arms (and declines)
        chaos.note_event("drain", worker="w0")   # fast path
        assert chaos.observer() is None

    def test_knob_on_arms_once(self, monkeypatch):
        monkeypatch.setenv("DYN_TPU_CHAOS", "1")
        chaos.reset_for_tests()
        chaos.note_event("migration", ok=True, blocks=2)
        obs = chaos.observer()
        assert obs is not None
        chaos.note_event("migration", ok=False)
        assert len(obs.events("migration")) == 2
        t, kind, fields = obs.events("migration")[0]
        assert fields == {"ok": True, "blocks": 2}


# -- schedule generation -------------------------------------------------------


def _assert_admissible(sched: ChaosSchedule):
    """Re-check the composition constraints on a finished schedule."""
    evs = sched.events
    assert list(evs) == sorted(evs, key=lambda e: (e.t, e.kind, e.worker))
    for e in evs:
        assert e.kind in KINDS
        assert 0.2 <= e.t
        assert e.t + e.duration <= sched.horizon * 0.85 + 1e-9
        assert 0 <= e.worker < sched.n_workers
    blackouts = [e for e in evs if e.kind == "blackout"]
    for i, a in enumerate(blackouts):
        for b in blackouts[i + 1:]:
            assert not (a.t < b.end() and b.t < a.end()), "overlapping blackouts"
    for k in (e for e in evs if e.kind == "kill"):
        for b in blackouts:
            assert not (k.t < b.end() and b.t < k.end()), "kill inside blackout"
    # at every instant ≥1 worker free of disabling actions, and no worker
    # carries two overlapping disabling actions
    disabling = [e for e in evs if e.kind in DISABLING]
    bounds = sorted({e.t for e in disabling} | {e.end() for e in disabling})
    for t0 in bounds:
        active = [e for e in disabling if e.t <= t0 < e.end()]
        workers = [e.worker for e in active]
        assert len(workers) == len(set(workers)), "stacked disabling on one worker"
        assert len(set(workers)) < sched.n_workers, "no worker left serving"


class TestScheduleGeneration:
    def test_seed_determinism_byte_identical(self):
        a = ChaosSchedule.generate(5, n_workers=3, horizon=8.0, max_events=12)
        b = ChaosSchedule.generate(5, n_workers=3, horizon=8.0, max_events=12)
        assert a.to_json() == b.to_json()
        assert ChaosSchedule.from_json(a.to_json()) == a

    def test_seeds_differ(self):
        blobs = {
            ChaosSchedule.generate(s, 3, 8.0, 12).to_json() for s in range(8)
        }
        assert len(blobs) > 1

    def test_constraints_hold_across_seeds(self):
        for seed in range(60):
            _assert_admissible(
                ChaosSchedule.generate(seed, n_workers=3, horizon=8.0,
                                       max_events=12)
            )

    def test_weights_honored(self):
        only = {"kill": 1.0, "drain": 1.0}
        seen = set()
        for seed in range(30):
            s = ChaosSchedule.generate(seed, 3, 8.0, 10, weights=only)
            seen.update(e.kind for e in s.events)
        assert seen <= {"kill", "drain"} and seen
        with pytest.raises(ValueError):
            ChaosSchedule.generate(1, 3, 8.0, 10, weights={"kill": 0.0})

    def test_rejects_bad_input(self):
        with pytest.raises(ValueError):
            ChaosSchedule.generate(1, n_workers=1)
        with pytest.raises(ValueError):
            ChaosSchedule.from_json(json.dumps({"version": 99}))
        with pytest.raises(ValueError):
            ChaosEvent.from_dict({"t": 1.0, "kind": "meteor"})


# -- shrink --------------------------------------------------------------------


class TestShrink:
    def _sched(self, kinds):
        return ChaosSchedule(
            seed=1, n_workers=3, horizon=8.0,
            events=tuple(
                ChaosEvent(t=0.5 + i, kind=k, worker=i % 3)
                for i, k in enumerate(kinds)
            ),
        )

    def test_greedy_shrink_monotonic_and_minimal(self):
        sched = self._sched(
            ["drain", "corrupt", "kill", "slow", "corrupt", "delay"]
        )
        sizes = []

        def check(c):
            sizes.append(len(c.events))
            return any(e.kind == "corrupt" for e in c.events)

        small = shrink_schedule(sched, check)
        assert len(small.events) == 1
        assert small.events[0].kind == "corrupt"
        # every accepted schedule is no larger than the one before it
        kept = [len(sched.events)]
        for n in sizes:
            if n < kept[-1]:
                kept.append(n)
        assert kept == sorted(kept, reverse=True)
        assert small.seed == sched.seed and small.horizon == sched.horizon

    def test_shrink_requires_violation(self):
        sched = self._sched(["drain", "kill"])
        with pytest.raises(ValueError, match="does not violate"):
            shrink_schedule(sched, lambda c: False)


# -- fault determinism (satellite) ---------------------------------------------


class TestFaultDeterminism:
    def _drive(self, seed):
        inj = FaultInjector([
            FaultRule(plane="transfer", point="pages", action="corrupt",
                      probability=0.6, max_fires=3),
            FaultRule(plane="engine", point="dispatch", action="slow",
                      delay=0.0, jitter=0.01),
        ], seed=seed)
        outs = []
        body = bytes(range(256)) * 4
        with faults.active(inj):
            for _ in range(6):
                outs.append(faults.corrupt_pages("transfer", "a:1", body))
            for _ in range(6):
                outs.append(faults.slow_gate("engine", "w0"))
        log = [
            (d.seq, d.plane, d.addr, d.point, d.op_index, d.action, d.detail)
            for d in inj.log
        ]
        return outs, log

    def test_same_seed_identical_decision_logs(self):
        """Satellite regression: every action's RNG draw (the probability
        gate, corrupt's byte offset, slow's jitter) comes off the seeded
        RNG and lands in the decision log in draw order — two same-seed
        runs are indistinguishable."""
        outs_a, log_a = self._drive(9)
        outs_b, log_b = self._drive(9)
        assert log_a == log_b
        assert outs_a == outs_b
        assert log_a, "the script must actually fire decisions"
        seqs = [e[0] for e in log_a]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
        assert any(e[5] == "corrupt" and e[6].startswith("offset=")
                   for e in log_a)
        assert any(e[5] == "slow" and e[6].startswith("jitter=")
                   for e in log_a)

    def test_corrupt_offset_is_seed_drawn(self):
        body = bytes(1000)
        flipped = set()
        for seed in range(5):
            inj = FaultInjector([FaultRule(
                plane="transfer", point="pages", action="corrupt",
            )], seed=seed)
            with faults.active(inj):
                out = faults.corrupt_pages("transfer", "a:1", body)
            (i,) = [k for k in range(1000) if out[k] != body[k]]
            assert inj.log[-1].detail == f"offset={i}"
            flipped.add(i)
        assert len(flipped) > 1, "offset must vary with the seed"


# -- invariant suite units -----------------------------------------------------


def _clean_ctx(**kw):
    base = dict(
        streams=[StreamResult(index=0, prompt=[1, 2], golden=[5, 6, 7],
                              toks=[5, 6, 7], done=True)],
        engine_snapshots=[{"kv_active_blocks": 0, "migrate_staged": 0}],
        live_requests=[0],
        client_stats={"migrations": 0, "migration_resumes": 0, "resumes": 0},
        migration_counters=(0, 0, 0),
        reconverged=True,
    )
    base.update(kw)
    return ChaosContext(**base)


class TestInvariantSuite:
    def test_clean_context_passes(self):
        suite = InvariantSuite()
        assert suite.evaluate(_clean_ctx()) == []
        table = suite.table(_clean_ctx())
        assert all(vs == [] for vs in table.values())

    def _only(self, ctx, name):
        got = {v.invariant for v in InvariantSuite().evaluate(ctx)}
        assert got == {name}, got

    def test_wrong_bytes_caught(self):
        ctx = _clean_ctx(streams=[StreamResult(
            index=0, prompt=[1], golden=[5, 6, 7], toks=[5, 9, 7], done=True,
        )])
        self._only(ctx, "safety.bytes")

    def test_typed_error_with_clean_prefix_is_safe(self):
        ctx = _clean_ctx(streams=[StreamResult(
            index=0, prompt=[1], golden=[5, 6, 7], toks=[5, 6],
            errs=["MigrationRejected: target quarantined"], done=True,
        )])
        assert InvariantSuite().evaluate(ctx) == []

    def test_typed_error_with_wrong_prefix_caught(self):
        ctx = _clean_ctx(streams=[StreamResult(
            index=0, prompt=[1], golden=[5, 6, 7], toks=[5, 9],
            errs=["boom"], done=True,
        )])
        self._only(ctx, "safety.bytes")

    def test_incomplete_stream_without_error_caught(self):
        ctx = _clean_ctx(streams=[StreamResult(
            index=0, prompt=[1], golden=[5, 6, 7], toks=[5], done=False,
        )])
        self._only(ctx, "safety.typed_errors")

    def test_stuck_and_unreconverged_caught(self):
        ctx = _clean_ctx(stuck_streams=[0], reconverged=False,
                         reconverge_detail="probe dead")
        got = {v.invariant for v in InvariantSuite().evaluate(ctx)}
        assert got == {"liveness.streams", "liveness.reconverge"}

    def test_conservation_leaks_caught(self):
        ctx = _clean_ctx(
            engine_snapshots=[{"kv_active_blocks": 3, "migrate_staged": 1}],
            live_requests=[2],
        )
        got = [v.invariant for v in InvariantSuite().evaluate(ctx)]
        assert got.count("conservation.pages") == 2  # blocks + live reqs
        assert "conservation.staged" in got

    @pytest.mark.parametrize("stuck, wait_s", [
        ("staged", 0.4),  # an unclaimed stage: waited out until swept
        ("live", 0.1),    # a stream that never ends: judged at the bound
    ])
    def test_settle_waits_out_the_stage_sweep_only(self, stuck, wait_s):
        """An unclaimed stage is freed by its target's TTL sweep, later
        than the settle bound: the settle waits that out before the
        conservation invariants may call it a leak — and nothing else."""

        class Worker:
            t0 = time.monotonic()

            def _outstanding(self):
                return int(time.monotonic() - self.t0 < 0.4)

            def live_request_count(self):
                return self._outstanding() if stuck == "live" else 0

            def metrics_snapshot(self):
                n = self._outstanding() if stuck == "staged" else 0
                return {"kv_active_blocks": 6 * n, "migrate_staged": n}

            def _migration_ttl(self):
                return 5.0

        runner = ChaosRunner(
            ChaosSchedule(seed=1, n_workers=1, horizon=1.0, events=()),
            settle_bound=0.1,
        )
        runner._engines = [Worker()]
        t0 = time.monotonic()
        asyncio.run(runner._settle())
        assert wait_s <= time.monotonic() - t0 < wait_s + 0.3

    def test_ledger_equations_exact(self):
        # journal says 2 disruptions-followed, client ledger says 1: the
        # two ledgers over the same events MUST agree token-for-token
        s = StreamResult(index=0, prompt=[1], golden=[5], toks=[5],
                         done=True, journal_migrations=2, journal_resumes=1)
        ctx = _clean_ctx(
            streams=[s],
            client_stats={"migrations": 1, "migration_resumes": 0,
                          "resumes": 0},
            migration_counters=(1, 0, 0),
        )
        got = [v.invariant for v in InvariantSuite().evaluate(ctx)]
        assert got == ["conservation.disruptions"] * 2
        # balanced ledgers pass
        ctx = _clean_ctx(
            streams=[s],
            client_stats={"migrations": 1, "migration_resumes": 1,
                          "resumes": 1},
            migration_counters=(1, 0, 0),
        )
        assert InvariantSuite().evaluate(ctx) == []

    def test_quarantine_donation_caught_with_edge_grace(self):
        ctx = _clean_ctx(
            quarantine_windows=[(10.0, 12.0)],
            migration_times=[11.0],
        )
        self._only(ctx, "safety.quarantine_no_ship")
        # a ship that cleared the latch check a beat before the window
        # opened may note completion just inside the leading edge
        ctx = _clean_ctx(
            quarantine_windows=[(10.0, 12.0)],
            migration_times=[10.01, 9.0, 12.5],
        )
        assert InvariantSuite().evaluate(ctx) == []


# -- report + llmctl rendering -------------------------------------------------


def _mini_report(ok: bool) -> ChaosReport:
    sched = ChaosSchedule(
        seed=42, n_workers=3, horizon=4.0,
        events=(ChaosEvent(t=0.5, kind="kill", worker=1, duration=0.8),),
    )
    violations = [] if ok else [
        Violation("safety.bytes", "stream 2 diverged at token 7"),
    ]
    return ChaosReport(
        schedule=sched,
        violations=violations,
        invariants={"safety.bytes": ok, "liveness.streams": True},
        stats={"streams": 6},
        decision_log=[{"seq": 1, "plane": "transfer", "addr": "a:1",
                       "point": "pages", "op_index": 0, "action": "corrupt",
                       "detail": "offset=7"}],
    )


class TestLlmctlChaos:
    def _render(self, argv):
        from dynamo_tpu.cli import llmctl

        return asyncio.run(llmctl.amain(argv))

    def test_clean_run_renders_exit_0(self, tmp_path, capsys):
        _mini_report(ok=True).write(str(tmp_path))
        rc = self._render(["cluster", "chaos", str(tmp_path)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "seed=42" in out and "PASS" in out
        assert "all invariants held" in out

    def test_violating_run_renders_exit_2_json(self, tmp_path, capsys):
        _mini_report(ok=False).write(str(tmp_path))
        rc = self._render(["cluster", "chaos", str(tmp_path), "--json"])
        env = json.loads(capsys.readouterr().out)
        assert rc == 2
        assert env["ok"] is False and env["seed"] == 42
        assert env["invariants"]["safety.bytes"] is False
        assert env["violations"][0]["invariant"] == "safety.bytes"
        assert env["schedule"]["events"][0]["kind"] == "kill"

    def test_unreadable_dir_exit_1(self, tmp_path, capsys):
        rc = self._render(
            ["cluster", "chaos", str(tmp_path / "nope"), "--json"]
        )
        env = json.loads(capsys.readouterr().out)
        assert rc == 1 and env["ok"] is False and "error" in env

    def test_artifacts_round_trip(self, tmp_path):
        rep = _mini_report(ok=False)
        rep.write(str(tmp_path))
        text = (tmp_path / "schedule.json").read_text()
        assert ChaosSchedule.from_json(text) == rep.schedule
        result = json.loads((tmp_path / "result.json").read_text())
        assert result["decision_log"][0]["detail"] == "offset=7"


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
    base = dict(max_slots=4, kv_block_size=8, max_model_len=256)
    base.update(kw)
    return JaxServingEngine(cfg, params, EngineConfig(**base))


def _call(engine, fn, timeout=60):
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
    from dynamo_tpu.runtime.engine import Context

    out = []
    async for item in engine.generate(Context(_payload(toks, max_tokens, **kw))):
        if item.is_error:
            raise AssertionError(item.error_message())
        out.extend((item.data or {}).get("token_ids", []))
    return out


@pytest.fixture(scope="module")
def chaos_engines(tiny):
    """Three warmed engines shared across the pairwise matrix — engine
    build+compile is the expensive part, and the runner is written to
    reuse engines across runs (it rebuilds every runtime/server layer
    per composition)."""
    engines = [_engine(tiny) for _ in range(3)]
    for e in engines:
        asyncio.run(_collect(e, [1, 2, 3], 2))
    yield engines
    for e in engines:
        e.close()


# -- the integrity-off leg: chaos catches a disabled defense -------------------


class TestDisabledIntegrityCaught:
    def test_wrong_bytes_invariant_catches_and_replays(
        self, tiny, monkeypatch, tmp_path, run
    ):
        """Satellite acceptance: turn the KV-integrity checksums OFF, ship
        one corrupted page set through a real migration, adopt it — the
        wrong-bytes invariant must CATCH the divergence, the artifact set
        must round-trip, and a replay from the dumped schedule must
        reproduce the identical wrong bytes. Fresh engines on purpose:
        with integrity off the adopted corruption seals into the target's
        content-addressed prefix cache and would poison every later test
        that shares the fixture engines."""
        monkeypatch.setenv("DYN_TPU_KV_INTEGRITY", "0")
        # seed 23 pinned: its drawn offset (9499, an exponent byte of a
        # layer-0 V page of the prompt) is one the greedy continuation
        # diverges on from its first adopted token — most one-bit flips are
        # numerically invisible to argmax (14 of 16 seeds tried; the
        # once-pinned 11 among them since the model's numerics moved)
        sched = ChaosSchedule(
            seed=23, n_workers=2, horizon=4.0,
            events=(ChaosEvent(t=0.3, kind="corrupt", worker=0),
                    ChaosEvent(t=0.5, kind="drain", worker=0, duration=1.0)),
        )

        async def ship_corrupted(seed):
            """One migration under a corrupt rule; returns the delivered
            stream (pre-cut tokens + adopted continuation) and the log."""
            from dynamo_tpu.disagg.transfer import (
                KvTransferClient,
                KvTransferServer,
            )
            from dynamo_tpu.runtime.engine import Context

            src = _engine(tiny, max_slots=2)
            prompt = list(range(17, 43))
            ctx = Context(_payload(prompt, 28))
            gen = src.generate(ctx)
            got = []
            # hold the source back while the test reads its 4 tokens: a
            # free-running engine is frozen wherever the machine's load
            # lets it get to, and how many tokens were computed on clean
            # pages decides whether (and where) the flip shows
            with engines_held_back(0.15):
                async for item in gen:
                    got.extend((item.data or {}).get("token_ids", []))
                    if len(got) >= 4:
                        break
                cp = _call(src, src.export_migratable)[0]
            emitted = cp["token_ids"][len(prompt):]
            pages, _ = _call(src, lambda: src.extract_for_migration(
                cp["request_id"]
            ))
            tgt = _engine(tiny, max_slots=2)
            server = KvTransferServer(tgt, host="127.0.0.1", port=0)
            await server.start()
            client = KvTransferClient()
            inj = FaultInjector([FaultRule(
                plane="transfer", point="pages", action="corrupt",
                max_fires=1,
            )], seed=seed)
            with faults.active(inj):
                await client.migrate(
                    f"127.0.0.1:{server.port}",
                    {k: cp[k] for k in ("mid", "request_id", "token_ids",
                                        "emitted", "tenant", "level")},
                    pages,
                )
            log = [{"seq": d.seq, "plane": d.plane, "addr": d.addr,
                    "point": d.point, "op_index": d.op_index,
                    "action": d.action, "detail": d.detail}
                   for d in inj.log]
            _call(src, lambda: src.finish_migrated(
                cp["request_id"], "i", "w", cp["mid"]
            ))
            async for _ in gen:
                pass
            out = await _collect(
                tgt, cp["token_ids"], 28 - len(emitted),
                resume={"prompt_len": len(prompt),
                        "rng_offset": len(emitted)},
                migrate=cp["mid"],
            )
            await client.close()
            await server.stop()
            src.close()
            tgt.close()
            return prompt, emitted + out, log

        async def go():
            control = _engine(tiny, max_slots=2)
            prompt = list(range(17, 43))
            golden = await _collect(control, prompt, 28)
            control.close()

            got_prompt, delivered, log = await ship_corrupted(sched.seed)
            stream = StreamResult(index=0, prompt=got_prompt, golden=golden,
                                  toks=delivered, done=True,
                                  journal_migrations=1)
            ctx = ChaosContext(
                streams=[stream],
                client_stats={"migrations": 1, "migration_resumes": 0,
                              "resumes": 0},
                migration_counters=(1, 0, 0),
            )
            suite = InvariantSuite()
            table = suite.table(ctx)
            violations = [v for vs in table.values() for v in vs]
            assert violations, (
                "with integrity disabled the corrupted adoption MUST "
                "surface as wrong bytes"
            )
            assert {v.invariant for v in violations} == {"safety.bytes"}

            report = ChaosReport(
                schedule=sched, violations=violations,
                invariants={k: not vs for k, vs in table.items()},
                stats={"streams": 1}, decision_log=log,
            )
            run_dir = str(tmp_path / "run")
            report.write(run_dir)

            # the artifact is the replay contract: reload the dumped
            # schedule, re-run the corruption path from its seed, and the
            # wrong bytes must reproduce byte-identically
            reloaded = ChaosSchedule.from_json(
                open(os.path.join(run_dir, "schedule.json")).read()
            )
            assert reloaded == sched
            _, delivered2, log2 = await ship_corrupted(reloaded.seed)
            assert delivered2 == delivered
            # addr carries the ephemeral transfer port — everything the
            # seed controls (draw order + offsets) must reproduce exactly
            strip = lambda lg: [
                {k: v for k, v in d.items() if k != "addr"} for d in lg
            ]
            assert strip(log2) == strip(log)
            assert delivered != golden

            # and llmctl renders the dumped run as a failure
            from dynamo_tpu.cli import llmctl

            assert await llmctl.amain(
                ["cluster", "chaos", run_dir, "--json"]
            ) == 2

        run(go())


# -- the fixed-seed pairwise smoke (tier-1 gate) -------------------------------


def _pair_schedules():
    """9 hand-built compositions covering every kind in {kill, slow,
    corrupt, blackout, drain, quarantine}. Timings are fixed (not drawn)
    so the matrix is identical on every run; the seed still drives every
    in-run draw (fault RNG, resilience jitter)."""
    E = ChaosEvent
    return [
        ("kill x slow", ChaosSchedule(seed=201, n_workers=3, horizon=3.0,
         events=(E(t=0.3, kind="slow", worker=1, duration=1.0),
                 E(t=0.6, kind="kill", worker=0, duration=0.6)))),
        ("kill x drain", ChaosSchedule(seed=202, n_workers=3, horizon=3.0,
         events=(E(t=0.3, kind="drain", worker=1, duration=1.2),
                 E(t=0.5, kind="kill", worker=0, duration=0.6)))),
        ("kill x quarantine", ChaosSchedule(seed=203, n_workers=3, horizon=3.0,
         events=(E(t=0.3, kind="kill", worker=2, duration=0.5),
                 E(t=1.0, kind="quarantine", worker=1, duration=0.8)))),
        ("slow x blackout", ChaosSchedule(seed=204, n_workers=3, horizon=3.0,
         events=(E(t=0.25, kind="slow", worker=0, duration=1.2),
                 E(t=0.5, kind="blackout", worker=0, duration=0.8)))),
        ("slow x drain", ChaosSchedule(seed=205, n_workers=3, horizon=3.0,
         events=(E(t=0.25, kind="slow", worker=1, duration=1.2),
                 E(t=0.45, kind="drain", worker=1, duration=1.2)))),
        ("corrupt x drain", ChaosSchedule(seed=206, n_workers=3, horizon=3.0,
         events=(E(t=0.25, kind="corrupt", worker=0),
                 E(t=0.45, kind="drain", worker=0, duration=1.5)))),
        ("corrupt x quarantine", ChaosSchedule(seed=207, n_workers=3,
         horizon=3.0,
         events=(E(t=0.25, kind="corrupt", worker=0),
                 E(t=0.35, kind="quarantine", worker=2, duration=0.9),
                 E(t=1.5, kind="drain", worker=0, duration=1.0)))),
        ("blackout x drain", ChaosSchedule(seed=208, n_workers=3, horizon=3.0,
         events=(E(t=0.3, kind="blackout", worker=0, duration=0.8),
                 E(t=0.5, kind="drain", worker=2, duration=1.0)))),
        ("quarantine x drain", ChaosSchedule(seed=209, n_workers=3,
         horizon=3.0,
         events=(E(t=0.25, kind="quarantine", worker=1, duration=1.2),
                 E(t=0.35, kind="drain", worker=0, duration=1.5)))),
    ]


@pytest.mark.chaos
class TestPairwiseSmoke:
    # nine paced schedules of 3 s each plus settle: 46 s on an idle machine,
    # 62-86 s beside six other test files
    @pytest.mark.timeout(300)
    def test_pairwise_matrix_zero_violations(self, chaos_engines):
        """ISSUE 19 acceptance: the fixed-seed pairwise matrix over the
        six headline kinds runs on 3 real tiny engines under 2x streaming
        load with ZERO invariant violations. Any violation here is a real
        composition bug in the defenses — fix it, don't relax the gate."""
        from dynamo_tpu.runtime import integrity

        failed = []
        disruptions = 0
        for name, sched in _pair_schedules():
            _assert_admissible(sched)
            report = asyncio.run(ChaosRunner(
                sched, engines=chaos_engines, max_tokens=30,
            ).run())
            for v in report.violations:
                failed.append(f"{name}: {v.invariant}: {v.detail}")
            c = report.stats["client"]
            disruptions += (
                c["failures"] + c["failovers"] + c["resumes"]
                + c["migrations"] + c["migration_resumes"]
                + report.stats["errored"]
            )
            # the trip window and verdict latches are process-global:
            # one composition's nacks must not bleed into the next
            integrity.reset_for_tests()
        assert not failed, "\n".join(failed)
        assert disruptions > 0, (
            "the matrix must actually disrupt something — a zero-impact "
            "run means the schedules no longer land mid-stream"
        )

    def test_mock_fleet_runner(self):
        """Runner plumbing without engines: the deterministic token mock
        absorbs a kill+quarantine schedule byte-equal."""
        sched = ChaosSchedule(
            seed=7, n_workers=3, horizon=3.0,
            events=(ChaosEvent(t=0.4, kind="kill", worker=0, duration=0.6),
                    ChaosEvent(t=0.8, kind="quarantine", worker=1,
                               duration=0.7)),
        )
        report = asyncio.run(ChaosRunner(sched, max_tokens=20).run())
        assert report.ok, [v.to_dict() for v in report.violations]
        assert report.stats["mock"] is True
        # the mock's greedy continuation is a pure function of the prefix
        toks, exp = [3, 4], []
        for _ in range(3):
            toks.append((toks[-1] * 31 + len(toks) * 7 + 13) % 50021)
            exp.append(toks[-1])
        assert mock_expected_stream([3, 4], 3) == exp


@pytest.mark.slow
@pytest.mark.chaos
class TestSoak:
    def test_generated_seed_soak(self, chaos_engines):
        """Open-ended leg (-m slow): generated schedules straight from the
        seed stream, full vocabulary, real engines."""
        from dynamo_tpu.runtime import integrity

        for seed in range(10):
            sched = ChaosSchedule.generate(
                seed, n_workers=3, horizon=4.0, max_events=6,
            )
            report = asyncio.run(ChaosRunner(
                sched, engines=chaos_engines, max_tokens=30,
            ).run())
            assert report.ok, (
                f"seed {seed}: " + "; ".join(
                    v.detail for v in report.violations
                )
            )
            integrity.reset_for_tests()
