"""Fail-slow defense (ISSUE 18): differential straggler detection,
soft-demotion routing, and migrate-off-the-straggler.

Coverage:

- knob clamp table + the DYN_TPU_STRAGGLER=0 zero-overhead guard
  (monkeypatched detector constructor: nothing is ever built);
- detector units: EWMA seeding/convergence, token-free dispatches
  skipped, bounded debug ring;
- arbiter units (clock-injected, no sleeps): zero false positives on a
  uniform fleet, suspect → confirmed → clear ladder, the min_peers gate,
  the all-slow-fleet non-demotion, the drain-composition HOLD (a paused
  worker is never judged), probation decay of a starved verdict, and
  departed-worker expiry;
- the verdict latch + health plane: suspect sits between healthy and
  unhealthy, quarantine outranks it, no hysteresis, no self-drain;
- control-key integration on real runtimes: a put latches within a
  health tick, foreign keys are ignored, routing soft-demotes (all-
  suspect still serves), key deletion FAILS OPEN to ok, and a confirmed
  verdict fires the bounded drain pulse;
- `llmctl cluster status` slow= column + SLOW detail line via mock
  workers → a real aggregator;
- THE chaos gate: 3 real tiny-engine workers under 2x load, one slowed
  ~10x mid-run → suspect within a window, inflight migrates off
  byte-equal with zero recomputed prefill, new admissions avoid the
  straggler at ~control ITL while the undefended leg degrades >3x, and
  the worker auto-recovers once the fault lifts.
"""

import asyncio
import concurrent.futures
import random

import pytest

from dynamo_tpu.disagg import migration as mig_mod
from dynamo_tpu.disagg.migration import attach_migration
from dynamo_tpu.runtime import faults, health, resilience, straggler
from dynamo_tpu.runtime.annotated import Annotated
from dynamo_tpu.runtime.distributed import (
    DistributedRuntime,
    attach_kv_publishing,
)
from dynamo_tpu.runtime.engine import AsyncEngine, Context
from dynamo_tpu.runtime.faults import FaultInjector, FaultRule
from dynamo_tpu.runtime.resilience import ResiliencePolicy
from dynamo_tpu.runtime.statestore import StateStoreServer
from dynamo_tpu.runtime.straggler import (
    StragglerArbiter,
    StragglerDetector,
    StragglerPolicy,
)

NO_BUS = "127.0.0.1:1"


# -- knobs ---------------------------------------------------------------------


class TestStragglerKnobs:
    def test_from_env_table(self, monkeypatch):
        cases = [
            ({}, StragglerPolicy()),
            ({"DYN_TPU_STRAGGLER": "1"}, StragglerPolicy(enabled=True)),
            ({"DYN_TPU_STRAGGLER": "off"}, StragglerPolicy(enabled=False)),
            # clamps: malformed/non-positive → defaults; out of range → edge
            ({"DYN_TPU_STRAGGLER_FACTOR": "junk"}, StragglerPolicy()),
            ({"DYN_TPU_STRAGGLER_FACTOR": "-2"}, StragglerPolicy()),
            ({"DYN_TPU_STRAGGLER_FACTOR": "1.0"}, StragglerPolicy(factor=1.1)),
            ({"DYN_TPU_STRAGGLER_FACTOR": "1000"},
             StragglerPolicy(factor=100.0)),
            ({"DYN_TPU_STRAGGLER_WINDOW": "0.05"},
             StragglerPolicy(window=0.2)),
            ({"DYN_TPU_STRAGGLER_WINDOW": "90000"},
             StragglerPolicy(window=3600.0)),
            ({"DYN_TPU_STRAGGLER_WINDOW": "-1"}, StragglerPolicy()),
            ({"DYN_TPU_STRAGGLER_MIN_PEERS": "1"},
             StragglerPolicy(min_peers=2)),
            ({"DYN_TPU_STRAGGLER_MIN_PEERS": "9999"},
             StragglerPolicy(min_peers=4096)),
            ({"DYN_TPU_STRAGGLER_TRIPS": "-1"}, StragglerPolicy()),
            ({"DYN_TPU_STRAGGLER_TRIPS": "500"}, StragglerPolicy(trips=100)),
            ({"DYN_TPU_STRAGGLER": "1", "DYN_TPU_STRAGGLER_FACTOR": "2.5",
              "DYN_TPU_STRAGGLER_WINDOW": "5", "DYN_TPU_STRAGGLER_TRIPS": "2"},
             StragglerPolicy(enabled=True, factor=2.5, window=5.0, trips=2)),
        ]
        knobs = ("DYN_TPU_STRAGGLER", "DYN_TPU_STRAGGLER_FACTOR",
                 "DYN_TPU_STRAGGLER_WINDOW", "DYN_TPU_STRAGGLER_MIN_PEERS",
                 "DYN_TPU_STRAGGLER_TRIPS")
        for env, want in cases:
            for k in knobs:
                monkeypatch.delenv(k, raising=False)
            for k, v in env.items():
                monkeypatch.setenv(k, v)
            assert StragglerPolicy.from_env() == want, env

    def test_maybe_from_env_gate(self, monkeypatch):
        monkeypatch.delenv("DYN_TPU_STRAGGLER", raising=False)
        assert straggler.maybe_from_env() is None
        assert not straggler.enabled()
        monkeypatch.setenv("DYN_TPU_STRAGGLER", "1")
        pol = straggler.maybe_from_env()
        assert pol is not None and pol.enabled
        assert straggler.enabled()


# -- real tiny engines (harness mirrors test_migration.py) ---------------------


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
    fut = concurrent.futures.Future()

    def wrap():
        try:
            fut.set_result(fn())
        except Exception as e:  # delivered to the caller
            fut.set_exception(e)

    engine.post(wrap)
    return fut.result(timeout=timeout)


def _payload(toks, max_tokens):
    return {
        "token_ids": list(toks),
        "stop_conditions": {"max_tokens": max_tokens, "ignore_eos": True},
        "sampling_options": {"temperature": 0.0},
    }


async def _collect(engine, toks, max_tokens):
    out = []
    async for item in engine.generate(Context(_payload(toks, max_tokens))):
        if item.is_error:
            raise AssertionError(item.error_message())
        out.extend((item.data or {}).get("token_ids", []))
    return out


def _policy(**kw) -> ResiliencePolicy:
    base = dict(
        request_timeout=120.0,
        connect_timeout=2.0,
        max_attempts=4,
        backoff_base=0.01,
        backoff_max=0.05,
        breaker_threshold=2,
        breaker_cooldown=30.0,
        resume_attempts=2,
        seed=7,
    )
    base.update(kw)
    return ResiliencePolicy(**base)


async def _stream(client, prompt, max_tokens):
    ctx = Context(_payload(prompt, max_tokens))
    toks, errs = [], []
    async for item in client.generate(ctx):
        if item.is_error:
            errs.append(item.error_message())
        elif isinstance(item.data, dict):
            toks.extend(item.data.get("token_ids", []))
    return toks, errs, ctx


async def _timed_stream(client, prompt, max_tokens):
    """Like _stream but also records inter-token gaps (ITL, not TTFT —
    the first stamp is the baseline, so the prefill wait never counts)."""
    ctx = Context(_payload(prompt, max_tokens))
    loop = asyncio.get_running_loop()
    toks, errs, stamps = [], [], []
    async for item in client.generate(ctx):
        if item.is_error:
            errs.append(item.error_message())
        elif isinstance(item.data, dict):
            got = item.data.get("token_ids", [])
            if got:
                toks.extend(got)
                stamps.append(loop.time())
    gaps = [b - a for a, b in zip(stamps, stamps[1:])]
    return toks, errs, gaps


async def _timed_collect(engine, toks, max_tokens):
    """Direct-at-the-engine variant of _timed_stream (no routing)."""
    loop = asyncio.get_running_loop()
    out, stamps = [], []
    async for item in engine.generate(Context(_payload(toks, max_tokens))):
        if item.is_error:
            raise AssertionError(item.error_message())
        got = (item.data or {}).get("token_ids", [])
        if got:
            out.extend(got)
            stamps.append(loop.time())
    return out, [b - a for a, b in zip(stamps, stamps[1:])]


def _p95(gaps):
    if not gaps:
        return 0.0
    s = sorted(gaps)
    return s[min(int(0.95 * len(s)), len(s) - 1)]


async def _goldens(tiny, prompts, max_tokens):
    eng = _engine(tiny, max_slots=4)
    out = []
    for p in prompts:
        out.append(await _collect(eng, p, max_tokens))
    eng.close()
    return out


# -- zero-overhead guard -------------------------------------------------------


class TestZeroOverheadGuard:
    def test_straggler_off_constructs_nothing(self, tiny, run, monkeypatch):
        """DYN_TPU_STRAGGLER unset acceptance: no detector is ever
        constructed, the engine publishes no straggler gauges, and the
        constructor-free reads all answer empty."""
        monkeypatch.delenv("DYN_TPU_STRAGGLER", raising=False)

        def _boom(*a, **kw):
            raise AssertionError("constructed with the straggler plane off")

        monkeypatch.setattr(straggler, "StragglerDetector", _boom)

        assert straggler.maybe_detector() is None
        eng = _engine(tiny)
        try:
            toks = run(_collect(eng, [3, 5, 7], 8))
            assert len(toks) == 8
            snap = eng.metrics_snapshot()
            assert "dispatch_us_per_token_ewma" not in snap
            assert "straggler_state" not in snap
        finally:
            eng.close()
        assert straggler.maybe_detector() is None
        assert straggler.detector_gauges() == {}


# -- detector units ------------------------------------------------------------


class TestDetector:
    def test_first_sample_seeds_then_converges(self):
        det = StragglerDetector()
        det.note_dispatch("decode", 1000.0, 1)
        assert det.us_per_token() == 1000.0
        for _ in range(200):
            det.note_dispatch("decode", 100.0, 1)
        assert abs(det.us_per_token() - 100.0) < 1.0
        g = det.gauges()
        assert g["straggler_samples_total"] == 201
        assert g["dispatch_us_per_token_ewma"] == round(det.us_per_token(), 1)

    def test_tokenless_and_negative_dispatches_skipped(self):
        det = StragglerDetector()
        det.note_dispatch("decode", 500.0, 0)
        det.note_dispatch("decode", -1.0, 4)
        assert det.samples_total == 0
        assert det.us_per_token() == 0.0
        det.note_dispatch("chunk", 800.0, 8)  # 100 us/token, batch-normalized
        assert det.us_per_token() == 100.0

    def test_debug_ring_bounded(self):
        det = StragglerDetector()
        for _ in range(2000):
            det.note_dispatch("decode", 100.0, 1)
        assert len(det._ring) == StragglerDetector.RING
        dump = det.debug_dump()
        assert len(dump["recent"]) == 32
        assert dump["phase_ewma"]["decode"] == 100.0
        assert dump["samples_total"] == 2000


# -- verdict latch -------------------------------------------------------------


class TestVerdictLatch:
    def test_round_trip_and_unknown_dropped(self, caplog):
        assert straggler.verdict() == straggler.OK
        straggler.set_verdict(straggler.SUSPECT)
        assert straggler.verdict() == straggler.SUSPECT
        with caplog.at_level("WARNING"):
            straggler.set_verdict("zonked")
        assert straggler.verdict() == straggler.SUSPECT, (
            "unknown verdict must not clobber the latch"
        )
        assert "unknown straggler verdict" in caplog.text
        straggler.clear_verdict()
        assert straggler.verdict() == straggler.OK


# -- arbiter units (clock-injected, no sleeps) ---------------------------------


def _pol(**kw):
    base = dict(enabled=True, factor=3.0, window=10.0, min_peers=2, trips=3)
    base.update(kw)
    return StragglerPolicy(**base)


class TestArbiter:
    def test_uniform_fleet_zero_false_positives(self):
        """ISSUE 18 acceptance: ordinary jitter (±20%) on a uniform fleet
        produces ZERO verdicts over many windows."""
        rng = random.Random(7)
        arb = StragglerArbiter(_pol())
        s = {"a": 0, "b": 0, "c": 0}
        t = 0.0
        for _ in range(50):
            t += 11.0
            for wid in s:
                s[wid] += 5
                arb.observe(
                    wid, "m", 100.0 * rng.uniform(0.8, 1.2), s[wid], now=t
                )
            assert arb.evaluate(t) == {}
        assert arb.windows_total >= 49
        assert arb.trips_total == 0
        assert arb.verdicts() == {}

    def test_all_slow_fleet_stays_undemoted(self):
        """A pod-wide thermal event slows EVERYONE: relative to the (slow)
        median nobody is a straggler, and the fleet keeps serving."""
        arb = StragglerArbiter(_pol())
        s = {"a": 0, "b": 0, "c": 0}
        t = 0.0
        for _ in range(5):
            t += 11.0
            for wid in s:
                s[wid] += 5
                arb.observe(wid, "m", 900.0, s[wid], now=t)
            assert arb.evaluate(t) == {}
        assert arb.verdicts() == {}

    def _round(self, arb, t, s, ewmas, fresh=("a", "b", "c")):
        t += 11.0
        for wid, ewma in ewmas.items():
            if wid in fresh:
                s[wid] += 5
            arb.observe(wid, "m", ewma, s[wid], now=t)
        return t, arb.evaluate(t)

    def test_slow_worker_suspect_confirmed_then_clears(self):
        arb = StragglerArbiter(_pol(trips=3))
        s = {"a": 0, "b": 0, "c": 0}
        t = 0.0
        base = {"a": 100.0, "b": 100.0}
        t, ch = self._round(arb, t, s, dict(base, c=100.0))
        assert ch == {}  # first boundary: everyone clean
        t, ch = self._round(arb, t, s, dict(base, c=900.0))
        assert ch == {"c": straggler.SUSPECT}
        t, ch = self._round(arb, t, s, dict(base, c=900.0))
        assert ch == {}  # trip 2 of 3: still suspect, no CHANGE emitted
        t, ch = self._round(arb, t, s, dict(base, c=900.0))
        assert ch == {"c": straggler.CONFIRMED}
        assert arb.state_of("c") == straggler.CONFIRMED
        assert arb.verdicts() == {"c": straggler.CONFIRMED}
        # one full window back inside the peer envelope clears outright
        t, ch = self._round(arb, t, s, dict(base, c=110.0))
        assert ch == {"c": straggler.OK}
        assert arb.verdicts() == {}
        assert arb.state_of("c") == straggler.OK

    def test_min_peers_gate_no_lone_verdicts(self):
        """One reporter has no peers, hence no differential signal — even
        at an absurd EWMA nothing is ever judged."""
        arb = StragglerArbiter(_pol(min_peers=2))
        t, samples = 0.0, 0
        for _ in range(6):
            t += 11.0
            samples += 5
            arb.observe("lonely", "m", 99999.0, samples, now=t)
            assert arb.evaluate(t) == {}
        assert arb.verdicts() == {}

    def test_drain_pause_holds_never_judged(self):
        """Composition regression (ISSUE 18 satellite): a PR12 drain pauses
        worker c — its sample counter freezes while a slow fault rages
        elsewhere. Even with a numerically-high stale EWMA, c must HOLD at
        ok: a pause is not slowness."""
        arb = StragglerArbiter(_pol())
        s = {"a": 0, "b": 0, "c": 0}
        t = 0.0
        t, ch = self._round(arb, t, s, {"a": 100.0, "b": 100.0, "c": 100.0})
        assert ch == {}
        # c drains: heartbeats keep arriving (same samples_total), and its
        # last published EWMA was a queue-flush spike far above the cut
        for _ in range(6):
            t, ch = self._round(
                arb, t, s, {"a": 100.0, "b": 100.0, "c": 950.0},
                fresh=("a", "b"),
            )
            assert ch == {}
        assert arb.state_of("c") == straggler.OK
        assert arb.trips_total == 0

    def test_bus_blackout_stale_ewma_holds_not_convicts(self):
        """Composition regression (ISSUE 19 satellite, the chaos matrix's
        slow×blackout pairing): worker c's last load report before a bus
        blackout carried a queue-spike EWMA — then the bus dies and NOBODY
        publishes for many windows. The arbiter keeps evaluating on its
        clock, but a stale number is not a fresh differential signal: c
        must HOLD at its pre-blackout verdict (one SUSPECT trip), never
        ladder to CONFIRMED off data the blackout froze. When the bus
        returns with healthy samples, c clears outright."""
        arb = StragglerArbiter(_pol(trips=3))
        s = {"a": 0, "b": 0, "c": 0}
        t = 0.0
        base = {"a": 100.0, "b": 100.0}
        t, ch = self._round(arb, t, s, dict(base, c=100.0))
        assert ch == {}
        # last pre-blackout report: c spikes → first trip, SUSPECT
        t, ch = self._round(arb, t, s, dict(base, c=950.0))
        assert ch == {"c": straggler.SUSPECT}
        trips_before = arb.trips_total
        # bus blackout: zero observe() calls fleetwide; boundaries still
        # tick. Stale EWMAs must neither trip nor change anything.
        for _ in range(6):
            t += 11.0
            assert arb.evaluate(t) == {}
        assert arb.state_of("c") == straggler.SUSPECT
        assert arb.trips_total == trips_before, (
            "a blackout-frozen EWMA must not accumulate trips"
        )
        # bus restored: one healthy fresh window clears c
        t, ch = self._round(arb, t, s, dict(base, c=105.0))
        assert ch == {"c": straggler.OK}
        assert arb.verdicts() == {}

    def test_probation_decay_releases_starved_verdict(self):
        """Soft-demotion starves a suspect of the traffic that could clear
        it. A demoted worker with no fresh samples for PROBATION_WINDOWS
        consecutive windows decays one severity level — and a still-slow
        worker re-trips within one fresh window (trips ladder preserved)."""
        arb = StragglerArbiter(_pol(trips=2))
        s = {"a": 0, "b": 0, "c": 0}
        t = 0.0
        base = {"a": 100.0, "b": 100.0}
        t, _ = self._round(arb, t, s, dict(base, c=100.0))
        t, ch = self._round(arb, t, s, dict(base, c=900.0))
        assert ch == {"c": straggler.SUSPECT}
        t, ch = self._round(arb, t, s, dict(base, c=900.0))
        assert ch == {"c": straggler.CONFIRMED}
        # c starves: routers avoid it, so only heartbeats arrive
        P = StragglerArbiter.PROBATION_WINDOWS
        for i in range(1, 2 * P + 1):
            t, ch = self._round(
                arb, t, s, dict(base, c=900.0), fresh=("a", "b")
            )
            if i == P:
                assert ch == {"c": straggler.SUSPECT}, "first decay step"
            elif i == 2 * P:
                assert ch == {"c": straggler.OK}, "fully released"
            else:
                assert ch == {}
        assert arb.state_of("c") == straggler.OK
        # released but STILL slow: the first fresh window re-suspects and
        # the second re-confirms (trips=2) — bounded oscillation
        t, ch = self._round(arb, t, s, dict(base, c=900.0))
        assert ch == {"c": straggler.SUSPECT}
        t, ch = self._round(arb, t, s, dict(base, c=900.0))
        assert ch == {"c": straggler.CONFIRMED}

    def test_decayed_confirmed_reconfirms_in_one_window(self):
        """The probe cycle must not restart the whole trip ladder: a
        confirmed verdict that decayed to suspect re-confirms after ONE
        fresh slow window."""
        arb = StragglerArbiter(_pol(trips=3))
        s = {"a": 0, "b": 0, "c": 0}
        t = 0.0
        base = {"a": 100.0, "b": 100.0}
        t, _ = self._round(arb, t, s, dict(base, c=100.0))
        for want in (straggler.SUSPECT, None, straggler.CONFIRMED):
            t, ch = self._round(arb, t, s, dict(base, c=900.0))
            assert ch == ({"c": want} if want else {})
        for i in range(StragglerArbiter.PROBATION_WINDOWS):
            t, ch = self._round(
                arb, t, s, dict(base, c=900.0), fresh=("a", "b")
            )
        assert ch == {"c": straggler.SUSPECT}
        t, ch = self._round(arb, t, s, dict(base, c=900.0))
        assert ch == {"c": straggler.CONFIRMED}

    def test_departed_worker_expires_and_clears(self):
        """A worker that left the fleet entirely (no heartbeats at all) is
        dropped after EXPIRE_WINDOWS and its verdict cleared."""
        arb = StragglerArbiter(_pol(trips=1))
        s = {"a": 0, "b": 0, "c": 0}
        t = 0.0
        base = {"a": 100.0, "b": 100.0}
        t, _ = self._round(arb, t, s, dict(base, c=100.0))
        t, ch = self._round(arb, t, s, dict(base, c=900.0))
        assert ch == {"c": straggler.CONFIRMED}  # trips=1
        cleared = False
        for _ in range(14):  # > EXPIRE_WINDOWS of total silence from c
            t, ch = self._round(arb, t, s, base, fresh=("a", "b"))
            cleared = cleared or ch.get("c") == straggler.OK
        assert cleared, "the departed worker's verdict never cleared"
        assert arb.state_of("c") == straggler.OK
        assert "c" not in arb.debug_dump()["workers"]
        assert arb.verdicts() == {}


# -- health plane --------------------------------------------------------------


class TestHealthSuspect:
    def test_verdict_maps_to_suspect_no_hysteresis(self):
        mon = health.HealthMonitor(policy=health.HealthPolicy())
        assert mon.check() == health.HEALTHY
        straggler.set_verdict(straggler.SUSPECT)
        assert mon.check() == health.SUSPECT
        # confirmed is still the same soft health state (severity lives in
        # the verdict, not the health enum)
        straggler.set_verdict(straggler.CONFIRMED)
        assert mon.check() == health.SUSPECT
        # clears immediately both ways: the arbiter owns the flap damping
        straggler.clear_verdict()
        assert mon.check() == health.HEALTHY

    def test_quarantine_outranks_suspect(self):
        from dynamo_tpu.runtime import integrity

        mon = health.HealthMonitor(policy=health.HealthPolicy())
        straggler.set_verdict(straggler.SUSPECT)
        integrity.tracker().quarantine("store", reason="unit")
        try:
            assert mon.check() == health.QUARANTINED
        finally:
            integrity.reset_for_tests()
        assert mon.check() == health.SUSPECT

    def test_suspect_does_not_self_drain(self):
        """Plain suspects keep serving as route-of-last-resort; only the
        CONFIRMED drain pulse (control loop) ever touches drain state."""
        calls = []
        mon = health.HealthMonitor(
            policy=health.HealthPolicy(),
            set_draining=lambda flag, source=None: calls.append(
                (flag, source)
            ),
        )
        straggler.set_verdict(straggler.SUSPECT)
        assert mon.check() == health.SUSPECT
        straggler.clear_verdict()
        assert mon.check() == health.HEALTHY
        assert calls == []


# -- control-key integration (real runtimes, echo engines) ---------------------


class TestControlLatch:
    def test_key_latches_soft_demotes_and_fails_open(self, run, monkeypatch):
        """The full worker-side loop: a verdict key put by the arbiter (here
        by hand — the drill contract) latches within a health tick, routing
        soft-demotes the worker, an all-suspect pool still serves, a key
        for a FOREIGN worker is ignored, and deletion fails open to ok."""
        monkeypatch.setenv("DYN_TPU_STRAGGLER", "1")
        monkeypatch.setenv("DYN_TPU_HEALTH_CHECK_INTERVAL", "0.05")
        monkeypatch.setenv("DYN_TPU_LOAD_REPORT_INTERVAL", "0.05")

        marks = [0, 0]

        class _Marked(AsyncEngine):
            def __init__(self, i):
                self.i = i

            async def generate(self, request: Context):
                marks[self.i] += 1
                yield Annotated.from_data({"token_ids": [self.i]})

        async def _drain(client, n):
            for j in range(n):
                toks, errs, _ = await _stream(client, [1, 2, 3], 1)
                assert errs == []

        async def go():
            ss = StateStoreServer(port=0)
            await ss.start()
            rts = []
            for i in range(2):
                rt = await DistributedRuntime.create(ss.url, NO_BUS)
                ep = rt.namespace("sg").component("w").endpoint("gen")
                await ep.serve(_Marked(i))
                rts.append(rt)
            # one process hosts both workers, but the verdict latch is
            # process-global (one worker per process in production): stop
            # worker 1's monitor so only worker 0's health mirrors it
            await rts[1]._health_monitor.stop()
            fe = await DistributedRuntime.create(ss.url, NO_BUS)
            client = await fe.namespace("sg").component("w").endpoint(
                "gen"
            ).client("round_robin", policy=_policy())
            await client.wait_for_instances(2, timeout=10)
            prefix = f"sg/{straggler.CONTROL_PREFIX}/"
            loop = asyncio.get_running_loop()

            # a FOREIGN worker's key must not latch (the _mine filter)
            await fe.store.put(prefix + "someone-else", b"confirmed")
            await asyncio.sleep(0.3)
            assert straggler.verdict() == straggler.OK

            # this worker's key latches within a health tick
            await fe.store.put(prefix + rts[0].worker_id, b"suspect")
            deadline = loop.time() + 10.0
            while (rts[0]._health_monitor.state != health.SUSPECT
                   and loop.time() < deadline):
                await asyncio.sleep(0.02)
            assert straggler.verdict() == straggler.SUSPECT
            assert rts[0]._health_monitor.state == health.SUSPECT

            # wait for the client's view to flip, then: all new work lands
            # on the brisk sibling
            vids = [
                iid for iid, info in client._instances.items()
                if info.worker_id == rts[0].worker_id
            ]
            assert vids
            deadline = loop.time() + 10.0
            while (not all(client._is_suspect(i) for i in vids)
                   and loop.time() < deadline):
                await asyncio.sleep(0.02)
            assert all(client._is_suspect(i) for i in vids)
            marks[0] = marks[1] = 0
            await _drain(client, 6)
            assert marks == [0, 6], "suspect worker still drew new work"

            # route of last resort: an all-suspect pool must keep serving
            orig = client._is_suspect
            client._is_suspect = lambda i: True
            try:
                toks, errs, _ = await _stream(client, [1, 2, 3], 1)
                assert errs == []
            finally:
                client._is_suspect = orig

            # deletion (arbiter cleared it / lease expired) FAILS OPEN:
            # verdict drops to ok, health recovers, traffic returns
            await fe.store.delete(prefix + rts[0].worker_id)
            deadline = loop.time() + 10.0
            while ((straggler.verdict() != straggler.OK
                    or rts[0]._health_monitor.state != health.HEALTHY
                    or any(client._is_suspect(i) for i in vids))
                   and loop.time() < deadline):
                await asyncio.sleep(0.02)
            assert straggler.verdict() == straggler.OK
            assert rts[0]._health_monitor.state == health.HEALTHY
            marks[0] = marks[1] = 0
            await _drain(client, 6)
            assert marks[0] > 0, "recovered worker never re-entered rotation"

            await client.close()
            for rt in rts + [fe]:
                await rt.shutdown()
            await ss.stop()

        run(go())

    def test_confirmed_fires_bounded_drain_pulse(self, run, monkeypatch):
        """A CONFIRMED verdict fires ONE drain pulse: the worker drains
        (migration coordinator territory) while streams are inflight, then
        UNDRAINS once they're gone — unlike quarantine it stays in the
        pool as the route of last resort."""
        monkeypatch.setenv("DYN_TPU_STRAGGLER", "1")

        class _Dribble(AsyncEngine):
            async def generate(self, request: Context):
                for i in range(20):
                    await asyncio.sleep(0.05)
                    yield Annotated.from_data({"token_ids": [i]})

        async def go():
            ss = StateStoreServer(port=0)
            await ss.start()
            rt = await DistributedRuntime.create(ss.url, NO_BUS)
            ep = rt.namespace("sp").component("w").endpoint("gen")
            await ep.serve(_Dribble())
            fe = await DistributedRuntime.create(ss.url, NO_BUS)
            client = await fe.namespace("sp").component("w").endpoint(
                "gen"
            ).client("round_robin", policy=_policy())
            await client.wait_for_instances(1, timeout=10)
            loop = asyncio.get_running_loop()
            prefix = f"sp/{straggler.CONTROL_PREFIX}/"

            task = asyncio.create_task(_stream(client, [1, 2, 3], 20))
            await asyncio.sleep(0.2)  # stream inflight
            await fe.store.put(prefix + rt.worker_id, b"confirmed")
            deadline = loop.time() + 5.0
            while not rt.draining and loop.time() < deadline:
                await asyncio.sleep(0.02)
            assert rt.draining, "confirmed verdict never fired the pulse"
            assert straggler.verdict() == straggler.CONFIRMED
            toks, errs, _ = await asyncio.wait_for(task, 30)
            assert errs == [] and len(toks) == 20
            # inflight set empty ⇒ the pulse releases the drain source
            deadline = loop.time() + 10.0
            while rt.draining and loop.time() < deadline:
                await asyncio.sleep(0.02)
            assert not rt.draining, "pulse never undrained"
            # still demoted (the verdict stands) until the key clears
            assert straggler.verdict() == straggler.CONFIRMED
            await fe.store.delete(prefix + rt.worker_id)
            deadline = loop.time() + 5.0
            while (straggler.verdict() != straggler.OK
                   and loop.time() < deadline):
                await asyncio.sleep(0.02)
            assert straggler.verdict() == straggler.OK

            await client.close()
            await rt.shutdown()
            await fe.shutdown()
            await ss.stop()

        run(go())


# -- llmctl cluster status -----------------------------------------------------


class TestClusterCli:
    def test_cluster_status_slow_column_and_detail(self, run, monkeypatch,
                                                   capsys):
        """Mock workers → real aggregator → `llmctl cluster status`: the
        per-model line grows slow=N and a SLOW detail line names the
        demoted worker with the recovery contract."""
        from dynamo_tpu.components.mock_worker import MockWorkerStats
        from dynamo_tpu.components.telemetry_aggregator import (
            run_telemetry_aggregator,
        )
        from dynamo_tpu.runtime.bus import MessageBusServer
        from dynamo_tpu.runtime.distributed import KV_METRICS_SUBJECT

        async def go():
            ss = StateStoreServer(port=0)
            bus = MessageBusServer(port=0)
            await ss.start()
            await bus.start()
            drt = await DistributedRuntime.create(ss.url, bus.url)
            pub = await DistributedRuntime.create(ss.url, bus.url)
            ns = pub.namespace("dynamo")
            ready = asyncio.Event()
            agg_task = asyncio.create_task(run_telemetry_aggregator(
                drt, "dynamo", port=0, host="127.0.0.1", ready=ready,
            ))
            await asyncio.wait_for(ready.wait(), 10)
            try:
                workers = [
                    MockWorkerStats(seed=0),
                    MockWorkerStats(
                        seed=1, dispatch_us_per_token=900.0,
                        straggler_state="suspect", health_state="suspect",
                    ),
                    MockWorkerStats(seed=2, dispatch_us_per_token=95.0),
                ]
                for _ in range(3):
                    for i, w in enumerate(workers):
                        w.tick(requests=5)
                        await ns.publish(KV_METRICS_SUBJECT, {
                            "worker_id": f"w{i}",
                            "metrics": w.metrics("tiny-llama").to_dict(),
                        })
                    await asyncio.sleep(0.05)

                from dynamo_tpu.cli.llmctl import amain

                rc = await amain([
                    "--statestore", ss.url, "cluster", "status",
                    "dyn://dynamo.telemetry.status",
                ])
                out = capsys.readouterr().out
                assert rc == 0
                assert "slow=1" in out
                assert "SLOW: w1" in out
                assert "soft-demoted" in out
            finally:
                agg_task.cancel()
                try:
                    await agg_task
                except (asyncio.CancelledError, Exception):
                    pass
                await drt.shutdown()
                await pub.shutdown()
                await bus.stop()
                await ss.stop()

        run(go())

    def test_mock_worker_cli_flags_parse(self):
        """Satellite: the drill flags exist on the mock worker CLI."""
        from dynamo_tpu.components import mock_worker

        stats = mock_worker.MockWorkerStats(
            seed=3, dispatch_us_per_token=450.0, straggler_state="confirmed",
        )
        stats.tick(requests=2)
        m = stats.metrics("m").to_dict()
        assert m["dispatch_us_per_token_ewma"] > 0
        assert m["straggler_samples_total"] > 0
        assert m["straggler_state"] == "confirmed"


# -- THE chaos gate ------------------------------------------------------------


class TestStragglerChaosGate:
    # phases with deadlines of their own (40 + 40 + 20 + 120 s), 40-150 s
    # in all: twelve 192-token streams, four of them behind a slowed engine
    @pytest.mark.timeout(300)
    def test_fail_slow_detected_migrated_recovered(self, tiny, run,
                                                   monkeypatch):
        """ISSUE 18 acceptance, end to end over every real plane: 3 tiny
        engines under 2x load, one slowed ~10x mid-run by the fault
        injector. The aggregator's arbiter convicts it (zero false
        positives before the fault), the control key soft-demotes it, the
        CONFIRMED pulse migrates inflight streams off byte-equal with zero
        recomputed prefill, new admissions avoid it at ~control ITL while
        a stream routed INTO it (the undefended leg) degrades >3x — and
        once the fault lifts, probation decay releases it and the fleet
        re-admits it."""
        monkeypatch.setenv("DYN_TPU_STRAGGLER", "1")
        monkeypatch.setenv("DYN_TPU_STRAGGLER_WINDOW", "0.4")
        monkeypatch.setenv("DYN_TPU_STRAGGLER_FACTOR", "3.0")
        monkeypatch.setenv("DYN_TPU_STRAGGLER_TRIPS", "2")
        monkeypatch.setenv("DYN_TPU_STRAGGLER_MIN_PEERS", "2")
        monkeypatch.setenv("DYN_TPU_HEALTH_CHECK_INTERVAL", "0.1")
        monkeypatch.setenv("DYN_TPU_LOAD_REPORT_INTERVAL", "0.1")

        from dynamo_tpu.components.telemetry_aggregator import (
            run_telemetry_aggregator,
        )
        from dynamo_tpu.runtime import telemetry
        from dynamo_tpu.runtime.bus import MessageBusServer

        WINDOW = 0.4

        async def go():
            straggler.reset_for_tests()
            mig_mod.reset_migration_counters()
            resilience.reset_resume_counters()
            loop = asyncio.get_running_loop()
            ss = StateStoreServer(port=0)
            bus = MessageBusServer(port=0)
            await ss.start()
            await bus.start()
            agg_rt = await DistributedRuntime.create(ss.url, bus.url)
            ready = asyncio.Event()
            agg_task = asyncio.create_task(run_telemetry_aggregator(
                agg_rt, "strag", port=0, host="127.0.0.1", ready=ready,
                register=False,
            ))
            await asyncio.wait_for(ready.wait(), 10)

            rts, engines, coords = [], [], []
            for _ in range(3):
                rt = await DistributedRuntime.create(ss.url, bus.url)
                eng = _engine(tiny, max_slots=2)
                ep = rt.namespace("strag").component("w").endpoint("gen")
                await ep.serve(eng)
                coords.append(await attach_migration(ep, eng))
                await attach_kv_publishing(ep, eng, interval=0.1)
                # one process hosts the whole fleet, but the detector is
                # process-global (one worker per process in production):
                # give each engine its OWN detector so the arbiter sees
                # three distinct EWMA series
                eng._straggler = StragglerDetector()
                rts.append(rt)
                engines.append(eng)
            victim = 0
            # ...and the verdict latch is process-global too: freeze the
            # sibling monitors so only the victim's health plane mirrors it
            # (the test_integrity chaos-gate surgery)
            for i in range(3):
                if i != victim:
                    await rts[i]._health_monitor.stop()
            fe = await DistributedRuntime.create(ss.url, bus.url)
            client = await fe.namespace("strag").component("w").endpoint(
                "gen"
            ).client("round_robin", policy=_policy())
            await client.wait_for_instances(3, timeout=10)

            try:
                # 12 streams on 6 slots: 2x load, and long enough that the healthy
                # peers are still decoding when the windows close (the arbiter
                # judges a window only with both of them fresh in it)
                n_requests, max_t = 12, 192
                prompts = [[17 + i, 23 + 2 * i, 5 + 3 * i]
                           for i in range(n_requests)]
                controls = await _goldens(tiny, prompts, max_t)
                # warm every engine's jit caches off the timed path: the step
                # programs by a request, and the reads of the pages a dispatch
                # fills (engine._take_sealing: one program a block count, and
                # two lanes that seal in one dispatch are a count of their own)
                for i, eng in enumerate(engines):
                    await _collect(eng, [3 + i, 5, 7], 4)
                    for n in eng._sealing_sizes:
                        eng.extract_blocks([0] * n)

                # -- phase 0: no-fault control ITL + zero false positives --
                ctl = await asyncio.gather(*[
                    _timed_stream(client, [41 + 3 * j, 43 + j, 47], 32)
                    for j in range(4)
                ])
                assert all(errs == [] for _, errs, _ in ctl)
                ctl_p95 = _p95([g for _, _, gaps in ctl for g in gaps])
                assert ctl_p95 > 0.0
                await asyncio.sleep(3 * WINDOW)  # let windows close judged
                arb = telemetry.cluster().straggler_arbiter
                assert arb is not None and arb.windows_total >= 1
                assert arb.trips_total == 0 and arb.verdicts() == {}, (
                    "false positive on a uniform fleet"
                )
                assert straggler.verdict() == straggler.OK

                # -- phase A: slow the victim ~10x mid-run under 2x load ---
                # the engine's fault label: attach_migration relabels the
                # engine with its transfer address (migration.py — host-
                # tier/poison drills use the same label), so the slow rule
                # addresses the victim by coordinator address
                # ~10x is relative: on a machine busy with other work the
                # healthy engines' own dispatches stretch, and a fixed 80 ms
                # on top of them sinks under the arbiter's 3x-of-median bar
                slow_by = min(max(0.08, 8 * ctl_p95), 0.25)
                inj = FaultInjector([FaultRule(
                    plane="engine", point="dispatch", action="slow",
                    match_addr=coords[victim].address,
                    delay=slow_by, jitter=slow_by / 4,
                )])
                results = [None] * n_requests

                async def one(i):
                    results[i] = await _stream(client, prompts[i], max_t)

                with faults.active(inj):
                    t_fault = loop.time()
                    tasks = [asyncio.create_task(one(i))
                             for i in range(n_requests)]
                    # suspect: production granularity is one detection
                    # window (sampling + publish + sync + watch latencies on
                    # top); the deadline is the bound — how soon the windows
                    # close is the machine's load, not the defense
                    deadline = t_fault + 40.0
                    while (straggler.verdict() == straggler.OK
                           and loop.time() < deadline):
                        await asyncio.sleep(0.02)
                    arb = telemetry.cluster().straggler_arbiter
                    assert straggler.verdict() != straggler.OK, (
                        f"victim never convicted: {arb.windows_total} "
                        f"windows, {arb.trips_total} trips, us/token "
                        f"{[e._straggler.us_per_token() for e in engines]}"
                    )
                    # TRIPS consecutive windows ⇒ confirmed ⇒ migrate-off
                    deadline = loop.time() + 40.0
                    while (straggler.verdict() != straggler.CONFIRMED
                           and loop.time() < deadline):
                        await asyncio.sleep(0.02)
                    assert straggler.verdict() == straggler.CONFIRMED, (
                        f"{arb.windows_total} windows, {arb.trips_total} "
                        f"trips, slowed by {slow_by:.3f} s, live "
                        f"{[e.live_request_count() for e in engines]}"
                    )
                    # the victim's health plane mirrors the soft state
                    deadline = loop.time() + 5.0
                    while (rts[victim]._health_monitor.state != health.SUSPECT
                           and loop.time() < deadline):
                        await asyncio.sleep(0.02)
                    assert rts[victim]._health_monitor.state == health.SUSPECT

                    await asyncio.wait_for(asyncio.gather(*tasks), 180)

                    # every stream byte-equal to its undisturbed control —
                    # the fault injected latency, never wrong bytes, and
                    # migration carried KV instead of recomputing it
                    failures = [
                        (i, errs) for i, (t_, errs, _) in enumerate(results)
                        if errs
                    ]
                    assert failures == [], (
                        f"client-visible failures: {failures}"
                    )
                    for i, (toks, _, _) in enumerate(results):
                        assert toks == controls[i], f"stream {i} diverged"
                    assert client.stats["migrations"] >= 1, (
                        "no stream ever migrated off the straggler"
                    )
                    m_ok, _, m_blocks = mig_mod.migration_counters()
                    assert m_ok >= 1 and m_blocks > 0
                    for eng in engines:
                        snap = eng.metrics_snapshot()
                        assert snap["resume_recompute_tokens"] == 0, (
                            "migrate-off must be recompute-free"
                        )

                    # -- phase B: new admissions avoid the straggler ------
                    # the tail of phase A can transiently clear the verdict
                    # (a peer adopting a migrated stream jit-compiles fresh
                    # shapes, spiking its EWMA — and the peer median — for
                    # one window). The fault still rages, so unmeasured
                    # probe traffic re-establishes the verdict: any probe
                    # landing on the victim samples slow and the next
                    # window reconvicts
                    deadline = loop.time() + 20.0
                    while (straggler.verdict() == straggler.OK
                           and loop.time() < deadline):
                        pres = await asyncio.gather(*[
                            _stream(client, [83 + j, 29, 31], 8)
                            for j in range(3)
                        ])
                        assert all(errs == [] for _, errs, _ in pres)
                        await asyncio.sleep(0.1)
                    assert straggler.verdict() != straggler.OK, (
                        "defense never re-established under live traffic"
                    )
                    # wait for the ROUTING view to catch up: the client
                    # must see the victim's instances as suspect before the
                    # measured streams launch
                    deadline = loop.time() + 10.0
                    while loop.time() < deadline:
                        vids = [
                            iid for iid, info in client._instances.items()
                            if info.worker_id == rts[victim].worker_id
                        ]
                        if vids and all(
                            client._is_suspect(i) for i in vids
                        ):
                            break
                        await asyncio.sleep(0.05)
                    assert vids and all(
                        client._is_suspect(i) for i in vids
                    ), "client never soft-demoted the convicted worker"
                    v_samples = engines[victim]._straggler.samples_total
                    # other work on the machine only ever ADDS to a gap: the
                    # best of up to three rounds is the defended fleet's own
                    b_p95 = float("inf")
                    for attempt in range(3):
                        if attempt and straggler.verdict() == straggler.OK:
                            break  # starved of samples, the verdict decays
                        bres = await asyncio.gather(*[
                            _timed_stream(
                                client, [61 + 5 * j, 3 + j + attempt, 11], 32
                            )
                            for j in range(4)
                        ])
                        assert all(errs == [] for _, errs, _ in bres)
                        assert (engines[victim]._straggler.samples_total
                                == v_samples), (
                            "a post-verdict admission reached the straggler"
                        )
                        b_p95 = min(b_p95, _p95(
                            [g for _, _, gaps in bres for g in gaps]
                        ))
                        if b_p95 <= 1.5 * ctl_p95 + 0.010:
                            break
                    # defended fleet holds ~control ITL (small absolute pad
                    # absorbs scheduler noise on loaded CI boxes)...
                    assert b_p95 <= 1.5 * ctl_p95 + 0.010, (
                        f"defended p95 ITL {b_p95 * 1e3:.1f}ms vs control "
                        f"{ctl_p95 * 1e3:.1f}ms"
                    )
                    # ...while the undefended leg — a stream routed INTO
                    # the straggler, which is every stream's fate with the
                    # knob off — degrades far past the 3x bar
                    u_toks, u_gaps = await _timed_collect(
                        engines[victim], [71, 73, 79], 16
                    )
                    assert len(u_toks) == 16
                    u_p95 = _p95(u_gaps)
                    assert u_p95 > 3.0 * ctl_p95, (
                        f"undefended p95 ITL {u_p95 * 1e3:.1f}ms vs control "
                        f"{ctl_p95 * 1e3:.1f}ms"
                    )

                # -- phase C: fault lifted ⇒ auto-recovery ----------------
                # recovery is gradual, by design: the victim's EWMA still
                # carries fault-era memory, so each probation-decay release
                # hands it a burst of traffic that drags the average down
                # (with a reconviction flap or two along the way — bounded
                # by the trips ladder). Drive light traffic until the
                # victim's own detector re-enters the differential
                # envelope AND the verdict has cleared.
                v_samples = engines[victim]._straggler.samples_total
                deadline = loop.time() + 120.0
                while loop.time() < deadline:
                    res = await asyncio.gather(*[
                        _stream(client, [5 + j, 91, 8], 8) for j in range(3)
                    ])
                    assert all(errs == [] for _, errs, _ in res)
                    peers = [
                        engines[i]._straggler.us_per_token()
                        for i in range(3) if i != victim
                    ]
                    v = engines[victim]._straggler.us_per_token()
                    if (straggler.verdict() == straggler.OK
                            and v < 3.0 * min(peers)):
                        break
                    await asyncio.sleep(0.1)
                assert straggler.verdict() == straggler.OK, (
                    "verdict never cleared after the fault lifted"
                )
                assert (engines[victim]._straggler.samples_total
                        > v_samples), "recovered worker never served again"
                # converged and cleared ⇒ it STAYS clean: fresh fast
                # samples judged at the next boundaries produce no new
                # conviction, health recovers, the drain source is gone
                sres = await asyncio.gather(*[
                    _stream(client, [7 + j, 93, 9], 8) for j in range(3)
                ])
                assert all(errs == [] for _, errs, _ in sres)
                await asyncio.sleep(3 * WINDOW)
                assert straggler.verdict() == straggler.OK
                deadline = loop.time() + 10.0
                while (rts[victim]._health_monitor.state != health.HEALTHY
                       and loop.time() < deadline):
                    await asyncio.sleep(0.05)
                assert rts[victim]._health_monitor.state == health.HEALTHY
                assert not rts[victim].draining
            finally:
                agg_task.cancel()
                try:
                    await agg_task
                except (asyncio.CancelledError, Exception):
                    pass
                await client.close()
                for rt in rts + [fe]:
                    await rt.shutdown()
                for eng in engines:
                    eng.close()
                await agg_rt.shutdown()
                await bus.stop()
                await ss.stop()

        run(go())
