"""The phase clock (runtime/profiling.py:PhaseClock, PR 39): its arithmetic on
an injected clock, its counters on a tiny engine on the CPU, its spans in the
xplane of a CPU rehearsal, and what it may not cost at start-up. Nothing here
holds a clock's VALUE to anything: only to other readings of the same clock."""

import asyncio
import dataclasses
import json
import logging
import os
import subprocess
import sys

import pytest

from dynamo_tpu.runtime import profiling
from dynamo_tpu.runtime.profiling import (
    ENGINE_PHASES, P_ADMIT, P_ALLOC, P_COMPILE, P_DECODE_BUILD, P_DECODE_DISPATCH,
    P_DECODE_FETCH, P_SEAL_CRC, P_STEP, P_WAIT, PhaseClock,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tools import host_gaps  # noqa: E402


class Ticks:
    """A clock that tests move by hand (seconds)."""

    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now

    def tick(self, us):
        self.now += us / 1e6


class Spans:
    """Stands in for jax.profiler.TraceAnnotation: counts what is opened."""

    opened, open_now = [], []

    def __init__(self, name, **kw):
        self.name = name + (f"#{kw['step_num']}" if "step_num" in kw else "")

    def __enter__(self):
        Spans.opened.append(self.name)
        Spans.open_now.append(self.name)

    def __exit__(self, *exc):
        assert Spans.open_now.pop() == self.name  # spans close innermost first


@pytest.fixture
def ticks():
    Spans.opened, Spans.open_now = [], []
    return Ticks()


def engine_clock(ticks, **kw):
    return PhaseClock(ENGINE_PHASES, "engine.", Spans, Spans, clock=ticks, **kw)


def us_of(clock):
    return {n: round(v) for n, v in zip(clock.names, clock.us) if round(v)}


# -- the arithmetic ---------------------------------------------------------------


def test_a_phases_counter_is_its_interval_less_its_childrens(ticks):
    clock = engine_clock(ticks)
    with clock.step(7):
        ticks.tick(5)
        with clock(P_ADMIT):
            ticks.tick(10)
            with clock(P_ALLOC):
                ticks.tick(30)
            ticks.tick(2)
            with clock(P_ALLOC):  # entered again: the same counter
                ticks.tick(4)
        ticks.tick(1)
    assert us_of(clock) == {"step": 6, "admit": 12, "alloc": 34}
    assert Spans.opened == ["engine.step#7", "engine.admit", "engine.alloc", "engine.alloc"]
    assert not Spans.open_now


def test_a_phase_inside_itself_and_an_exception_inside_a_phase(ticks):
    clock = engine_clock(ticks)
    with pytest.raises(KeyError):
        with clock.step(1):
            with clock(P_SEAL_CRC):
                ticks.tick(3)
                with clock(P_SEAL_CRC):  # nested in itself: still its self time, once
                    ticks.tick(4)
                    raise KeyError("inside")
    # every phase was left on the way out, innermost first
    assert not Spans.open_now and clock._stack == [] and clock._cur == P_STEP
    with clock(P_ADMIT):
        ticks.tick(2)
    assert us_of(clock) == {"seal_crc": 7, "admit": 2}


def test_the_counters_add_up_to_the_time_since_the_thread_started(ticks):
    clock = engine_clock(ticks)
    ticks.tick(1_000_000)  # built long before its thread runs
    clock.start()
    for n in range(3):
        with clock.step(n):
            ticks.tick(7)
            with clock(P_WAIT):
                ticks.tick(500)
            with clock(P_ADMIT):
                ticks.tick(11)
        ticks.tick(2)  # between two steps: the root's
    with clock.step(3), clock(P_DECODE_BUILD):
        ticks.tick(40)  # a snapshot from another thread, mid-phase
        snap = clock.snapshot()
    assert snap["uptime_us"] == 3 * 520 + 40
    assert sum(snap["host_phase_us"].values()) == snap["uptime_us"]
    assert snap["host_phase_us"]["decode_build"] == 40 and snap["host_phase_us"]["step"] == 27
    assert set(snap["host_phase_us"]) == set(snap["host_starved_us"]) == set(ENGINE_PHASES)
    json.dumps(snap)  # as /debug/engine sends it


def test_starved_time_is_time_with_nothing_in_flight_and_a_slot_active(ticks):
    clock = engine_clock(ticks)
    with clock.step(1):
        with clock(P_DECODE_BUILD):
            ticks.tick(50)  # no slot active yet: nobody starves
        clock.active = True
        with clock(P_DECODE_BUILD):
            ticks.tick(20)  # starved: a request waits and the device has nothing
        with clock(P_DECODE_DISPATCH):
            ticks.tick(6)  # the call itself: still nothing in flight
            clock.dispatched(1)
            ticks.tick(1)  # ... and from here one is
        assert clock.in_flight == 1
        with clock(P_SEAL_CRC):
            ticks.tick(30)  # the device works meanwhile
        with clock(P_DECODE_FETCH):
            ticks.tick(9)  # waiting for it
            clock.fetched(1)
            ticks.tick(2)  # read back: nothing in flight again
        with clock(P_WAIT):
            ticks.tick(1000)  # parked: never starved
    assert clock.in_flight == 0
    assert {n: round(v) for n, v in zip(clock.names, clock.starved_us) if round(v)} == {
        "decode_build": 20, "decode_dispatch": 6, "decode_fetch": 2}
    assert round(clock.device_us[1]) == 6 + 1 + 30 + 9  # its dispatch phase began -> its read returned
    clock.fetched(1)  # a read nobody dispatched moves nothing
    assert clock.in_flight == 0


def test_a_stall_is_a_long_stretch_outside_wait_named_once(ticks, caplog):
    clock = engine_clock(ticks, stall_s=0.5)
    with caplog.at_level(logging.WARNING, logger="dynamo_tpu.runtime.profiling"):
        with clock.step(41):
            with clock(P_WAIT):
                ticks.tick(3_000_000)  # parked for three seconds: no stall
            with clock(P_DECODE_FETCH):
                ticks.tick(700_000)
            with clock(P_ADMIT):
                ticks.tick(499_000)
                with clock(P_ALLOC):
                    ticks.tick(2_100_000)
    assert clock.stall == {"count": 2, "longest_ms": 2100.0, "phase": "alloc", "step": 41}
    assert [r.getMessage() for r in caplog.records] == [
        "host stall: 700 ms in engine.decode.fetch at step 41",
        "host stall: 2100 ms in engine.alloc at step 41"]


def test_a_program_built_on_the_served_path_is_a_compile_span_with_its_key(ticks, caplog):
    clock = engine_clock(ticks)
    clock.compile_key = "decode lp=True pen=False sample=False [S=4,k=1]"
    with caplog.at_level(logging.WARNING, logger="dynamo_tpu.runtime.profiling"):
        with clock(P_DECODE_DISPATCH if clock.compile_key is None else P_COMPILE):
            ticks.tick(9)
        with clock(P_DECODE_DISPATCH if clock.compile_key is None else P_COMPILE):
            ticks.tick(1)
    assert Spans.opened == ["engine.compile:decode lp=True pen=False sample=False [S=4,k=1]",
                            "engine.decode.dispatch"]
    assert us_of(clock) == {"compile": 9, "decode_dispatch": 1}
    assert "compiles on the served path" in caplog.records[0].getMessage()


def test_a_start_up_is_phases_in_a_row_and_takes_time_measured_elsewhere(ticks):
    setup = PhaseClock(profiling.SETUP_PHASES, "setup.", clock=ticks)
    setup.credit(profiling.S_BEFORE_MAIN, 4_000_000)
    setup.switch(profiling.S_DEVICES)  # no jax yet: no span
    ticks.tick(3_000_000)
    setup.annotate = Spans
    setup.switch(profiling.S_WEIGHTS)
    ticks.tick(2_000_000)
    setup.switch(profiling.S_COMPILE)
    ticks.tick(10_000_000)
    setup.credit(profiling.S_LOWER, 6_000_000, out_of=profiling.S_COMPILE)  # the pool's threads measured it
    setup.switch(None)
    setup.switch(None)  # ending twice is ending once
    ticks.tick(9_000_000)  # served from here on: nobody's
    assert us_of(setup) == {"before_main": 4_000_000, "devices": 3_000_000, "weights": 2_000_000,
                            "lower": 6_000_000, "compile": 4_000_000}
    assert Spans.opened == ["setup.weights", "setup.compile"] and not Spans.open_now


def test_the_process_knows_its_age():
    age = profiling.process_age_us()
    assert age is None or 0 < age < 3600e6  # this very test run, not the machine's uptime


# -- on a tiny engine ---------------------------------------------------------------


def tiny_engine(**cfg):
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
    from dynamo_tpu.models.llama import LLAMA_PRESETS, init_params

    model = dataclasses.replace(LLAMA_PRESETS["tiny"], dtype=jnp.float32)
    return JaxServingEngine(model, init_params(jax.random.PRNGKey(0), model), EngineConfig(
        **{"max_slots": 4, "kv_block_size": 8, "max_model_len": 128, "prefill_chunk": 16, **cfg}))


async def serve(eng, prompt, n):
    from dynamo_tpu.llm.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions
    from dynamo_tpu.runtime.engine import Context

    req = PreprocessedRequest(
        token_ids=prompt, stop_conditions=StopConditions(max_tokens=n, ignore_eos=True),
        sampling_options=SamplingOptions())
    return [item async for item in eng.generate(Context(req))]


def test_the_engines_counters_add_up_and_nothing_stays_in_flight():
    eng = tiny_engine()
    try:
        async def six():
            return await asyncio.gather(*[
                serve(eng, [(5 * i + j) % 90 + 1 for i in range(20 + j)], 12 + j) for j in range(6)])

        assert all(asyncio.run(six()))
        snap = eng.metrics_snapshot()
        assert eng._clock.in_flight == 0 and eng._inflight is None
    finally:
        eng.close()
    total = sum(snap["host_phase_us"].values())
    # a snapshot is read from another thread: a stretch may tear, nothing more
    assert abs(total - snap["uptime_us"]) <= 0.02 * snap["uptime_us"]
    for phase in ("admit", "alloc", "prepare", "chunk_build", "chunk_fetch", "decode_build", "decode_emit"):
        assert snap["host_phase_us"][phase] > 0, phase
    assert snap["host_starved_us"]["wait"] == 0
    assert all(snap["host_starved_us"][p] <= snap["host_phase_us"][p] + 1 for p in ENGINE_PHASES)
    # six requests on four slots: each admitted once, two of them after a wait for a slot
    assert snap["queue_wait_count"] == 6 and snap["queue_wait_us_sum"] > 0
    assert snap["host_steps"]["prefill"] >= 2 and snap["host_steps"]["decode"] >= 10
    assert snap["prefix_probe_tokens"] == sum(20 + j for j in range(6))


def test_in_flight_follows_the_dispatches_and_a_drain_empties_it():
    """The engine stepped on the test's thread: one dispatch in flight after a
    decode step, none after ``_drain_inflight``; starved time rises only while
    none is in flight and a slot is active."""
    from dynamo_tpu.llm.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions
    from dynamo_tpu.engine_jax.engine import _Seq
    from dynamo_tpu.runtime.engine import Context

    eng = tiny_engine()
    clock = eng._clock
    try:
        req = PreprocessedRequest(
            token_ids=[(7 * i + 3) % 90 + 1 for i in range(11)],
            stop_conditions=StopConditions(max_tokens=30, ignore_eos=True), sampling_options=SamplingOptions())
        loop = asyncio.new_event_loop()
        eng._pending.append(_Seq(Context(req), req, loop))
        starved = lambda: sum(clock.starved_us)  # noqa: E731
        eng._admit()
        assert starved() == 0 and clock.in_flight == 0  # no slot was active yet
        clock.active = any(eng._slots)
        eng._dispatch_step()  # the prompt's chunk: dispatched and read back in one step
        assert clock.in_flight == 0 and clock.steps == [1, 0]
        s0 = starved()
        assert s0 > 0  # it was built while a request waited and nothing ran
        eng._dispatch_step()  # decode dispatch 1 stays in flight
        assert clock.in_flight == 1 and eng._inflight is not None
        s1 = starved()
        clock._charge()  # time passes with one in flight: nobody starves
        assert starved() == s1 > s0
        eng._dispatch_step()  # dispatch 2 goes out before 1 is read back
        assert clock.in_flight == 1 and clock.steps == [1, 2]
        eng._drain_inflight()
        assert clock.in_flight == 0 and eng._inflight is None
        s2 = starved()
        clock._charge()  # ... and with none in flight every stretch starves
        assert starved() > s2
        assert clock.us[profiling.P_DRAIN] > 0 and clock.device_us[1] > 0
        loop.close()
    finally:
        eng.close()


# -- what start-up may not pay ---------------------------------------------------


def test_construction_and_warmup_open_the_setup_spans_and_none_a_program(monkeypatch):
    """The TraceAnnotation constructor counts: building the engine opens none
    (one clock is built, no phase, no span object), ``warmup`` opens the
    start-up phases it times and nothing per program or per phase name."""
    import jax

    opened = []

    class Counting:
        def __init__(self, name, **kw):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", Counting)
    eng = tiny_engine()
    try:
        assert opened == [] and eng._clock.annotate is Counting
        timings = eng.warmup()
        assert len(timings) >= 7  # seven or more programs were lowered and compiled
        assert opened == ["setup.compile"]
        assert eng._clock.compile_key is None  # what warmup built is not a compile on the served path
        phases = eng.metrics_snapshot()["setup_phase_s"]
        assert set(phases) == set(profiling.SETUP_PHASES)
        assert phases["lower"] > 0 and phases["compile"] >= 0 and phases["sealing"] >= 0
        # the step programs are what warmup compiled, unwrapped: served straight off them
        assert all(not hasattr(fn, "lower") for fn in eng._decode_fns.values())
        assert asyncio.run(serve(eng, [3, 1, 4, 1, 5, 9, 2, 6], 5))
        assert "engine.step" in opened and not [n for n in opened if n.startswith("engine.compile")]
    finally:
        eng.close()


def test_importing_the_profiling_module_loads_no_jax():
    done = subprocess.run(
        [sys.executable, "-c",
         "import sys; import dynamo_tpu.runtime.profiling as p; "
         "c = p.PhaseClock(p.ENGINE_PHASES, 'engine.'); c.snapshot(); p.setup_clock(); "
         "print(sorted(m for m in sys.modules if m.split('.')[0] in ('jax', 'jaxlib', 'numpy')))"],
        cwd=ROOT, capture_output=True, text=True, timeout=60)
    assert done.returncode == 0, done.stderr[-1500:]
    assert done.stdout.strip() == "[]"


# -- the reader of the trace ------------------------------------------------------


def test_nested_spans_become_stretches_named_by_the_innermost():
    spans = [["engine.step", 0, 100], ["engine.admit", 10, 30], ["engine.alloc", 15, 10],
             ["engine.decode.build", 50, 20], ["engine.step", 110, 40]]
    assert host_gaps.innermost_segments(spans) == [
        ["engine.step", 0, 10], ["engine.admit", 10, 15], ["engine.alloc", 15, 25], ["engine.admit", 25, 40],
        ["engine.step", 40, 50], ["engine.decode.build", 50, 70], ["engine.step", 70, 100],
        ["engine.step", 110, 150]]


def test_the_join_names_each_gap_and_adds_idle_time_by_span():
    ms = 1_000_000
    spans = [["engine.step", 0, 100 * ms], ["engine.decode.build", 10 * ms, 30 * ms],
             ["PjitFunction(convert_element_type)", 12 * ms, 2 * ms],
             ["engine.decode.dispatch", 40 * ms, 2 * ms], ["engine.decode.emit", 60 * ms, 35 * ms],
             ["engine.seal.crc", 70 * ms, 10 * ms]]
    modules = [["jit_decode(1)", 0, 20 * ms], ["jit_decode(1)", 41 * ms, 24 * ms],
               ["jit_chunk(2)", 90 * ms, 5 * ms], ["jit_chunk(2)", 95 * ms + 1000, ms]]
    got = host_gaps.join(spans, modules)
    assert [(g["span"], round(g["gap_s"] * 1e3, 3), g["before"]) for g in got["gaps"]] == [
        ("engine.decode.emit", 25.0, "jit_chunk"), ("engine.decode.build", 21.0, "jit_decode"),
        ("engine.step", 0.001, "jit_chunk")]
    assert got["gaps"][1]["span_share"] == pytest.approx(20 / 21)
    idle = {k: round(v * 1e3, 3) for k, v in got["idle_s_by_span"].items()}
    assert idle == {"engine.decode.build": 20.0, "engine.decode.emit": 15.0, "engine.seal.crc": 10.0,
                    "engine.decode.dispatch": 1.0, "engine.step": 0.001}
    # of the idle time in gaps over 1 ms, all of it has a name other than the root's
    assert got["idle_s_in_long_gaps"] == pytest.approx(0.046) and got["named_share"] == pytest.approx(1.0)
    per = got["per_span"]
    assert per["engine.decode.emit"] == {"count": 1, "total_s": pytest.approx(0.035), "self_s": pytest.approx(0.025)}
    assert per["PjitFunction(convert_element_type)"]["inside"] == "engine.decode.build"
    # module event k starts inside or after dispatch span k, once the one dispatched before the trace is passed over
    assert got["one_clock"]["jit_decode"] == {"skip": 1, "pairs": 1, "min_lag_us": 1000.0, "median_lag_us": 1000.0}
    assert got["one_clock"]["jit_chunk"] is None


SERVED_SPANS = {"engine.admit", "engine.prepare", "engine.decode.build", "engine.decode.dispatch",
                "engine.decode.emit", "engine.chunk.build", "engine.chunk.dispatch", "engine.chunk.fetch",
                "engine.seal.read", "engine.seal.crc"}


def test_a_traced_engines_xplane_holds_its_spans_on_the_device_events_clock(tmp_path):
    """A tiny engine stepped on the test's thread under the profiler: two
    prompts, one of two chunks, until nothing is left. The test makes the N
    host steps itself, so the xplane's host plane holds exactly N
    ``engine.step`` spans and a dispatch span for every dispatch the clock
    counted; every event of XLA's CPU client (the "device" of a CPU run)
    starts between the first step's start and the last step's end, and
    ``tools/host_gaps.py`` puts every gap between them under an ``engine.*``
    span. Counts by construction: no window of wall-clock time is sampled.
    A CPU run: no device number."""
    import jax

    from .step_programs import busy, submit

    eng = tiny_engine()
    clock = eng._clock
    try:
        eng.warmup()
        clock.start()
        options = jax.profiler.ProfileOptions()  # as benchmark/server_child.py asks for its trace
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            for n_prompt, n_answer in ((11, 9), (27, 6)):  # prefill_chunk is 16: one chunk, two chunks
                submit(eng, [(7 * i + 3) % 90 + 1 for i in range(n_prompt)], n_answer)
            n_steps = 0
            while busy(eng):
                assert not eng._host_step(clock)
                n_steps += 1
        finally:
            jax.profiler.stop_trace()
        dispatched = list(clock.steps)
    finally:
        eng.close()
    spans, modules = host_gaps.read(host_gaps.find_xplane(str(tmp_path)), device="CPU")
    steps = [e for e in spans if e[0] == host_gaps.ROOT_SPAN]
    assert len(steps) == n_steps and modules
    assert SERVED_SPANS <= {e[0] for e in spans}
    count = lambda name: sum(1 for e in spans if e[0] == name)  # noqa: E731
    # the clock counts host steps with a chunk dispatch, and those with a decode dispatch alone
    with_chunk, decode_alone = dispatched
    assert count("engine.chunk.dispatch") == with_chunk >= 1 and decode_alone >= 2
    assert decode_alone <= count("engine.decode.dispatch") <= with_chunk + decode_alone <= n_steps
    # one clock: nothing ran on the "device" but between the steps' two ends
    lo, hi = min(e[1] for e in steps), max(e[1] + e[2] for e in steps)
    assert [e for e in modules if not lo <= e[1] <= hi] == []
    report = host_gaps.join(spans, modules, n=len(modules))
    assert report["gaps"] and report["idle_s"] > 0
    # the steps follow one another on one thread: all that no span covers of the
    # stretch is what lies between one step's end and the next one's start, so
    # a gap has an engine.* span's name or sits in there, and no more idle time
    # goes unnamed than those slivers hold
    in_turn = sorted(steps, key=lambda e: e[1])
    between_ns = sum(b[1] - (a[1] + a[2]) for a, b in zip(in_turn, in_turn[1:]))
    for gap in report["gaps"]:
        if not gap["span"].startswith("engine."):
            assert gap["span"] == "(no span)", gap
            assert gap["gap_s"] * gap["span_share"] * 1e9 <= between_ns + 1, gap
    assert report["idle_s_by_span"].get("(no span)", 0.0) * 1e9 <= between_ns + 1


@pytest.mark.slow  # a whole benchmark rehearsal in a subprocess: its timing follows the machine's load
@pytest.mark.timeout(400)
def test_a_rehearsals_xplane_holds_the_engines_spans_on_the_device_events_clock(tmp_path):
    """``run.py --rehearse --trace 1`` from a checkout of links (its scratch
    directory is its own, so no other rehearsal of the cell is in its way): the
    xplane's host plane holds ``engine.step`` spans that overlap, in time, the
    events of XLA's CPU client (the "device" of a rehearsal), and
    ``tools/host_gaps.py`` gives every gap a span. A CPU run: no device number.
    What it asserts is what a 5 s window happened to hold, so it runs beside
    the tier-1 tests no longer; the test above holds the same join to counts
    the test makes itself."""
    for name in ("benchmark", "dynamo_tpu", "BENCHMARK.json"):
        os.symlink(os.path.join(ROOT, name), tmp_path / name)
    done = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"), "--workload", "batch.qwen2.5-1.5b",
         "--seed", "2147483789", "--seconds", "5", "--trace", "1", "--rehearse"],
        cwd=tmp_path, capture_output=True, text=True, timeout=380)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads([x for x in done.stdout.splitlines() if x.startswith("{")][-1])
    assert line["correct"] and line["metrics"] == {}
    path = host_gaps.find_xplane(str(tmp_path / ".bench_runs" / "batch.qwen2.5-1.5b" / "trace"))
    spans, modules = host_gaps.read(path, device="CPU")
    steps = [e for e in spans if e[0] == "engine.step"]
    assert len(steps) >= 3 and modules
    names = {e[0] for e in spans}
    assert SERVED_SPANS <= names
    # one clock: the steps and the device's events cover the same stretch of it
    lo, hi = min(e[1] for e in steps), max(e[1] + e[2] for e in steps)
    inside = [e for e in modules if lo <= e[1] <= hi]
    assert len(inside) >= 0.5 * len(modules)
    report = host_gaps.join(spans, modules)
    assert report["gaps"] and report["idle_s"] > 0
    first, last = min(e[1] for e in modules), max(e[1] + e[2] for e in modules)
    for gap in report["gaps"]:
        at = first + gap["at_s"] * 1e9
        if lo <= at and at + gap["gap_s"] * 1e9 <= hi:  # a gap the traced steps cover
            assert gap["span"].startswith("engine."), gap
    assert first < hi and lo < last
