"""What PR 56 brought: the answer held against the plain reference waits for
its programs (``client.probe(..., timeout_s=)``: the server's start-up patience
and not the 120 s of a request whose programs are warm), the ``info`` line says
what that answer cost (``run.reference_probe_report``), and a probe that gave
no answer fails by that name, with no reference child started. A local
``aiohttp`` server that answers late stands in the server's place: no engine,
no JAX compile.
"""

import asyncio
import inspect
import json
import os
import sys

import pytest
from aiohttp import web

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import client  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.serving import free_port  # noqa: E402

ANSWERS_AFTER_S = 0.3
PROMPT, WORDS = "w001 w002 w003", ["w010", "w011"]


async def _late_completions(request):
    """``/v1/completions`` as the server streams it, ``ANSWERS_AFTER_S`` late
    (a program that compiles on the served path)."""
    body = await request.json()
    await asyncio.sleep(ANSWERS_AFTER_S)
    resp = web.StreamResponse(headers={"Content-Type": "text/event-stream"})
    await resp.prepare(request)
    for i, word in enumerate(WORDS):
        choice = {"text": " " + word, "finish_reason": "length" if i + 1 == len(WORDS) else None}
        if body.get("logprobs"):
            choice["logprobs"] = {"top_logprobs": [{" " + word: -0.5}]}
        await resp.write(b"data: " + json.dumps({"choices": [choice]}).encode() + b"\n\n")
    usage = {"prompt_tokens": len(body["prompt"].split()), "completion_tokens": body["max_tokens"]}
    await resp.write(b"data: " + json.dumps({"choices": [], "usage": usage}).encode() + b"\n\n")
    await resp.write(b"data: [DONE]\n\n")
    return resp


def probe_the_late_server(**kwargs) -> dict:
    async def main():
        app = web.Application()
        app.router.add_post("/v1/completions", _late_completions)
        runner = web.AppRunner(app)
        await runner.setup()
        port = free_port()
        try:
            await web.TCPSite(runner, "127.0.0.1", port).start()
            return await client.probe(port, "m", PROMPT, len(WORDS), 20, **kwargs)
        finally:
            await runner.cleanup()
    return asyncio.run(main())


def snapshot(compile_us, recompiles):
    snap = {"jit_recompiles": recompiles}
    if compile_us is not None:
        snap["host_phase_us"] = {"compile": compile_us, "wait": 7}
    return snap


def test_the_probes_default_patience_is_the_120_s_of_a_request_whose_programs_are_warm():
    assert inspect.signature(client.probe).parameters["timeout_s"].default == 120.0


@pytest.mark.parametrize("timeout_s, ok", [(0.1, False), (2.0, True)], ids=["gives_up", "waits"])
def test_a_probe_waits_as_long_as_it_is_given(timeout_s, ok):
    rec = probe_the_late_server(timeout_s=timeout_s)
    assert rec["ok"] is ok
    if ok:
        assert rec["text"] == " w010 w011" and rec["first_s"] >= ANSWERS_AFTER_S and "error" not in rec
        assert rec["top_logprobs"] == [{" w010": -0.5}, {" w011": -0.5}]
    else:
        assert rec["error"].startswith("TimeoutError") and "text" not in rec


@pytest.mark.parametrize("after, probed, compile_s, recompiles", [
    # the program keeps the engine thread's clock: its self time under engine.compile, in seconds
    (snapshot(2_000_000, 8), snapshot(105_000_000, 10), 103.0, 2),
    # every program in the cache and compiled before: nothing rose, and that is a reading
    (snapshot(2_000_000, 8), snapshot(2_000_000, 8), 0.0, 0),
    # a program without host_phase_us: nothing to read, never 0
    (snapshot(None, 8), snapshot(None, 10), None, 2),
    (snapshot(None, 8), {}, None, None),
], ids=["compiled", "nothing_rose", "no_phase_clock", "no_snapshot"])
def test_the_info_line_says_what_the_answer_held_against_the_reference_cost(after, probed, compile_s, recompiles):
    rec = {"ok": True, "first_s": 104.5}
    assert bench_run.reference_probe_report(rec, 106.25, 900, after, probed) == {
        "seconds": 106.25, "first_token_s": 104.5, "timeout_s": 900,
        "compile_s": compile_s, "jit_recompiles": recompiles}


def test_a_probe_that_gave_no_answer_fails_by_that_name_and_starts_no_reference_child():
    """From the clock to the reason: the late server, a patience under its
    delay, and what ``run.py`` makes of that record."""
    def never(answers):
        pytest.fail("a probe that gave no answer was sent to the reference child")

    long_probe = dict(probe_the_late_server(timeout_s=0.1), prompt=PROMPT)
    report = bench_run.reference_probe_report(long_probe, 0.1, 0.1, snapshot(0, 8), snapshot(90_000, 8))
    assert report["first_token_s"] is None and report["compile_s"] == 0.09
    verdict = bench_run.reference_verdict(long_probe, report, never)
    assert verdict["agrees"] is False and verdict["no_answer"].startswith("TimeoutError")
    not_correct_because = bench_run.reference_faults(verdict)
    assert len(not_correct_because) == 1 and "disagrees" not in not_correct_because[0]
    for said in ("gave no answer", "TimeoutError", "after 0.1 s of the 0.1 s", "compile_s 0.09"):
        assert said in not_correct_because[0], said
    compared = bench_run.numbers_compared(
        [{"text": "a"}] * 3, {"failed": 0}, [8, 8], {"memory_peak_bytes": 2}, 1.0, verdict)
    assert compared["reference_probe_answered"] == [0, 1]
    assert compared["logprob_pairs_at_least"] == [None, bench_run.REFERENCE_PAIRS_FLOOR]


def test_a_refused_answer_is_no_answer_either():
    """HTTP 200 and a stream that ends short of ``max_tokens``: no error text,
    so the reason says what came."""
    short = {"ok": False, "status": 200, "finish_reason": "stop", "usage": {"completion_tokens": 3}}
    report = bench_run.reference_probe_report(short, 1.5, 900, {}, {})
    verdict = bench_run.reference_verdict(short, report, lambda answers: pytest.fail("sent to the reference"))
    assert "finish_reason 'stop'" in verdict["no_answer"] and "after 1.5 s of the 900 s" in verdict["no_answer"]


def test_an_answered_probe_goes_to_the_reference_and_is_counted_as_answered():
    sent = []

    def against_reference(answers):
        sent.append(answers)
        return [{"agrees": True, "tokens": 2, "argmax_matches": 2, "worst_gap": 0.0, "tolerance": 0.3,
                 "logprob_rms": 0.001, "logprob_rms_limit": 0.016, "logprob_pairs": 480}]

    long_probe = dict(probe_the_late_server(timeout_s=2.0), prompt=PROMPT)
    verdict = bench_run.reference_verdict(long_probe, {"seconds": 0.4}, against_reference)
    assert sent == [[long_probe]] and "no_answer" not in verdict
    assert bench_run.reference_faults(verdict) == []
    compared = bench_run.numbers_compared(
        [{"text": "a"}] * 3, {"failed": 0}, [8, 8], {"memory_peak_bytes": 2}, 1.0, verdict)
    assert compared["reference_probe_answered"] == [1, 1] and compared["logprob_pairs_at_least"] == [480, 456]


def test_only_the_reference_probe_is_given_another_patience():
    """Of the probes ``run.py`` sends, the three greedy ones keep the default;
    the long one takes the configuration's start-up patience, which every
    configuration states and which is more than a warm request's."""
    with open(os.path.join(ROOT, "benchmark", "run.py")) as f:
        source = f.read()
    assert source.count("timeout_s=") == 1 and "timeout_s=go.ready_timeout_s" in source
    with open(os.path.join(ROOT, "benchmark", "client.py")) as f:
        assert "total=120" not in f.read()
    for name in sorted(os.listdir(os.path.join(ROOT, "benchmark", "configs"))):
        with open(os.path.join(ROOT, "benchmark", "configs", name)) as f:
            assert json.load(f)["serving"]["ready_timeout_s"] > 120.0, name
