#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX while the server lives. It writes the model
directory, starts ONE child (``server_child.py``: ``dynamo_tpu.cli.run.amain``
``in=http out=jax``), waits for ready, sends two probes, runs pre-roll, window
and drain over HTTP, reads the counters, sends a third probe, stops the child
and prints one JSON line. A traced run then holds one more greedy answer against
the plain reference (``reference_child.py``, a second short-lived child); that
request compiles its programs on the served path, so it is given the server's
start-up patience (``serving.ready_timeout_s``) and the ``info`` line says what
it cost (``reference_probe``). Without a TPU named by the child the run fails
(``--rehearse`` relaxes that, at a tiny width, and prints no device metric).
See README.md for the files a cell, a configuration, a generator and a metric
are made of.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib.util
import json
import os
import random
import shutil
import subprocess
import sys
import time

import aiohttp

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
sys.path.insert(0, ROOT)

from benchmark import bytes_and_flops, client, serving, stats, trace_reduce, traffic  # noqa: E402
from benchmark.serving import BenchFailure, check  # noqa: E402

# under one KV block, so that all three probes take the same path (no prefix hit)
PROBE_PROMPT_TOKENS, PROBE_OUTPUT_TOKENS = 12, 16
# the answer held against the reference: two prefill chunks (the second reads
# history) and six decode dispatches
REFERENCE_PROMPT_TOKENS, REFERENCE_OUTPUT_TOKENS = 150, 24
# ... with the log-probabilities of its 20 likeliest tokens at every position
# (the most the server gives): 480 numbers to hold against the reference's
REFERENCE_TOP_LOGPROBS = 20
# ... of which this many pairs at least must be held: a pair whose word the
# tokenizer's table does not hold (a special token decodes to no text) is left
# out, and an answer that loses more than a twentieth of its pairs proves little
REFERENCE_PAIRS_FLOOR = 456
TRACE_AT_S, TRACE_FOR_S = 5.0, 4.0
FOLDERS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_readers(kind: str) -> dict:
    """``<kind>/<name>.py`` -> module, by listing the directory."""
    found = {}
    folder = os.path.join(HERE, kind)
    for fname in sorted(os.listdir(folder)):
        if not fname.endswith(".py") or fname.startswith("_"):
            continue
        spec = importlib.util.spec_from_file_location(
            f"benchmark_{kind}_{fname[:-3].replace('.', '_')}", os.path.join(folder, fname))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        found[mod.NAME] = mod
    return found


def registered(bench: dict, kind: str, cell_name: str) -> list:
    """The metrics of ``kind`` that BENCHMARK.json registers for this cell."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def read_metrics(bench: dict, kind: str, cell_name: str, ctx: dict) -> dict:
    readers, out = load_readers(FOLDERS[kind]), {}
    for entry in registered(bench, kind, cell_name):
        check(entry["name"] in readers,
              f"BENCHMARK.json registers {entry['name']!r} but {FOLDERS[kind]}/ has no reader of it")
        value = readers[entry["name"]].read(ctx)
        if value is not None:  # nothing to read: the metric is left out
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


async def sample_engine(clock, stop, session, port: int, seconds: float, into: list):
    """``GET /debug/engine`` at 2 Hz over the window: the whole snapshot of
    each sample, so that a reader takes the rise of any counter the program
    keeps between the window's first and last sample (``counters.py``)."""
    url = f"http://127.0.0.1:{port}/debug/engine"
    while not stop.is_set() and clock() < seconds:
        if clock() >= 0.0:
            try:
                async with session.get(url) as resp:
                    snap = await resp.json()
                into.append(snap | {"t": clock()})
            except (aiohttp.ClientError, asyncio.TimeoutError, ValueError):
                pass  # a sample lost is a sample lost
        await asyncio.sleep(0.5)


async def sample_cpu(clock, stop, session, child, seconds: float, into: dict):
    """The child's CPU seconds at both ends of the window."""
    await asyncio.sleep(max(0.0, -clock()))
    into["start"] = child.cpu_seconds()
    await asyncio.sleep(max(0.0, seconds - clock()))
    into["end"] = child.cpu_seconds()


async def trigger_trace(clock, stop, session, trace_dir: str, seconds: float):
    at = min(TRACE_AT_S, seconds * 0.3)
    length = min(TRACE_FOR_S, seconds * 0.4)
    await asyncio.sleep(max(0.0, at - clock()))
    open(os.path.join(trace_dir, "start"), "w").close()
    await asyncio.sleep(length)
    open(os.path.join(trace_dir, "stop"), "w").close()


def wait_for_xplane(trace_dir: str, alive, deadline_s: float = 90.0, still_s: float = 2.0,
                    poll_s: float = 0.1, clock=time.monotonic, sleep=time.sleep) -> bool:
    """Wait until the child's trace can be taken: its ``done`` is there, or
    ``trace_reduce.find_xplane`` names a file whose size has not changed for
    ``still_s`` (this process reads the xplane alone; whatever the child's
    profiler converts after writing it is killed unread). False once
    ``deadline_s`` have passed or the child has exited (``alive()`` is false)
    without either."""
    start, seen = clock(), None  # seen: (path, size, since when)
    while not os.path.exists(os.path.join(trace_dir, "done")):
        now = clock()
        path = trace_reduce.find_xplane(trace_dir)
        size = os.path.getsize(path) if path else 0
        if size and seen and seen[:2] == (path, size):
            if now - seen[2] >= still_s:
                return True
        else:
            seen = (path, size, now)
        if now - start > deadline_s or not alive():
            return False
        sleep(poll_s)
    return True


def trace_seconds(trace_dir: str, taken_at: float) -> dict:
    """The ``info`` line's account of the trace's export, in seconds after the
    child was told to stop tracing (the mtime of ``stop``): when the xplane
    was last written, when ``done`` was (None: the child never got there), and
    when this process took the trace."""
    stop_at = os.path.getmtime(os.path.join(trace_dir, "stop"))
    path, done = trace_reduce.find_xplane(trace_dir), os.path.join(trace_dir, "done")
    return {"trace_taken_s": taken_at - stop_at,
            "xplane_written_s": os.path.getmtime(path) - stop_at if path else None,
            "done_s": os.path.getmtime(done) - stop_at if os.path.exists(done) else None}


def reference_case(answer: dict, word_id: dict):
    """One answer (``client.probe``'s record with ``prompt``) as the case
    ``reference_child.py`` reads, and the answered words the tokenizer's table
    does not hold (an answer with any cannot be teacher-forced: it fails by
    name). A pair of ``top_logprobs`` whose word is not in the table (a
    special token decodes to no text) is left out of the case, not sent as
    another token's."""
    words = answer.get("text", "").split()
    case = {"prompt_ids": [word_id[w] for w in answer["prompt"].split()],
            "output_ids": [word_id[w] for w in words if w in word_id],
            "top_logprobs": [[[word_id[w.strip()], lp] for w, lp in top.items() if w.strip() in word_id]
                             for top in answer.get("top_logprobs", ())]}
    return case, [w for w in words if w not in word_id]


def device_report(dev: dict, engine: dict) -> dict:
    """The result line's ``device``: what the child named at start-up, and the
    allocator's peak on the fullest chip from ``/debug/engine``."""
    memory = [d.get("peak_bytes_in_use") for d in engine.get("device_memory") or []]
    memory = [m for m in memory if m is not None]
    return {"platform": dev["platform"], "kind": dev["kind"], "count": dev["count"],
            "memory_peak_bytes": max(memory) if memory else None}


def weights_floor_bytes(account: dict, chips: int) -> float:
    """The least a chip holds of a model sharded evenly: 0.98 of the whole
    model's bytes (``memory_account_bytes.weights``) / chips."""
    return 0.98 * account["weights"] / chips


def weights_not_held(account: dict, chips: int, device: dict):
    """Why the server does not hold the weights its configuration's file says
    it has, or None. The allocator's peak on the fullest chip is at least
    ``weights_floor_bytes``: a tree that maps the config.json onto a smaller
    model than the file accounts for fails here, in every run. No upper limit,
    and nothing about the pool: the program sizes that itself. Off the TPU (a
    rehearsal at a tiny shape) there is nothing to hold."""
    if device["platform"] != "tpu":
        return None
    need, peak = weights_floor_bytes(account, chips), device["memory_peak_bytes"]
    if peak is None:
        return (f"the device reported no memory_peak_bytes to hold against the "
                f"{account['weights']} B of weights the configuration accounts for")
    if peak < need:
        return (f"memory_peak_bytes {peak} is under 0.98 x weights {account['weights']} B "
                f"/ {chips} chips = {need:.0f} B: the server does not hold the model "
                f"the configuration's file describes")
    return None


def numbers_compared(probes, summary, recompiles, device, weights_floor, verdict) -> dict:
    """Every number ``correct`` compares, beside its limit: ``{name: [number,
    limit]}``, the result line's last key and the run's last lines on standard
    error (the driver's record of a run that is not correct keeps those ends
    and nothing else)."""
    out = {
        "probe_texts_distinct": [len({p.get("text") for p in probes}), 1],
        "requests_failed": [summary["failed"], 0],
        "jit_recompiles_in_window": [recompiles[1] - recompiles[0], 0],
        "memory_peak_bytes_at_least": [device["memory_peak_bytes"], weights_floor],
    }
    if verdict is not None:  # the traced run's answer against the plain reference
        out["reference_probe_answered"] = [0 if "no_answer" in verdict else 1, 1]
        out["worst_gap_at_most"] = [verdict.get("worst_gap"), verdict.get("tolerance")]
        out["argmax_matches_at_least"] = [verdict.get("argmax_matches"), (verdict.get("tokens") or 0) / 2]
        out["logprob_rms_at_most"] = [verdict.get("logprob_rms"), verdict.get("logprob_rms_limit")]
        out["logprob_pairs_at_least"] = [verdict.get("logprob_pairs"), verdict.get("logprob_pairs_floor")]
    return out


def reference_probe_report(rec: dict, seconds: float, timeout_s: float, after: dict, probed: dict) -> dict:
    """The ``info`` line's ``reference_probe``: what the answer held against the
    reference cost. ``seconds`` is the request's wall against its ``timeout_s``;
    ``compile_s`` the rise of ``host_phase_us.compile`` (the engine thread's
    self time under ``engine.compile``) and ``jit_recompiles`` that counter's
    rise between ``after`` (the snapshot behind the drain) and ``probed`` (the
    one behind this request): its ``lp=True`` programs compile inside it. None
    where a program keeps no such counter, never 0."""
    def rise(read):
        try:
            return read(probed) - read(after)
        except (KeyError, TypeError):
            return None

    compile_us = rise(lambda snap: snap["host_phase_us"]["compile"])
    return {"seconds": seconds, "first_token_s": rec.get("first_s"), "timeout_s": timeout_s,
            "compile_s": None if compile_us is None else compile_us / 1e6,
            "jit_recompiles": rise(lambda snap: snap["jit_recompiles"])}


def reference_verdict(long_probe: dict, report: dict, against_reference) -> dict:
    """The verdict on the answer held against the plain reference. A probe that
    gave no answer is not sent to ``against_reference`` (minutes of chip on an
    answer of no tokens): its verdict says what happened under ``no_answer``."""
    if long_probe["ok"]:
        return against_reference([long_probe])[0]
    error = long_probe.get("error") or (
        f"HTTP {long_probe.get('status')}, finish_reason {long_probe.get('finish_reason')!r}, "
        f"usage {long_probe.get('usage')}")
    return {"agrees": False, "no_answer": (
        f"{error} after {report['seconds']:.1f} s of the {report['timeout_s']:g} s it is given "
        f"(compile_s {report['compile_s']}, jit_recompiles +{report['jit_recompiles']})")}


def reference_faults(verdict: dict) -> list:
    """Why the answer held against the plain reference does not pass, as
    reasons for ``not_correct_because``: the probe gave no answer (and nothing
    was held), or the reference's own verdict, and too few of the answer's 480
    log-probabilities held (the floor goes into the verdict beside the count,
    for the ``info`` line)."""
    verdict["logprob_pairs_floor"] = REFERENCE_PAIRS_FLOOR
    if "no_answer" in verdict:
        return [f"the probe held against the plain reference gave no answer: {verdict['no_answer']}"]
    faults = []
    if not verdict["agrees"]:
        faults.append(f"the probe's answer disagrees with the plain reference: {verdict}")
    if (verdict.get("logprob_pairs") or 0) < REFERENCE_PAIRS_FLOOR:
        faults.append(f"{verdict.get('logprob_pairs')} of the answer's {REFERENCE_OUTPUT_TOKENS} x "
                      f"{REFERENCE_TOP_LOGPROBS} log-probabilities were held against the reference, "
                      f"under the floor of {REFERENCE_PAIRS_FLOOR}")
    return faults


def engine_state(port: int) -> dict:
    status, raw = serving.http_json(port, "GET", "/debug/engine")
    check(status == 200, f"/debug/engine: HTTP {status}")
    return json.loads(raw)


class Launch:
    """Everything one run needs before the server answers: the cell, its
    configuration, the model directory, the schedule, and the child."""

    def __init__(self, workload: str, seed: int, trace: bool, rehearse: bool):
        self.bench = load_json(ROOT, "BENCHMARK.json")
        cell_file = os.path.join(HERE, "workloads", f"{workload}.json")
        check(os.path.exists(cell_file), f"no workload {workload!r}: no {cell_file}")
        self.cell = load_json(cell_file)
        cfg_entry = next((c for c in self.bench["configs"] if c["name"] == self.cell["config"]), None)
        check(cfg_entry is not None, f"no configuration {self.cell['config']!r} in BENCHMARK.json")
        self.cfg = load_json(ROOT, cfg_entry["file"])
        self.serve = self.cfg["serving"]
        # how long the server may take to compile: its warm-up before it serves,
        # and the answer held against the reference, whose programs no warm-up compiles
        self.ready_timeout_s = self.serve.get("ready_timeout_s", 900)
        self.chips = self.serve["chips"]
        # a cell whose file is here and that BENCHMARK.json does not list is
        # parked (PERF.md 7): it runs by hand, rehearses and sweeps, the driver never asks for it
        entry = next((w for w in self.bench["workloads"] if w["name"] == workload), None)
        check(entry is None
              or (entry["config"] == self.cell["config"] and entry["chips"] == self.chips),
              "the cell's file and BENCHMARK.json disagree on configuration or chips")
        self.shape = {k: v for k, v in self.cfg.items() if not isinstance(v, dict)
                      and k not in ("source", "reduced", "assumed", "deployment",
                                    "reference", "bytes_and_flops")}
        if rehearse:
            self.shape = load_json(HERE, "rehearse.json")["shape"]
        self.scratch = os.path.join(ROOT, ".bench_runs", workload)
        shutil.rmtree(self.scratch, ignore_errors=True)
        os.makedirs(self.scratch)
        self.model_dir = os.path.join(self.scratch, self.cell["config"])
        self.model = os.path.basename(self.model_dir)
        self.words = serving.write_model_dir(self.model_dir, self.shape, seed)
        self.plain = [w for w in self.words[4:] if w not in serving.SPECIALS]
        engine_args = dict(self.serve["engine_args"])
        args_file = os.path.join(self.scratch, "engine_args.json")
        with open(args_file, "w") as f:
            json.dump(engine_args, f)
        self.env = env = dict(self.serve.get("env") or {})
        if rehearse:
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={self.chips}"
        self.trace_dir = os.path.join(self.scratch, "trace")
        own = []
        if trace:
            os.makedirs(self.trace_dir)
            own = ["--trace-dir", self.trace_dir]
        self.port = serving.free_port()
        self.child = serving.Child(
            [sys.executable, os.path.join(HERE, "server_child.py"), *own, "--",
             "in=http", "out=jax", "--model-path", self.model_dir,
             "--host", "127.0.0.1", "--port", str(self.port),
             *self.serve["server_flags"], "--extra-engine-args", args_file],
            os.path.join(self.scratch, "server.log"), env,
        )
        self.rehearse = rehearse

    def against_reference(self, answers: list, control: str = None) -> list:
        """The verdicts of ``reference_child.py`` on greedy answers, given as
        the records ``client.probe`` returned (with ``prompt``, ``text`` and
        ``top_logprobs``). Run it only after the server has exited: it
        needs the chip. ``control`` names a module to put in the program's
        place as well (``correct_readings.py``; never in a benchmark run)."""
        word_id = {w: i for i, w in enumerate(self.words)}
        # set from readings on the chip (PERF.md 2); a rehearsal's tiny shape has none
        limits = {} if self.rehearse else self.cfg.get("correct_limits", {})
        cases = [reference_case(a, word_id) for a in answers]
        unknown = [w for _, words in cases for w in words]
        if unknown:
            return [{"agrees": False, "answered_words_not_in_the_table": unknown[:8]}] * len(answers)
        case_file = os.path.join(self.scratch, "reference_case.json")
        with open(case_file, "w") as f:
            json.dump([case for case, _ in cases], f)
        child = serving.Child(
            [sys.executable, os.path.join(HERE, "reference_child.py"),
             "--model-dir", self.model_dir, "--case", case_file,
             "--seed", str(self.serve["engine_args"].get("seed", 0)),
             "--reference", self.cfg.get("reference", "reference"),
             *(["--logprob-rms-limit", str(limits["logprob_rms"])] if "logprob_rms" in limits else []),
             *(["--control", control] if control else []),
             "--", *self.serve["server_flags"]],
            os.path.join(self.scratch, "reference.log"), self.env,
        )
        try:
            child.proc.wait(timeout=self.ready_timeout_s)
        except subprocess.TimeoutExpired:
            pass
        finally:
            child.stop()
        verdicts = child.log_json("reference")
        missing = {"agrees": False, "log": child.log()[-1500:]}
        return verdicts + [missing] * (len(answers) - len(verdicts))

    def wait_ready(self) -> dict:
        """The device the child named, once it serves. No TPU, no run."""
        dev = serving.wait_device(self.child)
        if not self.rehearse:
            check(dev["platform"] == "tpu",
                  f"no TPU: JAX found platform {dev['platform']!r}")
        check(dev["count"] >= self.chips,
              f"the cell needs {self.chips} chips, JAX found {dev['count']}")
        self.peaks = None if self.rehearse else bytes_and_flops.load_peaks(dev["kind"])
        serving.wait_http(self.child, self.port, self.ready_timeout_s)
        return dev


def run(args) -> int:
    go = Launch(args.workload, args.seed, bool(args.trace), args.rehearse)
    bench, cell, cfg, shape, chips = go.bench, go.cell, go.cfg, go.shape, go.chips
    child, port, model, plain, trace_dir = go.child, go.port, go.model, go.plain, go.trace_dir
    schedule = traffic.build_schedule(cell, args.seed, args.seconds)
    samples, cpu, trace_done, trace_s, probe_report = [], {}, True, None, None
    try:
        dev = go.wait_ready()
        peaks = go.peaks
        ready_s = time.perf_counter() - T_START
        probe_prompt = traffic.prompt_text(
            plain, PROBE_PROMPT_TOKENS, random.Random(args.seed + 1))
        probes = [asyncio.run(client.probe(port, model, probe_prompt, PROBE_OUTPUT_TOKENS))
                  for _ in range(2)]
        before = engine_state(port)

        background = [lambda c, s, sess: sample_cpu(c, s, sess, child, args.seconds, cpu)]
        if args.trace:
            background += [
                lambda c, s, sess: sample_engine(c, s, sess, port, args.seconds, samples),
                lambda c, s, sess: trigger_trace(c, s, sess, trace_dir, args.seconds),
            ]
        window_at = {}
        result = asyncio.run(client.run_window(
            port, model, plain, schedule, args.seconds,
            on_window=lambda: window_at.setdefault("t", time.perf_counter()),
            background=background,
        ))
        after = engine_state(port)
        probes.append(asyncio.run(client.probe(port, model, probe_prompt, PROBE_OUTPUT_TOKENS)))
        if args.trace:
            long_prompt = traffic.prompt_text(
                plain, REFERENCE_PROMPT_TOKENS, random.Random(args.seed + 2))
            asked_at = time.perf_counter()
            long_probe = asyncio.run(client.probe(
                port, model, long_prompt, REFERENCE_OUTPUT_TOKENS, REFERENCE_TOP_LOGPROBS,
                timeout_s=go.ready_timeout_s))
            long_probe["prompt"] = long_prompt
            probe_report = reference_probe_report(
                long_probe, time.perf_counter() - asked_at, go.ready_timeout_s, after, engine_state(port))
            trace_done = wait_for_xplane(trace_dir, lambda: child.proc.poll() is None)
            trace_s = trace_seconds(trace_dir, time.time())
    finally:
        child.stop()

    # -- everything below runs after the child has exited ----------------------
    records = result["records"]
    if args.keep_records:
        with open(args.keep_records, "w") as f:
            json.dump([{k: v for k, v in r.items() if k != "text"} for r in records], f)
    summary = stats.summarize(records, args.seconds)
    setup_s = window_at["t"] - T_START
    reasons = []
    if not all(p["ok"] for p in probes):
        reasons.append("a probe request failed: " + str([p.get("error") or p.get("usage") for p in probes]))
    if len({p.get("text") for p in probes}) != 1:
        reasons.append("the greedy probe returned different text before and after the window")
    if after["jit_recompiles"] != before["jit_recompiles"]:
        reasons.append(f"jit_recompiles rose {before['jit_recompiles']} -> {after['jit_recompiles']} over the window")
    tiers = after.get("attention_tiers") or {}
    if not tiers:
        reasons.append("the engine reported no compiled attention tier")
    if dev["platform"] == "tpu" and any(t.get("interpret") for t in tiers.values()):
        reasons.append(f"an interpreted kernel on a TPU: {tiers}")
    if summary["failed"]:
        bad = [r for r in records if r["in_window"] and not r["ok"]][:3]
        reasons.append(f"{summary['failed']} requests failed, e.g. " + str(
            [{k: r.get(k) for k in ("status", "finish_reason", "usage", "error", "max_tokens")} for r in bad]))
    if summary["attempted"] == 0:
        reasons.append("no request fell due inside the window")

    verdict = None
    if args.trace:
        verdict = reference_verdict(long_probe, probe_report, go.against_reference)
        reasons += reference_faults(verdict)

    reduced = None
    if args.trace and trace_done:
        path = trace_reduce.find_xplane(trace_dir)
        if path:
            reduced = trace_reduce.read_xplane(
                path, device_marker="CPU" if args.rehearse else "TPU")
            if args.keep_trace:
                trace_reduce.save_reduced(reduced, args.keep_trace)
    device = device_report(dev, after)
    not_held = weights_not_held(cfg["memory_account_bytes"], chips, device)
    if not_held:
        reasons.append(not_held)
    ctx = {
        "cell": cell, "cell_name": args.workload, "config": cfg, "shape": shape,
        "chips": chips, "seconds": args.seconds, "peaks": peaks, "device": device,
        "records": records, "summary": summary, "setup_s": setup_s,
        "engine_samples": samples, "engine_before": before, "engine_after": after,
        "child_cpu_s": (cpu["end"] - cpu["start"]) if len(cpu) == 2 else None,
        "trace": reduced, "rehearse": args.rehearse,
    }
    info = {
        "workload": args.workload, "seed": args.seed, "seconds": args.seconds,
        "registered": any(w["name"] == args.workload for w in bench["workloads"]),
        "samples": summary["samples"], "ready_s": ready_s, "drain_s": result["drain_s"],
        "warmup_s": (child.log_json("warmup") or [None])[0],
        "compile_cache": dev.get("compile_cache"),
        "jit_recompiles": [before["jit_recompiles"], after["jit_recompiles"]],
        "against_reference": verdict, "reference_probe": probe_report, **(trace_s or {}),
        "memory_peak_bytes_and_weights_floor": [
            device["memory_peak_bytes"], weights_floor_bytes(cfg["memory_account_bytes"], chips)],
        "tiers": sorted({t.get("tier") for t in tiers.values()}),
        "mean_prompt_tokens": summary["mean_prompt_tokens"],
        "mean_output_tokens": summary["mean_output_tokens"],
        "end_to_end": {k: summary[k] for k in (
            "ttft_mean_ms", "ttft_p50_ms", "ttft_p90_ms",
            "tpot_mean_ms", "tpot_p50_ms", "tpot_p90_ms",
            "output_tokens_per_s", "client_lag_p90_ms", "longest_silence_ms")},
        "token_count_mismatches": sum(
            1 for r in records if r["ok"]
            and sum(n for _, n in r["token_times"]) != r["got_tokens"]),
        "setup_s": setup_s, "not_correct_because": reasons,
    }
    print(json.dumps({"info": info}), flush=True)

    kind = "per_layer" if args.trace else "end_to_end"
    metrics = read_metrics(bench, kind, args.workload, ctx)
    line = {"correct": not reasons, "attempted": summary["attempted"],
            "failed": summary["failed"], "metrics": metrics, "device": device}
    if args.rehearse:
        # a CPU run is never written under the name of a device metric
        line["metrics"] = {}
        line["rehearsal"] = {k: v["value"] for k, v in metrics.items()}
    if args.trace and reduced is not None and reduced["devices"]:
        span = trace_reduce.span_ns(reduced)
        device["busy_s"] = trace_reduce.busy_seconds(reduced)
        device["window_s"] = (span[1] - span[0]) / 1e9 if span else 0.0
        line["breakdown"] = {
            "device_ops": trace_reduce.top_ops(reduced, 10),
            "idle_gaps": trace_reduce.idle_gaps(reduced, 5),
            "idle_s_by_span": trace_reduce.idle_s_by_span(reduced, 10),
        }
    elif args.trace and not args.rehearse:
        raise BenchFailure(
            "the traced run read no device event: "
            + json.dumps((reduced or {}).get("planes_seen", "no xplane file"))[:1500])
    line["compared"] = numbers_compared(
        probes, summary, info["jit_recompiles"], device,
        info["memory_peak_bytes_and_weights_floor"][1], verdict)
    print(json.dumps(line), flush=True)
    for name, (number, limit) in line["compared"].items():
        print(f"compared {name}: {number} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    return 0


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="tiny width on the CPU; prints no device metric")
    p.add_argument("--keep-records", default=None,
                   help="also write the per-request records (JSON) here")
    p.add_argument("--keep-trace", default=None,
                   help="also write the reduced trace (gzipped JSON) here")
    args = p.parse_args()
    if args.seconds is None:
        args.seconds = float(load_json(ROOT, "BENCHMARK.json")["run_seconds"])
    try:
        return run(args)
    except BenchFailure as e:
        print(f"benchmark failed: {e}", file=sys.stderr, flush=True)
        return 1


if __name__ == "__main__":
    sys.exit(main())
