#!/usr/bin/env python3
"""Hold the served path against the plain reference at a cell's TIMED sizes. A
tool: the driver never runs it.

    python3 tools/long_reference_probe.py [--workload long.trinity-large-preview] [--prompt-tokens 5888] [--seeds 3]

``benchmark/run.py``'s reference probe is 150 prompt tokens and 24 answered
(``REFERENCE_PROMPT_TOKENS``), which never leaves a window of 4,096 positions,
and a PR that adds a cell edits no file the benchmark has. So this starts the
cell's server with the cell's flags (``benchmark/run.py:Launch``), sends one
greedy request a seed of ``--prompt-tokens`` seeded words with ``logprobs: 20``
and 24 answered through the served path (``benchmark/client.py:probe``), and,
with the server gone, holds each answer against the configuration's plain
reference teacher-forced over the same weights by the harness's own rule
(``benchmark/reference_child.py``: every token within max|logit| / 16 of the
reference's first choice, half of them that choice, ``logprob_rms`` at or under
the configuration's ``correct_limits.logprob_rms``). One JSON line a seed, and
a last line with the largest ``logprob_rms`` and whether every seed agreed;
exit 1 where one did not. It folds into ``run.py`` once a cell names its
probe's length (ROADMAP B11).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import client, traffic  # noqa: E402
from benchmark.run import REFERENCE_OUTPUT_TOKENS, REFERENCE_TOP_LOGPROBS, Launch  # noqa: E402
from benchmark.serving import BenchFailure, check  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", default="long.trinity-large-preview")
    p.add_argument("--prompt-tokens", type=int, default=5888)
    p.add_argument("--seed", type=int, default=2147481000)
    p.add_argument("--seeds", type=int, default=3)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    go = Launch(args.workload, args.seed, False, args.rehearse)
    answers = []
    try:
        dev = go.wait_ready()
        for k in range(args.seeds):
            prompt = traffic.prompt_text(go.plain, args.prompt_tokens, random.Random(args.seed + k))
            probe = asyncio.run(client.probe(
                go.port, go.model, prompt, REFERENCE_OUTPUT_TOKENS, REFERENCE_TOP_LOGPROBS,
                timeout_s=go.ready_timeout_s))  # the first compiles the logprobs programs
            check(probe["ok"], f"the probe of seed {args.seed + k} failed: {probe.get('error') or probe.get('usage')}")
            answers.append(dict(probe, prompt=prompt))
    finally:
        go.child.stop()
    verdicts = go.against_reference(answers)
    for k, v in enumerate(verdicts):
        print(json.dumps({"seed": args.seed + k, "prompt_tokens": args.prompt_tokens, "verdict": v}), flush=True)
    agreed = all(v.get("agrees") for v in verdicts)
    print(json.dumps({
        "workload": args.workload, "device": dev, "seeds": args.seeds, "prompt_tokens": args.prompt_tokens,
        "logprob_rms_largest": max((v.get("logprob_rms") or 0.0) for v in verdicts),
        "worst_gap_over_max_logit_largest": max(
            (v["worst_gap"] / v["max_abs_logit"]) for v in verdicts if "worst_gap" in v) if agreed else None,
        "agrees": agreed}))
    return 0 if agreed else 1


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchFailure as e:
        print(f"long_reference_probe failed: {e}", file=sys.stderr)
        sys.exit(1)
