#!/usr/bin/env python3
"""Read the numbers ``correct`` compares, over many seeds. A tool: the driver
never runs it.

    python3 benchmark/correct_readings.py --workload <cell> --seeds 12 [--control reference_control]

One server of the cell's configuration; one greedy answer per seed, the same
request a traced run holds against the reference (run.py: 150 prompt tokens,
24 answered); then, with the server gone, ``reference_child.py`` once over all
of them, with the control in the program's place beside it. Prints one JSON
line per seed with the program's numbers and the control's, and a last line
with the two readings a limit is set from: the largest the sound program gave
and the smallest the control gave, for each number compared.
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
from benchmark.run import (  # noqa: E402
    REFERENCE_OUTPUT_TOKENS, REFERENCE_PROMPT_TOKENS, REFERENCE_TOP_LOGPROBS, Launch)
from benchmark.serving import BenchFailure, check  # noqa: E402

NUMBERS = ("logprob_rms", "worst_gap", "mismatch_share")


def relative(verdict: dict) -> dict:
    """The numbers compared: the log-probabilities' error as it is, the worst
    gap as a share of max|logit|, mismatches as a share of the tokens."""
    return {"logprob_rms": verdict["logprob_rms"],
            "worst_gap": verdict["worst_gap"] / verdict["max_abs_logit"],
            "mismatch_share": 1.0 - verdict["argmax_matches"] / verdict["tokens"]}


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=2147480000)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control", default="reference_control")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    go = Launch(args.workload, args.seed, False, args.rehearse)
    answers = []
    try:
        dev = go.wait_ready()
        for k in range(args.seeds):
            prompt = traffic.prompt_text(
                go.plain, REFERENCE_PROMPT_TOKENS, random.Random(args.seed + k))
            probe = asyncio.run(client.probe(
                go.port, go.model, prompt, REFERENCE_OUTPUT_TOKENS, REFERENCE_TOP_LOGPROBS,
                timeout_s=go.ready_timeout_s))  # the first compiles the logprobs programs, as a traced run's does
            check(probe["ok"], f"the probe of seed {args.seed + k} failed: {probe.get('error')}")
            answers.append(dict(probe, prompt=prompt))
    finally:
        go.child.stop()
    verdicts = go.against_reference(answers, control=args.control)
    sound, control = [], []
    for k, v in enumerate(verdicts):
        check("control" in v, f"no verdict for seed {args.seed + k}: {v}")
        sound.append(relative(v))
        control.append(relative(v["control"]))
        print(json.dumps({"seed": args.seed + k, "program": v, "program_relative": sound[-1],
                          "control_relative": control[-1]}), flush=True)
    print(json.dumps({
        "workload": args.workload, "device": dev, "seeds": args.seeds, "control": args.control,
        "sound_largest": {n: max(s[n] for s in sound) for n in NUMBERS},
        "control_smallest": {n: min(c[n] for c in control) for n in NUMBERS},
    }))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchFailure as e:
        print(f"correct_readings failed: {e}", file=sys.stderr)
        sys.exit(1)
