#!/usr/bin/env python3
"""Find the knee of an open-loop cell, once. A tool: the driver never runs it.

    python3 benchmark/sweep.py --workload <open-loop cell> --rates 1.0,1.2,1.4,1.6,1.8

One server; one window of ``--seconds`` (60: several request lifetimes, so
that the slots fill where they are going to) per rate, in the order given,
each with the cell's own lengths and arrival generator at that rate and
prompts of its own words, each followed by its drain. A rate is sustained if
every request due in the window finished within the drain AND, over the
second half of the window, the tokens delivered are at least 0.9 of the
tokens offered (the output lengths of the requests due in that half): above
the knee the queue grows and delivery falls behind the offer. The knee is
the highest sustained rate. Prints one JSON line per rate and a last line
with the knee; the cell's file then takes its ``rate_rps`` by hand.

PR 23's first rule (15-20 s windows, TTFT of the last third against the
first) passed every rate: a request lives 11-16 s and the window ended before
the slots were full. This rule ran on the chip in PR 26 (Qwen2.5-1.5B, chat
lengths, 1.5-3.0 requests/s): every rate passed, 3.0/s at 0.914 of its offer
with the median TTFT doubled against 2.7/s, so the knee it names is a lower
bound where the highest rate given still passes; read ``ttft_p50_ms`` beside
``sustained``. The cell's file says which share of the knee its rate is.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import client, stats, traffic  # noqa: E402
from benchmark.run import Launch  # noqa: E402
from benchmark.serving import BenchFailure  # noqa: E402


def second_half(records: list, seconds: float) -> tuple:
    """(tokens/s delivered, tokens/s offered) over [seconds/2, seconds)."""
    half = seconds / 2.0
    delivered = sum(n for r in records for t, n in r["token_times"] if half <= t < seconds)
    offered = sum(r["max_tokens"] for r in records
                  if r["in_window"] and r["due_s"] >= half)
    return delivered / half, offered / half


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True, help="comma-separated requests/s")
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=60.0)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    rates = [float(r) for r in args.rates.split(",")]
    go = Launch(args.workload, args.seed, False, args.rehearse)
    knee = None
    try:
        dev = go.wait_ready()
        for k, rate in enumerate(rates):
            cell = dict(go.cell, arrivals=dict(go.cell["arrivals"], rate_rps=rate),
                        preroll_s=0.0)
            # other words in every window: a prompt seen in an earlier window
            # would be served from the prefix cache
            schedule = traffic.build_schedule(cell, args.seed + k, args.seconds)
            result = asyncio.run(client.run_window(
                go.port, go.model, go.plain, schedule, args.seconds))
            summary = stats.summarize(result["records"], args.seconds)
            delivered, offered = second_half(result["records"], args.seconds)
            sustained = bool(summary["attempted"] and not summary["failed"]
                             and delivered >= 0.9 * offered)
            row = {
                "rate_rps": rate, "attempted": summary["attempted"],
                "failed": summary["failed"], "drain_s": result["drain_s"],
                "ttft_p50_ms": summary["ttft_p50_ms"], "ttft_p90_ms": summary["ttft_p90_ms"],
                "tpot_p90_ms": summary["tpot_p90_ms"],
                "output_tokens_per_s": summary["output_tokens_per_s"],
                "second_half_delivered_tokens_per_s": delivered,
                "second_half_offered_tokens_per_s": offered,
                "sustained": sustained,
            }
            print(json.dumps(row), flush=True)
            if sustained:
                knee = max(knee or 0.0, rate)
    finally:
        go.child.stop()
    print(json.dumps({"workload": args.workload, "device": dev, "knee_rps": knee}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchFailure as e:
        print(f"sweep failed: {e}", file=sys.stderr)
        sys.exit(1)
