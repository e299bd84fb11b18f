"""From per-request records to end-to-end metrics. Pure arithmetic.

A record is a dict the client fills:
  due_s, sent_s        seconds from the start of the window (due_s == sent_s
                       in a closed loop)
  first_s, last_s      arrival of the first and the last token, same clock
  prompt_tokens, max_tokens, got_tokens
  ok                   completed as asked (see client.py)
  in_window            fell due inside the window: counts as attempted
  token_times          arrival of every chunk: [(t_s, n_tokens), ...]
"""

from __future__ import annotations

import math


def percentile(values: list, p: float):
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        return None
    xs = sorted(values)
    k = (len(xs) - 1) * p / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def ttft_ms(rec: dict) -> float:
    """Open loop: from the instant the request was due. Closed: from the send
    (the client sets due_s = sent_s there)."""
    return (rec["first_s"] - rec["due_s"]) * 1e3


def tpot_ms(rec: dict):
    """Mean gap between tokens of one request: (last - first) / (n - 1)."""
    if rec["got_tokens"] < 2:
        return None
    return (rec["last_s"] - rec["first_s"]) * 1e3 / (rec["got_tokens"] - 1)


def drain_limit_s(records: list, floor_s: float) -> float:
    """How long the drain may take: the longest answer a request can ask for
    at twice the median gap the finished requests saw, and never under
    ``floor_s``. A slower but correct program is then not failed by the
    clock; a request that hangs still is."""
    tpots = [t for t in (tpot_ms(r) for r in records if r["ok"]) if t is not None]
    longest = max((r["max_tokens"] for r in records), default=0)
    if not tpots:
        return floor_s
    return max(floor_s, 2.0 * longest * percentile(tpots, 50) / 1e3)


def longest_silence_ms(records: list, window_s: float):
    """The longest stretch of the window in which no token of any request
    arrived. With several streams open the server answers every dispatch, a
    few hundred milliseconds apart at most: a far longer silence is a server
    (or a client) that stood still, which an open loop pays for many times
    over in its mean TTFT (PERF.md 7)."""
    times = sorted(t for r in records for t, _ in r.get("token_times", ()) if 0.0 <= t < window_s)
    if not times:
        return None
    edges = [0.0] + times + [window_s]
    return max(b - a for a, b in zip(edges, edges[1:])) * 1e3


def summarize(records: list, window_s: float) -> dict:
    """Metrics over the requests that fell due inside the window; tokens per
    second over every token that arrived inside it, whoever sent it."""
    window = [r for r in records if r["in_window"]]
    done = [r for r in window if r["ok"]]
    ttfts = [ttft_ms(r) for r in done]
    tpots = [t for t in (tpot_ms(r) for r in done) if t is not None]
    lags = [(r["sent_s"] - r["due_s"]) * 1e3 for r in window]
    tokens_in_window = sum(
        n for r in records for t, n in r.get("token_times", ())
        if 0.0 <= t < window_s
    )
    return {
        "attempted": len(window),
        "failed": len(window) - len(done),
        "samples": {"ttft": len(ttfts), "tpot": len(tpots)},
        "ttft_mean_ms": sum(ttfts) / len(ttfts) if ttfts else None,
        "tpot_mean_ms": sum(tpots) / len(tpots) if tpots else None,
        "ttft_p50_ms": percentile(ttfts, 50),
        "ttft_p90_ms": percentile(ttfts, 90),
        "tpot_p90_ms": percentile(tpots, 90),
        "tpot_p50_ms": percentile(tpots, 50),
        "output_tokens_per_s": tokens_in_window / window_s,
        "output_tokens_in_window": tokens_in_window,
        "client_lag_p90_ms": percentile(lags, 90),
        "longest_silence_ms": longest_silence_ms(records, window_s),
        "mean_prompt_tokens": (
            sum(r["prompt_tokens"] for r in window) / len(window) if window else None
        ),
        "mean_output_tokens": (
            sum(r["max_tokens"] for r in window) / len(window) if window else None
        ),
    }
