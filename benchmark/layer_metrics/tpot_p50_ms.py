"""Median, over requests, of (last token time - first token time) / (output
tokens - 1), as the client saw it in the traced run. Recorded, not judged: see tpot_mean_ms."""

NAME = "tpot_p50_ms"
UNIT = "ms"
LAYER = "benchmark client"
MOVES = "ttft_mean_ms"


def read(ctx):
    return ctx["summary"]["tpot_p50_ms"]
