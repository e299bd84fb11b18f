"""90th percentile, over requests, of (last token time - first token time) /
(output tokens - 1), as the client saw it in the traced run: a client-side
tail. Recorded, not judged: see ttft_p90_ms."""

NAME = "tpot_p90_ms"
UNIT = "ms"
LAYER = "benchmark client"
MOVES = "ttft_mean_ms"


def read(ctx):
    return ctx["summary"]["tpot_p90_ms"]
