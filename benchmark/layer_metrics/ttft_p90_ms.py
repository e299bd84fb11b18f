"""90th percentile of the time to first token over the window's requests, as
the client saw it in the traced run: a client-side tail, not a layer's. A
tail over the 70 requests of a window rests on 7 of them and swung 5-9 %
between runs (PR 23), too wide for a bound of at most 10 %: recorded, not
judged, until a window holds some hundreds of requests."""

NAME = "ttft_p90_ms"
UNIT = "ms"
LAYER = "benchmark client"
MOVES = "ttft_mean_ms"


def read(ctx):
    return ctx["summary"]["ttft_p90_ms"]
