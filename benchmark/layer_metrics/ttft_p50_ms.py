"""Median time to first token over the window's requests, as the client saw
it in the traced run. Recorded beside the judged mean: the median prompt (256
tokens) is exactly two prefill chunks, so the median sits on a step of the
distribution and swings more than the mean (4.3 % against 1.6 % between
identical runs of `chat`, PR 23)."""

NAME = "ttft_p50_ms"
UNIT = "ms"
LAYER = "benchmark client"
MOVES = "ttft_mean_ms"


def read(ctx):
    return ctx["summary"]["ttft_p50_ms"]
