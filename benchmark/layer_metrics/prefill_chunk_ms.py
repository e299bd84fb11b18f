"""Median device duration of the jit_chunk module events on device 0."""

from benchmark import trace_reduce

NAME = "prefill_chunk_ms"
UNIT = "ms"
LAYER = "model, prompt processing"
MOVES = "ttft_mean_ms"


def read(ctx):
    if not ctx["trace"]:
        return None
    m = trace_reduce.module_medians_ms(ctx["trace"]).get("jit_chunk")
    return m["median_ms"] if m else None
