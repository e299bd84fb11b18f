"""Median device duration of the jit_decode module events on device 0,
divided by decode_steps (one dispatch scans that many steps)."""

from benchmark import trace_reduce

NAME = "decode_step_ms"
UNIT = "ms"
LAYER = "model, token generation"
MOVES = "ttft_mean_ms"


def read(ctx):
    if not ctx["trace"]:
        return None
    m = trace_reduce.module_medians_ms(ctx["trace"]).get("jit_decode")
    if not m:
        return None
    return m["median_ms"] / ctx["config"]["serving"]["engine_args"]["decode_steps"]
