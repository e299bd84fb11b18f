"""Roofline share of one decode step, bound by memory bandwidth: the bytes
the step must stream on each chip (every weight once + the live K and V of
the occupied lanes at the traffic's mean context; bytes_and_flops.py, or
the module the configuration's file names under ``bytes_and_flops``) /
peak bytes per second / decode_step_ms. The padded part of the dense
history buffer is work the algorithm does not need, so it lowers the share.

Live context: mean occupied lanes (the /debug/engine samples) x (mean prompt
+ half the mean output of the window's requests)."""

from benchmark import bytes_and_flops, trace_reduce

NAME = "decode_step_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "ttft_mean_ms"


def read(ctx):
    if not ctx["trace"] or not ctx["peaks"]:
        return None
    m = trace_reduce.module_medians_ms(ctx["trace"]).get("jit_decode")
    lanes = [s["request_active_slots"] for s in ctx["engine_samples"]
             if s.get("request_active_slots") is not None]
    s = ctx["summary"]
    if not m or not lanes or s["mean_prompt_tokens"] is None:
        return None
    step_s = m["median_ms"] / 1e3 / ctx["config"]["serving"]["engine_args"]["decode_steps"]
    context = s["mean_prompt_tokens"] + s["mean_output_tokens"] / 2.0
    needed = bytes_and_flops.for_config(ctx["config"]).decode_step_stream_bytes(
        ctx["shape"], sum(lanes) / len(lanes) * context, ctx["chips"])
    return 100.0 * needed / ctx["peaks"]["hbm_bytes_per_s"] / step_s
