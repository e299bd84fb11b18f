"""1 - union of the module-event intervals on device 0 / traced span."""

from benchmark import trace_reduce

NAME = "device_idle_share"
UNIT = "%"
LAYER = "device"
MOVES = "ttft_mean_ms"


def read(ctx):
    if not ctx["trace"]:
        return None
    idle = trace_reduce.idle_share(ctx["trace"])
    return None if idle is None else 100.0 * idle
