"""Mean of request_active_slots / request_total_slots from GET /debug/engine,
sampled at 2 Hz over the window."""

NAME = "batch_occupancy"
UNIT = "%"
LAYER = "engine step loop"
MOVES = "ttft_mean_ms"


def read(ctx):
    shares = [s["request_active_slots"] / s["request_total_slots"]
              for s in ctx["engine_samples"] if s.get("request_total_slots")]
    return 100.0 * sum(shares) / len(shares) if shares else None
