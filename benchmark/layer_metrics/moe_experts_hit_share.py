"""Of the experts held here, the share a call of the expert layer had to
read: 100 x the rise of ``moe_experts_hit`` (held experts with at least one
row, summed over calls) over the rise of ``moe_layer_calls`` x the experts
held (cumulative counters of GET /debug/engine; ops/moe.py
``dropless_experts``). The expert weights are most of what a decode step
streams, so this is how much of them a step pays for; lower is cheaper at the
same rows. Taken between the snapshots at both ends of the run. None where
the program has no such counter, or where no call was made."""

NAME = "moe_experts_hit_share"
UNIT = "%"
LAYER = "model, expert layer"
MOVES = "ttft_mean_ms"

HIT, CALLS = "moe_experts_hit", "moe_layer_calls"


def read(ctx):
    before, after = ctx.get("engine_before"), ctx.get("engine_after")
    if not before or not after or any(s.get(n) is None for s in (before, after) for n in (HIT, CALLS)):
        return None
    calls = after[CALLS] - before[CALLS]
    if calls <= 0:
        return None
    return 100.0 * (after[HIT] - before[HIT]) / (calls * int(ctx["shape"]["num_experts"]))
