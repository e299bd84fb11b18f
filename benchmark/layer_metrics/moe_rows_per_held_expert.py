"""Rows a held expert computes in one call of the expert layer, on average:
the rise of ``moe_held_rows`` (token-expert pairs routed to an expert held
here) over the rise of ``moe_layer_calls`` x the experts held (cumulative
counters of GET /debug/engine; ops/moe.py ``dropless_experts`` returns the
sums and the engine's host loop adds them up). A call is one expert layer
over the tokens of one dispatch: a decode step's lanes, or a group of a
chunk's rows. Few rows an expert means its weights are read for little work.
Taken between the snapshots at both ends of the run (before the pre-roll,
after the drain), as ``chunk_history_read_share`` falls back to. None where
the program has no such counter (a dense model, a parent without the layer),
or where no call was made."""

NAME = "moe_rows_per_held_expert"
UNIT = "rows"
LAYER = "model, expert layer"
MOVES = "ttft_mean_ms"

ROWS, CALLS = "moe_held_rows", "moe_layer_calls"


def read(ctx):
    before, after = ctx.get("engine_before"), ctx.get("engine_after")
    if not before or not after or any(s.get(n) is None for s in (before, after) for n in (ROWS, CALLS)):
        return None
    calls = after[CALLS] - before[CALLS]
    if calls <= 0:
        return None
    return (after[ROWS] - before[ROWS]) / (calls * int(ctx["shape"]["num_experts"]))
