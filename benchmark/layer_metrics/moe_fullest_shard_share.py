"""Of the pairs the expert layers routed, the share that went to the chip that
got most: 100 x the rise of ``moe_pairs_fullest_shard`` (a layer call's pairs
on its fullest shard, summed over the calls) over the rise of
``moe_pairs_all_shards`` (cumulative counters of GET /debug/engine;
``models/mellum.py`` counts both from the router's choices, which every shard
holds whole). A layer's all-reduce waits for its fullest shard: 100 / chips is
even routing (25 % on four chips), and what lies above it is time the other
chips wait. Over the window (``benchmark/counters.py``). None where the program
has no such counter (a model on one chip, a parent without the module), or
where nothing was routed."""

from benchmark import counters

NAME = "moe_fullest_shard_share"
UNIT = "%"
LAYER = "model, expert layer"
MOVES = "ttft_mean_ms"


def read(ctx):
    return counters.rise_ratio(ctx, "moe_pairs_fullest_shard", "moe_pairs_all_shards", 100.0)
