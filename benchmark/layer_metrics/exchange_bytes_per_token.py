"""What a chip hands to the all-reduces of attention's and the experts' partial
sums for every token it computes, in KB: the rise of ``exchange_rows`` (rows a
shard handed to such an all-reduce, summed over the layers and both kinds;
``models/mellum.py`` counts them as its step programs dispatch them, so the
padding rows of a rung and the lanes of a decode step that do not decode travel
too, which is what this shows) x ``hidden_size`` x 4 bytes (float32 sums) / the
tokens computed over the same window (the rise of ``chunk_tokens_fed`` and the
tokens the window's answers brought). 2 x layers x hidden x 4 (516 KB at 28
layers of 2,304) is a program without a padding row. None where the program has
no such counter (one chip, a parent without the module) or nothing was
computed."""

from benchmark import counters

NAME = "exchange_bytes_per_token"
UNIT = "KB"
LAYER = "sharding"
MOVES = "ttft_mean_ms"

ROWS, FED = "exchange_rows", "chunk_tokens_fed"


def read(ctx):
    ends = counters.window_ends(ctx, ROWS, FED)
    if ends is None:
        return None
    tokens = ends[1][FED] - ends[0][FED] + (ctx["summary"].get("output_tokens_in_window") or 0)
    rows = ends[1][ROWS] - ends[0][ROWS]
    if tokens <= 0 or rows <= 0:
        return None
    return rows * ctx["shape"]["hidden_size"] * 4 / tokens / 1e3
