"""Of the tiles of pool history that the history-bearing chunk dispatches
would read at the block tables' full width (``max_model_len``), the share
their loop did read: 100 x the rise of ``chunk_history_tiles_read`` over the
rise of ``chunk_history_tiles_full`` (cumulative counters of GET /debug/engine,
models/llama.py ``chunk_history_tiles``). Taken between the first and the last
sample of the window that carry the counters; where the sampler kept none of
them, between the snapshots at both ends of the run (before the pre-roll,
after the drain). None where the program has no such counter, or where no such
dispatch ran."""

NAME = "chunk_history_read_share"
UNIT = "%"
LAYER = "model, prompt processing"
MOVES = "ttft_mean_ms"

READ, FULL = "chunk_history_tiles_read", "chunk_history_tiles_full"


def read(ctx):
    for snaps in (ctx["engine_samples"], [ctx.get("engine_before"), ctx.get("engine_after")]):
        snaps = [s for s in snaps if s and s.get(READ) is not None and s.get(FULL) is not None]
        if len(snaps) >= 2:
            full = snaps[-1][FULL] - snaps[0][FULL]
            return 100.0 * (snaps[-1][READ] - snaps[0][READ]) / full if full > 0 else None
    return None
