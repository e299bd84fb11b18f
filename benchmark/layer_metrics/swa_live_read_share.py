"""Of the ring entries the window layers' attention read, the share that held
history inside the window of the row's first query: 100 x the rise of
``swa_history_positions_live`` over the rise of ``swa_history_positions_read``
(cumulative counters of GET /debug/engine; ``models/trinity.py``). The rest is
what the layout costs: the tiles a chunk group walks for its longest row, the
ring's one block past the window, entries a short lane has not filled. Over
the window (``benchmark/counters.py``). None where the program has no such
counter (another model, a parent without the module), or where nothing was
read."""

from benchmark import counters

NAME = "swa_live_read_share"
UNIT = "%"
LAYER = "model, window attention"
MOVES = "ttft_mean_ms"

LIVE, READ = "swa_history_positions_live", "swa_history_positions_read"


def read(ctx):
    return counters.rise_ratio(ctx, LIVE, READ, 100.0)
