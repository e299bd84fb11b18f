"""Of the positions of history that the window layers' queries have behind
them, the share their attention read: 100 x the rise of
``swa_history_positions_read`` over the rise of ``swa_history_positions_whole``
(cumulative counters of GET /debug/engine; ``models/trinity.py`` returns the
sums over its window layers, chunk and decode dispatches alike, and the
engine's host loop adds them up). ``read`` is the ring entries a query row
scored (a decode lane its ring a layer and step, a chunk's row the tiles its
group's loop walked); ``whole`` is what the same rows' lanes hold under their
first query, which is what a full layer in the window layer's place would have
read. 100 % while no lane has passed the window (and more where tiles round
up); it falls as lanes grow past ``sliding_window``. Over the window
(``benchmark/counters.py``: its samples that carry the counters, else the two
ends of the run). None where the program has no such counter (another model, a
parent without the module), or where no window layer read history."""

from benchmark import counters

NAME = "swa_history_read_share"
UNIT = "%"
LAYER = "model, window attention"
MOVES = "ttft_mean_ms"

READ, WHOLE = "swa_history_positions_read", "swa_history_positions_whole"


def read(ctx):
    return counters.rise_ratio(ctx, READ, WHOLE, 100.0)
