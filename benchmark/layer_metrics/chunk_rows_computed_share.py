"""Of the rows the chunk dispatches held (the rung of the row ladder each
took), the share the chunk program computed: 100 x the rise of
``chunk_rows_computed`` (``models/jamba.py``'s ``COUNTERS``, since PR 67: the
rows of the groups of ``ROWS_AT_ONCE`` its loop ran, as far as the last row
that holds a token; the program returns the sum and the engine's host loop
adds it up) over the rise of ``chunk_rows_dispatched`` (the engine's own,
``engine_jax/engine.py:_chunk_dispatch``). 100 by construction for a program
that takes the whole rung at once; lower is what the rung's empty rows no
longer cost. Cumulative counters of GET /debug/engine, over the window
(``benchmark/counters.py``: its samples that carry the counters, else the two
ends of the run). None where the program has no such counter (another model,
a parent without it), or where no chunk dispatch ran."""

from benchmark import counters

NAME = "chunk_rows_computed_share"
UNIT = "%"
LAYER = "model, prompt processing"
MOVES = "ttft_mean_ms"


def read(ctx):
    return counters.rise_ratio(ctx, "chunk_rows_computed", "chunk_rows_dispatched", 100.0)
