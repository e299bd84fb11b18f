"""Token rows that share one computation of the residual path's maps: the
rise of ``mhc_rows_mixed`` over the rise of ``mhc_mix_calls`` (cumulative
counters of GET /debug/engine; ``models/xing4.py`` returns the sums over its
sublayers' calls, chunk, decode and verify dispatches alike, and the engine's
host loop adds them up). A call is one sublayer's ``mhc_maps`` over the tokens
of one dispatch: a decode step's lanes, or a group of a chunk's rows. Its 2 x
``hc_sinkhorn_iters`` normalisations depend on one another, so a call takes
about as long over 64 rows as over 512 (``tools/profile_decode.py mhc``): the
more rows share one, the less of a token's time the residual path is. Over
the window (``benchmark/counters.py``: its samples that carry the counters,
else the two ends of the run). None where the program has no such counter
(another model, a parent without the module), or where no call was made."""

from benchmark import counters

NAME = "mhc_rows_per_mix"
UNIT = "rows"
LAYER = "model, residual path"
MOVES = "ttft_mean_ms"

ROWS, CALLS = "mhc_rows_mixed", "mhc_mix_calls"


def read(ctx):
    return counters.rise_ratio(ctx, ROWS, CALLS)
