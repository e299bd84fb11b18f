"""Of the cached positions latent attention attended, the share that held
history: 100 x the rise of ``mla_history_positions_live`` over the rise of
``mla_history_positions_read`` (cumulative counters of GET /debug/engine;
``models/openpangu.py`` returns the sums over its layers' calls, chunk, decode
and verify dispatches alike, and the engine's host loop adds them up). A call's
row attends its whole block table (``ops/latent.py:attend_absorbed`` scores
every position under a mask), of which the positions up to its last token hold
history; the rest is read, scored and masked for nothing. A form that gathers
and scores the live part alone raises it to 100 (ROADMAP S3 (f), S8). Taken
between the snapshots at both ends of the run (before the pre-roll, after the
drain), as ``chunk_history_read_share`` falls back to. None where the program
has no such counter (another model, a parent without the module), or where
nothing was read."""

NAME = "mla_history_read_share"
UNIT = "%"
LAYER = "model, latent attention"
MOVES = "ttft_mean_ms"

LIVE, READ = "mla_history_positions_live", "mla_history_positions_read"


def read(ctx):
    before, after = ctx.get("engine_before"), ctx.get("engine_after")
    if not before or not after or any(s.get(n) is None for s in (before, after) for n in (LIVE, READ)):
        return None
    attended = after[READ] - before[READ]
    if attended <= 0:
        return None
    return 100.0 * (after[LIVE] - before[LIVE]) / attended
