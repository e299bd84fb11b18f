"""Of the positions the chunk dispatches computed (rows x ``prefill_chunk``
each), the share that held a prompt token: 100 x the rise of
``chunk_tokens_fed`` over the rise of ``chunk_positions_dispatched``
(cumulative counters of GET /debug/engine, engine_jax/engine.py
``_chunk_dispatch``). The rest is padding: the tail of a row whose lane had
less than a chunk left, and the rows between the lanes that prefill and the
rung of the row ladder that holds them. Taken as ``chunk_history_read_share``
takes its two counters: between the first and the last sample of the window
that carry them; where the sampler kept none, between the snapshots at both
ends of the run (before the pre-roll, after the drain). None where the program
has no such counter, or where no chunk dispatch ran."""

NAME = "chunk_token_fill_share"
UNIT = "%"
LAYER = "model, prompt processing"
MOVES = "ttft_mean_ms"

FED, DISPATCHED = "chunk_tokens_fed", "chunk_positions_dispatched"


def read(ctx):
    for snaps in (ctx["engine_samples"], [ctx.get("engine_before"), ctx.get("engine_after")]):
        snaps = [s for s in snaps if s and s.get(FED) is not None and s.get(DISPATCHED) is not None]
        if len(snaps) >= 2:
            dispatched = snaps[-1][DISPATCHED] - snaps[0][DISPATCHED]
            return 100.0 * (snaps[-1][FED] - snaps[0][FED]) / dispatched if dispatched > 0 else None
    return None
