"""Mean wait of a request between its arrival at the engine and the slot it
was given: the rise of ``queue_wait_us_sum`` over the rise of
``queue_wait_count`` (cumulative counters of GET /debug/engine, stamped where
``admit_t`` is), in ms. Taken between the first and the last sample of the
window that carry the counters; where the sampler kept none of them, between
the snapshots at both ends of the run (before the pre-roll, after the drain).
None where the program has no such counter, or where nothing was admitted."""

NAME = "queue_wait_mean_ms"
UNIT = "ms"
LAYER = "engine step loop"
MOVES = "ttft_mean_ms"


def _ends(ctx, *keys):
    """The two snapshots of GET /debug/engine to take a rise between: the
    first and the last sample of the window that carry ``keys``; where the
    sampler kept none of them, the snapshots at both ends of the run (before
    the pre-roll, after the drain). None where the program has no such
    counter."""
    for snaps in (ctx.get("engine_samples") or [], [ctx.get("engine_before"), ctx.get("engine_after")]):
        snaps = [s for s in snaps if s and all(s.get(k) is not None for k in keys)]
        if len(snaps) >= 2:
            return snaps[0], snaps[-1]
    return None


def read(ctx):
    ends = _ends(ctx, "queue_wait_us_sum", "queue_wait_count")
    if ends is None:
        return None
    first, last = ends
    admitted = last["queue_wait_count"] - first["queue_wait_count"]
    waited = last["queue_wait_us_sum"] - first["queue_wait_us_sum"]
    return waited / admitted / 1000.0 if admitted > 0 else None
