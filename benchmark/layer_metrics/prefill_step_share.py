"""Of the host steps that dispatched anything, the share that dispatched a
chunk program (a lane prefilled): 100 x the rise of ``host_steps.prefill`` over
the rise of ``prefill`` + ``decode`` (cumulative counters of GET /debug/engine).
Taken between the first and the last sample of the window that carry the
counters; where the sampler kept none of them, between the snapshots at both
ends of the run (before the pre-roll, after the drain). None where the program
has no such counter, or where no step dispatched."""

NAME = "prefill_step_share"
UNIT = "%"
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
    ends = _ends(ctx, "host_steps")
    if ends is None:
        return None
    rise = {k: ends[1]["host_steps"][k] - ends[0]["host_steps"][k] for k in ("prefill", "decode")}
    steps = rise["prefill"] + rise["decode"]
    return 100.0 * rise["prefill"] / steps if steps > 0 else None
