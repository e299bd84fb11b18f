"""Of the engine thread's time, the share in which the device had nothing in
flight while a slot held a request: 100 x the rise of the sum of
``host_starved_us`` (by phase: what the host was doing then) over the rise of
``uptime_us`` (cumulative counters of GET /debug/engine,
runtime/profiling.py:PhaseClock). Taken between the first and the last sample
of the window that carry the counters; where the sampler kept none of them,
between the snapshots at both ends of the run (before the pre-roll, after the
drain: the pre-roll's admission wave is then in it). A LOWER bound of
``device_idle_share``: a device that finished before its read was called idled
unseen. None where the program has no such counter (the parent's)."""

NAME = "host_starved_share"
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
    ends = _ends(ctx, "host_starved_us", "uptime_us")
    if ends is None:
        return None
    first, last = ends
    uptime = last["uptime_us"] - first["uptime_us"]
    starved = sum(last["host_starved_us"].values()) - sum(first["host_starved_us"].values())
    return 100.0 * starved / uptime if uptime > 0 else None
