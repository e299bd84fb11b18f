"""Of the engine thread's time, the share spent on the seal-time checksum of
the blocks a dispatch filled (their pages to the host, a crc over each): 100 x
the rise of ``host_phase_us.seal_crc`` over the rise of ``uptime_us``
(cumulative counters of GET /debug/engine). Taken between the first and the
last sample of the window that carry the counters; where the sampler kept none
of them, between the snapshots at both ends of the run (before the pre-roll,
after the drain). None where the program has no such counter."""

NAME = "seal_crc_host_share"
UNIT = "%"
LAYER = "KV cache"
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
    ends = _ends(ctx, "host_phase_us", "uptime_us")
    if ends is None:
        return None
    first, last = ends
    uptime = last["uptime_us"] - first["uptime_us"]
    crc = last["host_phase_us"]["seal_crc"] - first["host_phase_us"]["seal_crc"]
    return 100.0 * crc / uptime if uptime > 0 else None
