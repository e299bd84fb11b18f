"""Of the prompt tokens the allocator probed its prefix cache with, the share
it found there: 100 x the rise of ``prefix_hit_tokens`` over the rise of
``prefix_probe_tokens`` (the allocator's own cumulative counters, GET
/debug/engine): the run's traffic, not warm-up and probes as
``gpu_prefix_cache_hit_rate`` since boot. Taken between the first and the last
sample of the window that carry the counters; where the sampler kept none of
them, between the snapshots at both ends of the run (before the pre-roll, after
the drain). None where the program has no such counter, or where nothing was
probed."""

NAME = "prefix_hit_share"
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
    ends = _ends(ctx, "prefix_hit_tokens", "prefix_probe_tokens")
    if ends is None:
        return None
    first, last = ends
    probed = last["prefix_probe_tokens"] - first["prefix_probe_tokens"]
    return 100.0 * (last["prefix_hit_tokens"] - first["prefix_hit_tokens"]) / probed if probed > 0 else None
