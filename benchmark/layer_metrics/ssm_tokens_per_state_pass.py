"""Valid tokens a chunk's selective scan advances for each time a row's
recurrent state goes through its token loop: the rise of ``ssm_chunk_tokens``
over the rise of ``ssm_state_passes`` (cumulative counters of GET
/debug/engine; ``models/jamba.py`` returns the sums, over the Mamba layers, and
the engine's host loop adds them up). 1 by construction while the chunk's
recurrence is a scan over its tokens (the state is read and written a token);
a kernel that keeps a row's state on the chip for a chunk raises it to the
valid tokens of a row (as ``kda_chunk_tokens`` / ``kda_state_passes`` read 107
on ``batch.kimi-linear-48b-a3b`` since PR 40). Taken between the snapshots at
both ends of the run (before the pre-roll, after the drain), as
``chunk_history_read_share`` falls back to. None where the program has no such
counter (another model, a parent without the module), or where no pass was
made."""

NAME = "ssm_tokens_per_state_pass"
UNIT = "tokens"
LAYER = "model, state-space layers"
MOVES = "ttft_mean_ms"

TOKENS, PASSES = "ssm_chunk_tokens", "ssm_state_passes"


def read(ctx):
    before, after = ctx.get("engine_before"), ctx.get("engine_after")
    if not before or not after or any(s.get(n) is None for s in (before, after) for n in (TOKENS, PASSES)):
        return None
    passes = after[PASSES] - before[PASSES]
    if passes <= 0:
        return None
    return (after[TOKENS] - before[TOKENS]) / passes
