"""Mean of kv_active_blocks / kv_total_blocks from GET /debug/engine, sampled
at 2 Hz over the window: the share of the KV pool that live requests hold.
The rest is what the prefix cache may keep; with unshared prompts nothing
ever hits it."""

NAME = "kv_pool_fill"
UNIT = "%"
LAYER = "KV cache"
MOVES = "ttft_mean_ms"


def read(ctx):
    shares = [s["kv_active_blocks"] / s["kv_total_blocks"]
              for s in ctx["engine_samples"]
              if s.get("kv_total_blocks") and s.get("kv_active_blocks") is not None]
    return 100.0 * sum(shares) / len(shares) if shares else None
