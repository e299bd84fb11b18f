"""Max peak_bytes_in_use over the devices, from /debug/engine after the
drain, in GB (1e9 bytes)."""

NAME = "peak_hbm_gb"
UNIT = "GB"
LAYER = "device"
MOVES = "setup_s"


def read(ctx):
    peak = ctx["device"].get("memory_peak_bytes")
    return None if peak is None or ctx["rehearse"] else peak / 1e9
