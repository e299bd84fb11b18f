"""How late the generator sent against its schedule: 90th percentile of
(sent - due) over the window's requests. A closed loop has no schedule to be
late against, so nothing is read there."""

NAME = "client_lag_p90_ms"
UNIT = "ms"
LAYER = "benchmark client"
MOVES = "ttft_mean_ms"


def read(ctx):
    if ctx["cell"]["arrivals"]["gen"] == "closed":
        return None
    return ctx["summary"]["client_lag_p90_ms"]
