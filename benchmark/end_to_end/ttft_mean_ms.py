"""Mean time to first token over the requests that fell due inside the window
(open loop: from the instant due; closed: from the send)."""

NAME = "ttft_mean_ms"
UNIT = "ms"


def read(ctx):
    return ctx["summary"]["ttft_mean_ms"]
