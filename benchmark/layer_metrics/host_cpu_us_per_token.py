"""CPU the server process spent per output token: utime + stime of the child
over the window (/proc/<pid>/stat) / output tokens received in the window.
Frontend, preprocessor and the engine's host loop share the process."""

NAME = "host_cpu_us_per_token"
UNIT = "us"
LAYER = "HTTP frontend and engine host loop"
MOVES = "ttft_mean_ms"


def read(ctx):
    tokens = ctx["summary"]["output_tokens_in_window"]
    if ctx["child_cpu_s"] is None or not tokens:
        return None
    return ctx["child_cpu_s"] * 1e6 / tokens
