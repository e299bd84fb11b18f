"""Mean, over the window's requests, of (last token time - first token time) /
(output tokens - 1), as the client saw it in the traced run. Recorded, not
judged: between seeds it spread 4.3-9 % in `batch` (PR 23), and no bound may
pass 10 % nor stand under twice the spread."""

NAME = "tpot_mean_ms"
UNIT = "ms"
LAYER = "benchmark client"
MOVES = "ttft_mean_ms"


def read(ctx):
    return ctx["summary"]["tpot_mean_ms"]
