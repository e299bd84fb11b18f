"""Output tokens received inside the window / window seconds, over every
request, in the traced run: capacity, in a saturated cell. Recorded, not
judged: which requests meet in one prefill dispatch moves it by 4-8 % between
seeds at 48 s (PR 23), and no bound may pass 10 % nor stand under twice the
spread. In a closed loop it is tied to the judged latency by Little's law:
callers = requests/s x mean time per request."""

NAME = "output_tokens_per_s"
UNIT = "tokens/s"
LAYER = "benchmark client"
MOVES = "ttft_mean_ms"


def read(ctx):
    return ctx["summary"]["output_tokens_per_s"]
