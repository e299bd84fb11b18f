"""From the parent's start to the start of the window: model directory, child
start, device init, weights, warm-up, probes and the pre-roll."""

NAME = "setup_s"
UNIT = "s"


def read(ctx):
    return ctx["setup_s"]
