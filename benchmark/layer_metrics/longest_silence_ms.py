"""The longest stretch of the traced run's window in which no token of any
request reached the client (`stats.longest_silence_ms`): with the slots busy a
dispatch answers every 50-200 ms, so a silence of a second or more is a server
or a client that stood still. It is what tells a run that reads far off
because the machine stalled from one that is slower throughout (PR 26: one
`chat` run in 35 and two or more of the driver's six stood 11-27 % off with
nothing recorded that said why). Recorded, not judged."""

NAME = "longest_silence_ms"
UNIT = "ms"
LAYER = "benchmark client"
MOVES = "ttft_mean_ms"


def read(ctx):
    return ctx["summary"]["longest_silence_ms"]
