"""Share of device 0's busy time spent in collective operations: the union of
the intervals of its collective op events / the union of its module events,
from the reduced trace.

An op event is named by its HLO line, ``%<instruction name> = <type> <op>(…``
(trace_reduce.short_op_name). It is a collective if its instruction name
starts with ``all-reduce``, ``all-gather``, ``reduce-scatter`` or
``collective-permute``: the compiler names an instruction after its op, and
the asynchronous halves (``all-reduce-start``, ``all-gather-done``) and the
numbered copies (``all-reduce.12``) keep the prefix. A fusion that only feeds
a collective is compute and is not counted. One device has no collectives and
reads 0 ops, so nothing is reported there."""

from benchmark import trace_reduce

NAME = "collective_share"
UNIT = "%"
LAYER = "sharding"
MOVES = "ttft_mean_ms"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute")


def is_collective(op_name: str) -> bool:
    return op_name.lstrip("%").startswith(COLLECTIVES)


def read(ctx):
    if not ctx["trace"] or ctx["chips"] < 2:
        return None
    dev = ctx["trace"]["devices"].get("0")
    if not dev or not dev["modules"]:
        return None
    busy = trace_reduce.busy_union_ns(dev["modules"])
    collective = trace_reduce.busy_union_ns([e for e in dev["ops"] if is_collective(e[0])])
    return 100.0 * collective / busy
