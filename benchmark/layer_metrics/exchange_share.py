"""Share of device 0's busy time spent in collective operations, found by the
operation and not by the instruction's name: the union of the intervals of the
op events whose HLO line's operation is ``all-reduce``, ``all-gather``,
``reduce-scatter``, ``collective-permute`` or ``all-to-all`` (their
asynchronous halves among them) / the union of its module events, from the
reduced trace.

``collective_share`` finds a collective by the NAME the compiler gives an
instruction it inserts (``%all-reduce.12 = ...``). A collective that a module
writes itself under ``shard_map`` is named after the JAX primitive (``%psum.283 =
f32[8,128,2304] all-reduce(...)``, ``%all_gather.7 = ... all-gather(...)``), so in
a cell whose exchange is written down (``models/mellum.py``) that reader sees
the compiler's own few and this one sees them all (ROADMAP B11: one reader, by
the operation). An op event is named by its HLO line, ``%<name> = <type>
<operation>(...`` (``trace_reduce.short_op_name`` keeps 96 characters of it: a
collective of one array fits). One device has no collectives: nothing is
reported there."""

import re

from benchmark import trace_reduce

NAME = "exchange_share"
UNIT = "%"
LAYER = "sharding"
MOVES = "ttft_mean_ms"
# `%name = <an array's type, or a tuple's in brackets> <operation>(`
COLLECTIVE = re.compile(
    r"^%?[\w.\-]+ = (?:\([^()]*\)|[\w\[\],]+) "
    r"(?:all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)(?:-start|-done)?\(")


def is_collective(op_line: str) -> bool:
    return bool(COLLECTIVE.match(op_line))


def read(ctx):
    if not ctx["trace"] or ctx["chips"] < 2:
        return None
    dev = ctx["trace"]["devices"].get("0")
    if not dev or not dev["modules"]:
        return None
    busy = trace_reduce.busy_union_ns(dev["modules"])
    collective = trace_reduce.busy_union_ns([e for e in dev["ops"] if is_collective(e[0])])
    return 100.0 * collective / busy
