"""The part of a start-up that is Python under the interpreter lock, so that
a warm start pays it one for one: ``setup_phase_s.before_main`` (process start
to ``amain``: the interpreter and the imports) + ``.lower`` (the time under
``warmup``'s ``tracing_turn``: tracing and lowering the step programs), in s,
from the snapshot before the pre-roll (GET /debug/engine). None where the
program has no such counter."""

NAME = "setup_python_s"
UNIT = "s"
LAYER = "HTTP frontend and engine host loop"
MOVES = "setup_s"


def read(ctx):
    phases = (ctx.get("engine_before") or {}).get("setup_phase_s") or {}
    if phases.get("before_main") is None or phases.get("lower") is None:
        return None
    return phases["before_main"] + phases["lower"]
