"""The control of ``correct`` for ``reference_mellum``: the plain reference one
precision down, as ``reference_control.py`` is to ``reference.py``. Every
product against a weight matrix (the attention layers' four projections, the
experts' feed-forwards, the head) is computed in int8; the head norms, both
rotations, attention's scores and values and the router stay float32 (a
near-tie in the router decides which expert computes; the control is of the
arithmetic, and keeps the choice the reference makes). Put in the program's
place this must come out as NOT correct: ``correct_readings.py --control
reference_control_mellum`` reads it over many seeds on the chips,
``tests/benchmark`` at a width a test holds. A benchmark run never runs it.
"""

from __future__ import annotations

import jax

from benchmark import reference_mellum
from benchmark.reference_control import _dot_int8


def logits(params: dict, shape: dict, tokens, at) -> jax.Array:
    return reference_mellum.logits(params, shape, tokens, at, dot=_dot_int8)
