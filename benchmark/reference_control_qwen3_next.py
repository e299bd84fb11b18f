"""The control of ``correct`` for ``reference_qwen3_next``: the plain reference
one precision down, as ``reference_control.py`` is to ``reference.py``. Every
product against a weight matrix (the DeltaNet mixers' three projections, the
attention layers' four, the routed and the shared experts' feed-forwards, the
head) is computed in int8; the convolution, the recurrence, the norms, the
rotation, attention's scores and values, the shared expert's gate and the
router stay float32 (a near-tie in the router decides which expert computes;
the control is of the arithmetic, and keeps the choice the reference makes).
Put in the program's place this must come out as NOT correct:
``correct_readings.py --control reference_control_qwen3_next`` reads it over
many seeds on the chip, ``tests/benchmark`` at a width a test holds. A
benchmark run never runs it.
"""

from __future__ import annotations

import jax

from benchmark import reference_qwen3_next
from benchmark.reference_control import _dot_int8


def logits(params: dict, shape: dict, tokens, at) -> jax.Array:
    return reference_qwen3_next.logits(params, shape, tokens, at, dot=_dot_int8)
