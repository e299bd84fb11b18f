"""The control of ``correct`` for ``reference_jamba``: the plain reference one
precision down, as ``reference_control.py`` is to ``reference.py``. Every
product against a weight matrix (the Mamba mixers' four projections, the
attention layers' four, the feed-forwards, the head) is computed in int8; the
selective scan, the convolution, the inner norms and attention's scores and
values stay float32. Put in the program's place this must come out as NOT
correct: ``correct_readings.py --control reference_control_jamba`` reads it
over many seeds on the chip, ``tests/benchmark`` at a width a test holds. A
benchmark run never runs it.
"""

from __future__ import annotations

import jax

from benchmark import reference_jamba
from benchmark.reference_control import _dot_int8


def logits(params: dict, shape: dict, tokens, at) -> jax.Array:
    return reference_jamba.logits(params, shape, tokens, at, dot=_dot_int8)
