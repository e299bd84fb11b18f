"""The control of ``correct`` for ``reference_xing4``: the plain reference one
precision down, as ``reference_control.py`` is to ``reference.py``. Every
product against a weight matrix (the MLA mixers' five projections, the dense,
routed and shared feed-forwards, the module's ``W_eh``, the head) is computed
in int8; the norms, the rotation, attention's scores and values, the router
AND the residual path's maps (``x̂ φ``, the Sinkhorn sweeps, the mixing) stay
float32: a near-tie in the router decides which expert computes and the maps
decide how every later layer is fed; the control is of the arithmetic, and
keeps the choices the reference makes. Put in the program's place this must
come out as NOT correct: ``correct_readings.py --control
reference_control_xing4`` reads it over many seeds on the chip,
``tests/benchmark`` at a width a test holds. A benchmark run never runs it.
"""

from __future__ import annotations

import jax

from benchmark import reference_xing4
from benchmark.reference_control import _dot_int8


def logits(params: dict, shape: dict, tokens, at) -> jax.Array:
    return reference_xing4.logits(params, shape, tokens, at, dot=_dot_int8)
