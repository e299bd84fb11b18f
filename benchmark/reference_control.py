"""The control of ``correct``: the plain reference one precision down.

``reference.py``'s forward pass with every product against a weight matrix
computed in int8 (weights quantised per output column, activations per row,
symmetric, accumulated in int32), the step below the bf16 the configurations
state and the one a later PR would be tempted by. Attention's scores and
values stay float32. Put in the program's place (its own first choice at
every position of the same history), this must come out as NOT correct:
``reference_child.py --control reference_control`` reads its numbers beside
the program's, ``correct_readings.py`` does so over many seeds on the chip,
and ``tests/benchmark`` keeps it as a test at a tiny width. A benchmark run
never runs it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import reference


def _quantise(a, axis):
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(a / scale).astype(jnp.int8), scale


def _dot_int8(x, w):
    qx, sx = _quantise(x, -1)
    qw, sw = _quantise(w.astype(jnp.float32), 0)
    acc = jax.lax.dot_general(qx, qw, (((x.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * sw


def logits(params: dict, shape: dict, tokens, at) -> jax.Array:
    return reference.logits(params, shape, tokens, at, dot=_dot_int8)
