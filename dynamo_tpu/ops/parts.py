"""Products of float32 activations against bfloat16 weights, the activation
taken in bfloat16 parts.

The MXU multiplies bfloat16: a float32 activation handed to it is rounded to
8 bits of mantissa first, and that rounding is the noise a bfloat16 program
carries from layer to layer. A model module that keeps its activations
float32 chooses, product by product, how many bfloat16 parts of the
activation it multiplies (what is left of it after the parts before, rounded
again: ``8 * parts`` bits), from readings against its float32 reference
(``models/jamba.py``, ``models/lfm2.py``; ``models/kimi_linear.py``'s ``wdot`` is
an einsum over :func:`operand_parts`). The weight IS bfloat16, so it needs no parts.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _split(x: jax.Array, parts: int, dtype) -> List[jax.Array]:
    """``parts`` arrays of ``dtype``, each a bfloat16 value, whose sum is the
    float32 ``x`` to ``8 * parts`` bits."""
    split, rest = [], x
    for _ in range(parts):
        part = jax.lax.reduce_precision(rest, exponent_bits=8, mantissa_bits=7)
        split.append(part.astype(dtype))
        rest = rest - part
    return split


def dot_parts(x: jax.Array, w: jax.Array, parts: int = 1) -> jax.Array:
    """``x @ w`` with a float32 result, for a float32 ``x``. Against a
    bfloat16 weight (the served case) ``x`` goes to the MXU as ``parts``
    bfloat16 arrays whose sum is ``x`` to ``8 * parts`` bits, stacked into ONE
    product so that the weight is read once, their products added in float32.
    The rounding is ``reduce_precision``, which the compiler keeps (a float32
    -> bfloat16 -> float32 pair of converts it may drop: models/kimi_linear.py
    found it so). Any other weight (the float32 weights of a CPU test) is
    multiplied as it is, at float32's own precision."""
    x = x.astype(jnp.float32)
    if w.dtype != jnp.bfloat16:
        return jnp.dot(x, w.astype(jnp.float32), precision=HIGHEST)
    if parts == 1:
        return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)
    return jnp.dot(jnp.stack(_split(x, parts, w.dtype)), w,
                   preferred_element_type=jnp.float32).sum(axis=0)


def operand_parts(x: jax.Array, dtype, parts: int) -> List[jax.Array]:
    """What of a float32 ``x`` a grouped product multiplies against the
    experts' weights of ``dtype`` (``ops/moe.py:dropless_experts``'
    ``parts_of``): ``parts`` bfloat16 parts for a bfloat16 weight, itself for
    any other. On the CPU the parts are handed over as float32 copies (the
    interpreted kernel's dot has no bfloat16 x bfloat16 -> float32 for every
    shape; the copies give the same parts and the same sums, exactly)."""
    if dtype != jnp.bfloat16:
        return [x]
    return _split(x, parts, jnp.float32 if jax.default_backend() == "cpu" else dtype)
