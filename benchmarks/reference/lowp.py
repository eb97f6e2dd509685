"""The precisions a reference can be computed in.

``float32`` is the reference itself: float32 operands, matmul precision
"highest".  The lower ones are the controls: the same arithmetic with
every matmul operand rounded to the named type first, and every cotangent
on the way back.  ``fp8`` is the plain cast to float8_e4m3fn, the step
below bfloat16 that would tempt a later PR; ``fp8_scaled`` rounds under a
per-tensor scale, as a careful fp8 path would (PERF.md section 2 says what
each of them reads).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# name -> (type, scaled per tensor)
_TYPES = {"float32": (None, False), "bfloat16": (jnp.bfloat16, False),
          "fp8": (jnp.float8_e4m3fn, False),
          "fp8_scaled": (jnp.float8_e4m3fn, True)}


def _scaled_round(x, dt):
    """Round to ``dt`` under a per-tensor scale that puts the largest
    magnitude at the type's largest finite value — what an fp8 path does
    so that small values (gradients above all) do not flush to zero."""
    top = float(jnp.finfo(dt).max)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return (x / scale).astype(dt).astype(jnp.float32) * scale


def rounder(precision: str):
    try:
        dt, scaled = _TYPES[precision]
    except KeyError:
        raise ValueError(f"unknown precision {precision!r}") from None
    if dt is None:
        return lambda x: x
    if not scaled:
        # the plain cast; its own derivative rounds the cotangents too
        return lambda x: x.astype(dt).astype(jnp.float32)

    @jax.custom_vjp
    def r(x):
        return _scaled_round(x, dt)

    r.defvjp(lambda x: (_scaled_round(x, dt), None),
             lambda _, g: (_scaled_round(g, dt),))
    return r


def matmul(a, b, r):
    return jnp.matmul(r(a), r(b), precision="highest")
