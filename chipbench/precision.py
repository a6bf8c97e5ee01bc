"""Matrix products of the plain references at a stated precision.

``"highest"`` is float32 as the configurations state it: on a TPU,
``Precision.HIGHEST``. ``"high"`` is the next precision below, the
three-pass bfloat16 product of ``Precision.HIGH`` on a TPU: each float32
operand split into a bfloat16 high part and a bfloat16 remainder, and the
three products that matter summed in float32. On a TPU it is asked of
the compiler; elsewhere (the CPU of a test run, where XLA ignores the
precision of a float32 dot) it is written out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LEVELS = ("highest", "high")


def _split(x):
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def einsum(spec: str, a, b, precision: str):
    """``jnp.einsum(spec, a, b)`` of float32 operands at ``precision``."""
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    if precision != "high":
        raise ValueError(f"precision {precision!r} not in {LEVELS}")
    if jax.default_backend() == "tpu":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGH,
                          preferred_element_type=jnp.float32)
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)

    def part(x, y):
        return jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)

    return part(a_hi, b_hi) + (part(a_hi, b_lo) + part(a_lo, b_hi))


def dot(a, b, precision: str):
    """``a @ b`` for a (..., k) and b (k, n) at ``precision``."""
    return einsum("...k,kn->...n", a, b, precision)
