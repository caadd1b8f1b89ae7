"""How the plain references contract tensors.

Every matrix product and contraction of a reference model goes through one
of two objects:

- :data:`EXACT`: float32 at ``Precision.HIGHEST`` (a TPU otherwise runs a
  float32 product in bfloat16 passes).  This is the reference.
- :data:`INT8`: the control.  Both operands of every contraction, forward
  and backward, are rounded to int8 with one symmetric scale per tensor
  (``amax / 127``) before an exact product: the precision step below the
  bfloat16 products the configurations state, and the one a later change
  would be tempted to take (int8 doubles the v5e MXU's peak).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _einsum(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def quantize_int8(x):
    """Round ``x`` to the int8 grid of its own absolute maximum, in float32."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.round(x / scale) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum_int8(spec: str, a, b):
    return _einsum(spec, quantize_int8(a), quantize_int8(b))


def _einsum_int8_fwd(spec, a, b):
    qa, qb = quantize_int8(a), quantize_int8(b)
    return _einsum(spec, qa, qb), (qa, qb)


def _einsum_int8_bwd(spec, res, g):
    qa, qb = res
    _, vjp = jax.vjp(lambda x, y: _einsum(spec, x, y), qa, qb)
    return vjp(quantize_int8(g))


_einsum_int8.defvjp(_einsum_int8_fwd, _einsum_int8_bwd)


class Numerics:
    """``einsum(spec, a, b)`` and ``mm(a, b)`` (``a @ b`` over a's last
    axis) in one precision."""

    def __init__(self, name: str, einsum):
        self.name = name
        self.einsum = einsum

    def mm(self, a, b):
        return self.einsum("...i,ij->...j", a, b)


EXACT = Numerics("float32-highest", _einsum)
INT8 = Numerics("int8-per-tensor", _einsum_int8)
