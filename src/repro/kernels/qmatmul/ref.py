"""Pure-jnp oracle for the quantized matmul family — differentiable.

The forward semantics match the fused kernel exactly; the VJP semantics
match its custom backward: straight-through gradients through the operand
rounding (quantized co-operands), with an optional gradient-side rounding
of the cotangent (``grad_width``) mirroring ``qbound``.  ``jax.grad`` of
:func:`qmatmul_ref` is therefore the bit-level oracle for the fused
dgrad/wgrad kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.policy import MATMUL_PRECISION
from repro.core.quant import exact_pow2


def _q(x, e, width):
    step = exact_pow2(e)
    qmax = float(2 ** (width - 1) - 1)
    qmin = -float(2 ** (width - 1))
    return jnp.clip(jnp.round(x.astype(jnp.float32) / step), qmin, qmax) * step


@functools.lru_cache(maxsize=None)
def _make_ste(width):
    """Operand rounding with a straight-through (identity) backward."""

    @jax.custom_vjp
    def ste(x, e):
        return _q(x, e, width)

    def fwd(x, e):
        return _q(x, e, width), None

    def bwd(_, ct):
        return ct, jnp.float32(0)

    ste.defvjp(fwd, bwd)
    return ste


@functools.lru_cache(maxsize=None)
def _make_gsite(width):
    """Identity forward; rounds the cotangent on the way back (qbound-style)."""

    @jax.custom_vjp
    def gs(y, e_g):
        del e_g
        return y

    def fwd(y, e_g):
        return y, (e_g,)

    def bwd(res, ct):
        (e_g,) = res
        return _q(ct, e_g, width), jnp.float32(0)

    gs.defvjp(fwd, bwd)
    return gs


def qmatmul_ref(a, b, e_a, e_b, *, width: int, quant_a: bool = True,
                quant_b: bool = True, transpose_b: bool = False,
                grad_width=None, e_g=0.0):
    aq = _make_ste(width)(a, jnp.asarray(e_a, jnp.float32)) if quant_a else a
    bq = _make_ste(width)(b, jnp.asarray(e_b, jnp.float32)) if quant_b else b
    if transpose_b:
        c = jax.lax.dot_general(aq, bq, (((1,), (1,)), ((), ())),
                                precision=MATMUL_PRECISION,
                                preferred_element_type=jnp.float32)
    else:
        c = jnp.dot(aq, bq, precision=MATMUL_PRECISION,
                    preferred_element_type=jnp.float32)
    c = c.astype(a.dtype)
    if grad_width is not None:
        c = _make_gsite(grad_width)(c, jnp.asarray(e_g, jnp.float32))
    return c
