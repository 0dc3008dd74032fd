"""Pallas TPU kernels: DFXP quantized matmul family with fused operand rounding.

One kernel body, three contraction layouts — together they cover the whole
training graph of a quantized weighted sum (paper §6-§7: narrow multiplier
operands, wide f32 accumulators == the TPU MXU's native mode):

  * ``nn`` — ``C[M,N] = q(A)[M,K] @ q(B)[K,N]``            (forward)
  * ``nt`` — ``C[M,K] = q(G)[M,N] @ q(B)[K,N]^T``          (dgrad)
  * ``tn`` — ``C[K,N] = q(A)[M,K]^T @ q(G)[M,N]``          (wgrad)

Quantization is *per operand* and optional (``width=None`` loads the tile
as-is): the forward fuses weight rounding into the B loads, the backward
kernels fuse the cotangent's DFXP rounding into the G loads — matching the
``qbound`` numerics — so each pass is one HBM round-trip instead of a
quantize→matmul chain.

TPU adaptation:
  * 128-aligned lane/contraction tiles feed the MXU directly; the
    accumulator lives in a VMEM scratch tile across the reduction grid
    axis (innermost/sequential);
  * operand scales are bit-exact powers of two in a (1, 4) SMEM-resident
    operand: ``[step_a, 1/step_a, step_b, 1/step_b]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.policy import MATMUL_PRECISION

# Scoped VMEM of the kernel.  The dispatch layer's largest tiles, double
# buffered and with the bf16 pieces of the full-precision dot
# (MATMUL_PRECISION) on top, outgrow Mosaic's 16 MiB default; the
# dispatch layer keeps its estimate under half of this (a v5e core has
# 128 MiB of VMEM).
VMEM_LIMIT_BYTES = 64 * 2**20

# (lhs contracting dims, rhs contracting dims) per layout.
_CONTRACT = {"nn": ((1,), (0,)), "nt": ((1,), (1,)), "tn": ((0,), (0,))}


def _load(ref, scales_ref, slot: int, width, cast):
    """Tile load with optional fused DFXP rounding (``width=None`` → raw)."""
    x = ref[...]
    if width is None:
        return x
    step = scales_ref[0, 2 * slot]
    inv_step = scales_ref[0, 2 * slot + 1]
    qmax = float(2 ** (width - 1) - 1)
    qmin = -float(2 ** (width - 1))
    m = jnp.round(x.astype(jnp.float32) * inv_step)
    return (jnp.clip(m, qmin, qmax) * step).astype(cast)


def _kernel(scales_ref, a_ref, b_ref, c_ref, acc_ref, *, kind: str,
            width_a, width_b, cast, nred: int):
    r = pl.program_id(2)

    @pl.when(r == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    aq = _load(a_ref, scales_ref, 0, width_a, cast)
    bq = _load(b_ref, scales_ref, 1, width_b, cast)
    acc_ref[...] += jax.lax.dot_general(
        aq, bq, (_CONTRACT[kind], ((), ())),
        precision=MATMUL_PRECISION,
        preferred_element_type=jnp.float32)

    @pl.when(r == nred - 1)
    def _done():
        c_ref[...] = acc_ref[...].astype(c_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "kind", "width_a", "width_b", "block_r", "block_c", "block_d",
    "cast", "out_dtype", "interpret"))
def qmm_2d(a, b, scales, *, kind: str, width_a, width_b, block_r: int,
           block_c: int, block_d: int, cast=jnp.float32, out_dtype=None,
           interpret: bool = False):
    """Blocked quantized matmul on pre-padded 2D operands.

    Output is (R, C) with reduction length D; per layout the operand
    shapes are ``nn``: a[R,D], b[D,C] · ``nt``: a[R,D'], b[C,D'] (D=D') ·
    ``tn``: a[D,R], b[D,C].  All dims must be multiples of their block.
    ``scales`` is the (1, 4) array [step_a, 1/step_a, step_b, 1/step_b].
    """
    if kind == "nn":
        R, D = a.shape
        _, C = b.shape
        a_spec = pl.BlockSpec((block_r, block_d), lambda i, j, r: (i, r))
        b_spec = pl.BlockSpec((block_d, block_c), lambda i, j, r: (r, j))
    elif kind == "nt":
        R, D = a.shape
        C, _ = b.shape
        a_spec = pl.BlockSpec((block_r, block_d), lambda i, j, r: (i, r))
        b_spec = pl.BlockSpec((block_c, block_d), lambda i, j, r: (j, r))
    elif kind == "tn":
        D, R = a.shape
        _, C = b.shape
        a_spec = pl.BlockSpec((block_d, block_r), lambda i, j, r: (r, i))
        b_spec = pl.BlockSpec((block_d, block_c), lambda i, j, r: (r, j))
    else:
        raise ValueError(f"unknown layout {kind!r}")

    nred = D // block_d
    out_dtype = a.dtype if out_dtype is None else out_dtype

    return pl.pallas_call(
        functools.partial(_kernel, kind=kind, width_a=width_a,
                          width_b=width_b, cast=cast, nred=nred),
        grid=(R // block_r, C // block_c, nred),
        in_specs=[
            pl.BlockSpec((1, 4), lambda i, j, r: (0, 0)),
            a_spec,
            b_spec,
        ],
        out_specs=pl.BlockSpec((block_r, block_c), lambda i, j, r: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, C), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_r, block_c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(scales, a, b)
