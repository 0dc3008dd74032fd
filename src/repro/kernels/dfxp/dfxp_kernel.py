"""Pallas TPU kernel: fused DFXP quantize + clip + overflow counting.

The quantization site is the hottest elementwise op in DFXP training — it
runs on every activation, backprop signal, and parameter-use. Unfused, the
paper's recipe costs 4 HBM passes per site (round, two overflow compares,
clip); this kernel does one read + one write per tile and keeps the
overflow statistics as per-tile partial sums in VMEM.

TPU adaptation notes:
  * tiles are (block_m × block_n) in VMEM, block_n a multiple of 128
    (lane width) and block_m a multiple of 8 (f32 sublanes);
  * ``step``/``inv_step`` are precomputed bit-exact powers of two and land
    in SMEM as (1,1) scalars — ``exp2`` inside the kernel would re-derive
    them through a polynomial approximation (observed inexact on CPU XLA,
    see core.quant.exact_pow2);
  * per-tile statistics go to one (8, 128) f32 tile per grid step —
    lanes 0 and 1 of row 0 hold the two counts, the rest is 0 — summed by
    the caller: cheaper than cross-tile atomics, exact because counts are
    integers ≪ 2^24, and a block shape Mosaic accepts (a (1, 1, 2) block
    of a (grid_m, grid_n, 2) array is not).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


_STATS_TILE = (8, 128)


def _kernel(step_ref, inv_ref, x_ref, y_ref, stats_ref, *, qmax: float,
            qmin: float):
    step = step_ref[0, 0]
    inv_step = inv_ref[0, 0]
    x = x_ref[...].astype(jnp.float32)
    m = jnp.round(x * inv_step)               # round-half-to-even
    over = (m > qmax) | (m < qmin)
    over_half = (m > qmax / 2) | (m < qmin / 2)
    y_ref[...] = (jnp.clip(m, qmin, qmax) * step).astype(y_ref.dtype)
    row = jax.lax.broadcasted_iota(jnp.int32, _STATS_TILE, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, _STATS_TILE, 1)
    n_over = jnp.sum(over.astype(jnp.float32), keepdims=True)
    n_half = jnp.sum(over_half.astype(jnp.float32), keepdims=True)
    stats_ref[...] = jnp.where((row == 0) & (lane == 0), n_over,
                               jnp.where((row == 0) & (lane == 1), n_half,
                                         0.0))


@functools.partial(jax.jit,
                   static_argnames=("width", "block_m", "block_n",
                                    "interpret"))
def dfxp_quantize_2d(x, step, inv_step, *, width: int, block_m: int = 256,
                     block_n: int = 512, interpret: bool = False):
    """``x``: [M, N] (M % block_m == 0, N % block_n == 0).

    Returns (y, stats[2]) with stats = (n_overflow, n_overflow_half).
    """
    M, N = x.shape
    qmax = float(2 ** (width - 1) - 1)
    qmin = -float(2 ** (width - 1))
    gm, gn = M // block_m, N // block_n
    step2 = jnp.asarray(step, jnp.float32).reshape(1, 1)
    inv2 = jnp.asarray(inv_step, jnp.float32).reshape(1, 1)

    y, stats = pl.pallas_call(
        functools.partial(_kernel, qmax=qmax, qmin=qmin),
        grid=(gm, gn),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
            pl.BlockSpec(_STATS_TILE, lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), x.dtype),
            jax.ShapeDtypeStruct((gm * _STATS_TILE[0], gn * _STATS_TILE[1]),
                                 jnp.float32),
        ],
        interpret=interpret,
    )(step2, inv2, x)
    tiles = stats.reshape(gm, _STATS_TILE[0], gn, _STATS_TILE[1])
    return y, tiles[:, 0, :, :2].sum(axis=(0, 1))
