"""Shared tiling policy for the Pallas kernels: padding + block choice.

Both kernel families (dfxp quantize, qmatmul fwd/dgrad/wgrad) pad their
operands up to block multiples before the ``pallas_call`` and slice the
result back.  Zero padding is semantically free for every kernel here:
pads quantize to 0 (0 never overflows, so the statistics are exact) and
contribute exactly 0.0 to f32 dot-product accumulations.

Block heuristics live here so the two ``ops.py`` wrappers and the
dispatch layer agree on one notion of "tile-friendly"; the measured
autotune cache in :mod:`repro.kernels.dispatch` overrides these numbers
per shape on compiled backends.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def default_interpret() -> bool:
    """Backend detection, resolved once per process.

    Compiled Pallas on TPU; everywhere else (CPU/GPU containers) the
    kernels run in interpret mode — numerically identical, used by tests
    and benchmarks.
    """
    if _BACKEND["interpret"] is None:
        _BACKEND["interpret"] = jax.default_backend() != "tpu"
    return _BACKEND["interpret"]


def resolve_interpret(interpret) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


_BACKEND = {"interpret": None}


# ---------------------------------------------------------------------------
# block heuristics
# ---------------------------------------------------------------------------

def mm_blocks(kind: str, R: int, C: int, D: int) -> tuple:
    """Heuristic (block_r, block_c, block_d) for an (R, C) output with
    reduction length D, per contraction layout (see qmatmul.qmm_2d).

    Lane and contraction tiles are 128-aligned to feed the MXU directly;
    dims that only ever sit on the sublane axis shrink in multiples of 8
    for skinny operands.  In ``tn`` the output-row dim R is a *lane* dim
    of the left operand tile (and D a sublane dim), so the alignment
    roles swap.
    """
    if kind == "tn":
        br = min(128, round_up(R, 128))
        bd = min(128, round_up(D, 8))
    else:
        br = min(128, round_up(R, 8))
        bd = min(128, round_up(D, 128))
    bc = min(128, round_up(C, 128))
    return br, bc, bd


def quantize_blocks(M: int, N: int) -> tuple:
    """Heuristic (block_m, block_n) for the elementwise quantize kernel."""
    bn = 128
    while bn * 2 <= min(N, 512):
        bn *= 2
    bm = 8
    while bm * 2 <= min(M, 256):
        bm *= 2
    return bm, bn


# ---------------------------------------------------------------------------
# padding
# ---------------------------------------------------------------------------

def pad2d(x, rows: int, cols: int):
    """Zero-pad a 2D array up to (rows, cols); no-op when already there."""
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    if pr or pc:
        return jnp.pad(x, ((0, pr), (0, pc)))
    return x
