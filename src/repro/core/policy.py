"""Precision policy: which arithmetic, which widths, which container (paper §6).

The paper's headline configuration is ``dfxp`` with ``comp_width=10`` (all
computations: activations, weighted sums, and every gradient) and
``update_width=12`` (parameter storage — wide enough to accumulate many
small SGD contributions). ``fixed`` reproduces §4 (global radix point after
the ``fixed_int_bits``-th MSB), the float names reproduce §3.

``storage``:
  * ``sim``    — paper-faithful: values live in wide float containers and are
    merely *representable* in the target format (the paper's §7 simulation).
  * ``packed`` — beyond-paper production mode: parameters/momentum are stored
    as int8/int16 mantissas + per-group scales (real HBM savings); compute
    containers are ``compute_dtype``. Exactness: bfloat16 holds DFXP widths
    ≤ 9 exactly, float16 ≤ 12, float32 ≤ 25 (see formats.container_exact_bits).
"""
from __future__ import annotations

import dataclasses

import jax

from .formats import (
    BFLOAT16,
    FLOAT8_E4M3,
    FLOAT8_E5M2,
    FLOAT16,
    FLOAT32,
    DynamicFixedPoint,
    FixedPoint,
    Format,
    Observe,
    container_exact_bits,
)

# Precision of every f32 dot, in XLA and in the Pallas kernels.  The TPU
# MXU multiplies bf16: at DEFAULT precision XLA and Mosaic feed an f32
# operand to it as one bf16 value, 8 significant bits — fewer than a
# DFXP computation operand holds (comp_width 10), so the chip would
# round the paper's operands below their width.  HIGHEST splits each
# operand into bf16 pieces and keeps f32 products: exact for operands of
# up to 16 significant bits.  On the CPU it changes nothing.
MATMUL_PRECISION = jax.lax.Precision.HIGHEST

_FLOATS = {
    "float32": FLOAT32,
    "float16": FLOAT16,
    "bfloat16": BFLOAT16,
    "float8_e4m3": FLOAT8_E4M3,
    "float8_e5m2": FLOAT8_E5M2,
}


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    arithmetic: str = "float32"      # float32|bfloat16|float16|float8_*|fixed|dfxp
    comp_width: int = 10             # paper: 10 (computations)
    update_width: int = 12           # paper: 12 (parameter updates)
    fixed_int_bits: int = 5          # paper Fig.1: radix after 5th MSB
    max_overflow_rate: float = 1e-4  # paper: 0.01%
    update_interval: int = 100       # controller cadence, in steps
    stochastic_rounding: bool = False   # beyond-paper (param updates only)
    quantize_momentum: bool = True
    storage: str = "sim"             # sim|packed
    compute_dtype: str = "float32"   # container dtype for activations/compute
    grad_compress_bits: int = 0      # 0=off; 8|16: DFXP DP all-reduce compression
    a2a_compress_bits: int = 0       # 0=off; 8|16: MoE all_to_all in int lanes
    fused_matmul: bool = False       # route DFXP QTape.dot through the fused
    #   Pallas qmatmul (fwd + dgrad + wgrad custom-VJP kernels; see
    #   repro.kernels.dispatch). Bit-identical to the jnp composite;
    #   off by default because interpret-mode Pallas (any non-TPU
    #   backend) trades speed for kernel-faithful execution.
    fused_decode: bool = False       # serve-side: run decode attention as
    #   the fused Pallas flash-decode kernel (repro.kernels.attn) directly
    #   on the KV pool's storage containers — packed pools dequantize
    #   int8/int16 mantissas in the tile loads instead of materializing
    #   f32 K/V per layer (codec.load), which is where the 4×/2× HBM-read
    #   win of the packed cache actually cashes out. CLI --fused-decode.
    prefill_chunk: int = 0           # serve-side: chunked prefill size C.
    #   0 = whole-prompt prefill (the bit-for-bit reference path, one jit
    #   per (group, prompt_len)). C > 0: ServeEngine admits any queued
    #   request into any free slot immediately and runs one C-token
    #   prefill chunk per engine step interleaved with decode — ONE jit
    #   for any prompt length (ragged tails masked in-kernel), chunk K/V
    #   quantized straight into the packed pool (codec.append_chunk) and
    #   history attended off the packed storage (flash-prefill kernel
    #   when fused_decode). Attention-family models only; MoE/SSM keep
    #   the whole-prompt path. CLI --prefill-chunk.
    page_size: int = 0               # serve-side: paged KV pool page size P.
    #   0 = slot-major pool (contiguous [B, W] rings). P > 0: the pool
    #   stores fixed-size pages with per-request block tables
    #   (repro.serve.paged) — per-PAGE DFXP exponents, refcounted
    #   prompt-prefix sharing with copy-on-write, page-granular
    #   quantize-on-write. Forces chunked prefill (C defaults to P);
    #   dense global-attention family only. CLI --page-size.

    def __post_init__(self):
        if self.prefill_chunk < 0:
            raise ValueError("prefill_chunk must be >= 0")
        if self.page_size < 0:
            raise ValueError("page_size must be >= 0")
        if self.arithmetic not in (*_FLOATS, "fixed", "dfxp", "observe"):
            raise ValueError(f"unknown arithmetic {self.arithmetic!r}")
        if self.storage not in ("sim", "packed"):
            raise ValueError(f"unknown storage {self.storage!r}")
        if self.storage == "packed" and self.arithmetic == "dfxp":
            exact = container_exact_bits(self.compute_dtype)
            if self.comp_width > exact:
                raise ValueError(
                    f"comp_width={self.comp_width} not exactly representable "
                    f"in {self.compute_dtype} containers (max {exact})")

    # -- format accessors ---------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self.arithmetic != "float32"

    @property
    def dynamic(self) -> bool:
        return self.arithmetic == "dfxp"

    @property
    def observing(self) -> bool:
        return self.arithmetic == "observe"

    def comp_format(self) -> Format:
        """Format for activations, weighted sums, and all gradients."""
        if self.arithmetic == "observe":
            return Observe()
        if self.arithmetic in _FLOATS:
            f = _FLOATS[self.arithmetic]
            return None if f.name == "float32" else f
        if self.arithmetic == "fixed":
            return FixedPoint(self.comp_width, self.fixed_int_bits)
        return DynamicFixedPoint(self.comp_width)

    def update_format(self) -> Format:
        """Format for parameter (and momentum) storage."""
        if self.arithmetic == "observe":
            return Observe()
        if self.arithmetic in _FLOATS:
            f = _FLOATS[self.arithmetic]
            return None if f.name == "float32" else f
        if self.arithmetic == "fixed":
            return FixedPoint(self.update_width, self.fixed_int_bits)
        return DynamicFixedPoint(self.update_width)


# Paper's headline policies (Table 3 rows).
SINGLE_FLOAT = PrecisionPolicy("float32")
HALF_FLOAT = PrecisionPolicy("float16")
FIXED_20 = PrecisionPolicy("fixed", comp_width=20, update_width=20)
DFXP_10_12 = PrecisionPolicy("dfxp", comp_width=10, update_width=12)
