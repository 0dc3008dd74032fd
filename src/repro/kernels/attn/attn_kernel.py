"""Pallas TPU kernel: fused flash-decode attention over a packed KV pool.

Single-query (decode) GQA attention computed **directly on the pool's
storage containers**: tiles of int8/int16 K/V mantissas stream from HBM,
are dequantized in-register against the per-layer/per-slot power-of-two
step (``value = mantissa * 2**e``, the
:class:`repro.serve.kv_pool.PackedKVCodec` layout), and feed an
online-softmax accumulator — so the f32 K/V never materializes and an
int8 cache really does read 4× fewer HBM bytes than float32 (the win
``codec.load`` + einsum throws away by widening first).

Grid layout (compiled path)::

        grid = (B, nsplit)               nsplit = ceil(W / block_w)

        qpos  [B]             -> SMEM (whole array, indexed by b)
        steps [B, 2]          -> SMEM (whole array, indexed by b)
        q     [B, K, G, hd]   -> tile [K, G, hd]          (all kv heads)
        k/v   [B, W, K, hd]   -> tile [block_w, K, hd]    (int8/int16/f32)
        pos   [B, ns, 1, bw]  -> tile [1, block_w]        (ring positions)
        out   [B, K, G, hd]   <- written on the last split

Every block keeps its last two dims either whole (``K, hd``; ``G, hd``;
``1, block_w`` after the wrapper pads ``pos`` to whole splits) or out of
the tiled pair altogether, which is what Mosaic requires of a block
shape; the kv heads of a window block ride in one tile and the kernel
walks them with a static loop.  Per-row scalars live in SMEM.

The split axis is innermost/sequential: VMEM scratch carries the running
``(m, l, acc)`` — partial max, softmax denominator, weighted-value
numerator, per kv head — across splits (flash combine:
``corr = exp(m_old - m_new)`` rescales both accumulators), and the final
reduction ``acc / l`` happens once on the last split.  Masked lanes
(empty slots ``pos < 0``, future positions, outside the sliding window)
contribute an exact 0, and a ragged last split is handled **in-kernel**
by a slot-index bounds mask (lanes ``>= W`` are dropped and their V rows
zeroed) — the wrapper never pads the K/V buffers, because a ``jnp.pad``
copy of the whole pool per layer per token would reintroduce exactly the
HBM round-trip this kernel exists to eliminate.

Interpret mode (any non-TPU backend) instead runs ONE grid step on
full-shape blocks and executes :func:`repro.kernels.attn.ref.attend`
verbatim on the dequantized arrays — identical ops on identical shapes,
which makes the fused kernel **bit**-identical to the composite on CPU
(the same contract the qmatmul family keeps, and what the serve tests
pin).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.policy import MATMUL_PRECISION

from . import ref as R

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)
# Scoped VMEM of the split kernels.  The dispatch layer sizes their tiles
# to half of Mosaic's 16 MiB default, since inputs are double-buffered;
# the full-precision dots (MATMUL_PRECISION) add bf16 pieces of their
# operands on top — 17.9 MiB at llama3_8b widths, f32 pool, C=64,
# block_w=512 — so the kernels ask for twice the default (a v5e core
# has 128 MiB of VMEM).
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=32 * 2**20)


def _dequant(tile, step, width):
    """Tile load: int mantissas × power-of-two step (``width=None`` → raw)."""
    if width is None:
        return tile.astype(jnp.float32)
    return tile.astype(jnp.float32) * step


def split_pos(pos, block_w: int):
    """``pos`` [B, W] → [B, nsplit, 1, block_w], padded with -1 (empty).

    Gives the position tile a block whose last two dims are whole, for
    any ``block_w``; the pad lanes are masked like empty ring slots.
    """
    B, W = pos.shape
    nsplit = pl.cdiv(W, block_w)
    pos = jnp.pad(pos, ((0, 0), (0, nsplit * block_w - W)),
                  constant_values=-1)
    return pos.reshape(B, nsplit, 1, block_w)


def scratch(K: int, rows: int, hd: int):
    """VMEM scratch for the per-head running (max, denominator, numerator)."""
    return [pltpu.VMEM((K, rows, 1), jnp.float32),
            pltpu.VMEM((K, rows, 1), jnp.float32),
            pltpu.VMEM((K, rows, hd), jnp.float32)]


def init_scratch(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, m_ref.dtype)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def online_update(m_ref, l_ref, acc_ref, h: int, qf, kf, vf, valid,
                  scale: float):
    """Fold one key/value tile into kv head ``h``'s flash accumulators.

    ``qf`` [rows, hd] · ``kf``/``vf`` [n, hd] f32 · ``valid`` broadcastable
    to [rows, n].  ``exp(-inf - m) == 0`` on the first tile.
    """
    s = jax.lax.dot_general(qf, kf, (((1,), (1,)), ((), ())),
                            precision=MATMUL_PRECISION,
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid, s, -1e30)
    m_old = m_ref[h]
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_old - m_new)
    l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
        p, vf, (((1,), (0,)), ((), ())), precision=MATMUL_PRECISION,
        preferred_element_type=jnp.float32)
    m_ref[h] = m_new


def finish(l_ref, acc_ref):
    return acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def _split_kernel(qpos_ref, steps_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, width, scale: float, window,
                  causal: bool, nsplit: int, K: int, block_w: int, W: int):
    b, r = pl.program_id(0), pl.program_id(1)

    @pl.when(r == 0)
    def _init():
        init_scratch(m_ref, l_ref, acc_ref)

    pos = pos_ref[0, 0]                         # [1, block_w] int32
    # ragged tail: rows past the true window length read out-of-bounds
    # garbage — their pos lanes are -1 (padded), and their V rows are
    # zeroed so the 0-probability × garbage product stays an exact 0
    row = jax.lax.broadcasted_iota(jnp.int32, (block_w, 1), 0)
    inb = r * block_w + row < W
    d = qpos_ref[b] - pos
    valid = pos >= 0
    if causal:
        valid = valid & (d >= 0)
    if window:
        valid = valid & (d < window)
    k_step = v_step = None
    if width is not None:
        k_step, v_step = steps_ref[b, 0], steps_ref[b, 1]
    for h in range(K):
        kf = _dequant(k_ref[0, :, h, :], k_step, width)
        vf = jnp.where(inb, _dequant(v_ref[0, :, h, :], v_step, width), 0.0)
        online_update(m_ref, l_ref, acc_ref, h, q_ref[0, h], kf, vf, valid,
                      scale)

    @pl.when(r == nsplit - 1)
    def _done():
        o_ref[0] = finish(l_ref, acc_ref).astype(o_ref.dtype)


def _batched_kernel(qpos_ref, steps_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
                    *, width, scale: float, window, causal: bool):
    """One grid step, full-shape blocks: ref.attend on the loaded arrays."""
    exp = (slice(None), None, None, None)
    kf = _dequant(k_ref[...], steps_ref[...][:, 0][exp], width)
    vf = _dequant(v_ref[...], steps_ref[...][:, 1][exp], width)
    o_ref[...] = R.attend(q_ref[...], kf, vf, pos_ref[...], qpos_ref[...],
                          scale=scale, window=window, causal=causal)


@functools.partial(jax.jit, static_argnames=(
    "width", "block_w", "scale", "window", "causal", "interpret"))
def flash_decode_call(q, k, v, pos, qpos, steps, *, width, block_w: int,
                      scale: float, window, causal: bool, interpret: bool):
    """Blocked flash-decode over the raw (unpadded) pool buffers.

    ``q``: f32 [B, K, G, hd] · ``k``/``v``: int8/int16/f32 [B, W, K, hd] ·
    ``pos``: int32 [B, W] · ``qpos``: int32 [B] · ``steps``: f32
    [B, 2] dequant steps ``[2**k_e, 2**v_e]`` (ignored for
    ``width=None``).  Returns f32 [B, K, G, hd].  ``W`` need not be a
    ``block_w`` multiple — the ragged tail is masked in-kernel.
    ``block_w >= W`` in interpret mode runs the single-step full-shape
    body (bit-identical to ``ref.attend``).
    """
    B, K, G, hd = q.shape
    W = k.shape[1]
    out_shape = jax.ShapeDtypeStruct((B, K, G, hd), jnp.float32)

    if interpret and block_w >= W:
        return pl.pallas_call(
            functools.partial(_batched_kernel, width=width, scale=scale,
                              window=window, causal=causal),
            out_shape=out_shape,
            interpret=True,
        )(qpos, steps, q, k, v, pos)

    nsplit = pl.cdiv(W, block_w)
    return pl.pallas_call(
        functools.partial(_split_kernel, width=width, scale=scale,
                          window=window, causal=causal, nsplit=nsplit,
                          K=K, block_w=block_w, W=W),
        grid=(B, nsplit),
        in_specs=[
            _SMEM,                                                  # qpos
            _SMEM,                                                  # steps
            pl.BlockSpec((1, K, G, hd), lambda b, r: (b, 0, 0, 0)),
            pl.BlockSpec((1, block_w, K, hd), lambda b, r: (b, r, 0, 0)),
            pl.BlockSpec((1, block_w, K, hd), lambda b, r: (b, r, 0, 0)),
            pl.BlockSpec((1, 1, 1, block_w), lambda b, r: (b, r, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, K, G, hd), lambda b, r: (b, 0, 0, 0)),
        out_shape=out_shape,
        scratch_shapes=scratch(K, G, hd),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(qpos, steps, q, k, v, split_pos(pos, block_w))


# -- paged variant: one extra block-table indirection ---------------------
#
# The paged pool (repro.serve.paged) stores K/V as [n_pages, P, K, hd]
# arenas with per-PAGE exponents and maps logical token blocks through a
# per-request block table bt [B, nblocks].  The split axis becomes the
# page axis: split r of batch row b streams physical page bt[b, r] —
# expressed as a scalar-prefetch index_map (PrefetchScalarGridSpec), so
# the gather happens in the tile DMA, not as a host-side copy of the
# arena.  The per-page dequant steps are gathered through the same table
# by the wrapper ([B, 2·nblocks], SMEM).  No ragged-tail mask is needed
# (Wp = nblocks·P exactly); rows the request never wrote — including
# every row of the null page 0 — carry pos == -1 and mask out like empty
# ring slots.


def _paged_split_kernel(bt_ref, qpos_ref, steps_ref, q_ref, k_ref, v_ref,
                        pos_ref, o_ref, m_ref, l_ref, acc_ref, *, width,
                        scale: float, window, causal: bool, nblocks: int,
                        K: int):
    del bt_ref                                  # consumed by the index maps
    b, r = pl.program_id(0), pl.program_id(1)

    @pl.when(r == 0)
    def _init():
        init_scratch(m_ref, l_ref, acc_ref)

    pos = pos_ref[0, 0]                         # [1, P] logical positions
    d = qpos_ref[b] - pos
    valid = pos >= 0
    if causal:
        valid = valid & (d >= 0)
    if window:
        valid = valid & (d < window)
    k_step = v_step = None
    if width is not None:
        k_step, v_step = steps_ref[b, 2 * r], steps_ref[b, 2 * r + 1]
    for h in range(K):
        kf = _dequant(k_ref[0, :, h, :], k_step, width)
        vf = _dequant(v_ref[0, :, h, :], v_step, width)
        online_update(m_ref, l_ref, acc_ref, h, q_ref[0, h], kf, vf, valid,
                      scale)

    @pl.when(r == nblocks - 1)
    def _done():
        o_ref[0] = finish(l_ref, acc_ref).astype(o_ref.dtype)


def _paged_batched_kernel(bt_ref, qpos_ref, steps_ref, q_ref, k_ref, v_ref,
                          pos_ref, o_ref, *, width, scale: float, window,
                          causal: bool):
    """One grid step, full shapes: the ref composite through the gather."""
    bt = bt_ref[...]
    kf = jnp.take(k_ref[...], bt, axis=0).astype(jnp.float32)
    vf = jnp.take(v_ref[...], bt, axis=0).astype(jnp.float32)
    if width is not None:
        kf = kf * jnp.take(steps_ref[...][:, 0], bt)[..., None, None, None]
        vf = vf * jnp.take(steps_ref[...][:, 1], bt)[..., None, None, None]
    B, nblocks, P = kf.shape[:3]
    shp = (B, nblocks * P) + kf.shape[3:]
    o_ref[...] = R.attend(q_ref[...], kf.reshape(shp), vf.reshape(shp),
                          pos_ref[...], qpos_ref[...], scale=scale,
                          window=window, causal=causal)


def page_steps(steps, bt):
    """Per-page steps [n_pages, 2] → per-(row, block) [B, 2·nblocks]."""
    return steps[bt].reshape(bt.shape[0], -1)


@functools.partial(jax.jit, static_argnames=(
    "width", "scale", "window", "causal", "interpret", "force_split"))
def flash_decode_paged_call(q, k, v, bt, pos, qpos, steps, *, width,
                            scale: float, window, causal: bool,
                            interpret: bool, force_split: bool = False):
    """Blocked flash-decode through a per-request block table.

    ``q``: f32 [B, K, G, hd] · ``k``/``v``: int8/int16/f32
    [n_pages, P, K, hd] page arenas · ``bt``: int32 [B, nblocks] ·
    ``pos``: int32 [B, nblocks·P] logical positions (-1 = empty) ·
    ``qpos``: int32 [B] · ``steps``: f32 [n_pages, 2] per-page dequant
    steps.  Returns f32 [B, K, G, hd].  Interpret mode runs the
    full-shape gather body (bit-identical to
    ``ref.paged_decode_attention_ref``) unless ``force_split`` exercises
    the scalar-prefetch split path (same math, split-order softmax).
    """
    B, K, G, hd = q.shape
    P = k.shape[1]
    nblocks = bt.shape[1]
    out_shape = jax.ShapeDtypeStruct((B, K, G, hd), jnp.float32)

    if interpret and not force_split:
        return pl.pallas_call(
            functools.partial(_paged_batched_kernel, width=width, scale=scale,
                              window=window, causal=causal),
            out_shape=out_shape,
            interpret=True,
        )(bt, qpos, steps, q, k, v, pos)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                   # bt rides ahead of tiles
        grid=(B, nblocks),
        in_specs=[
            _SMEM,                                                  # qpos
            _SMEM,                                                  # steps
            pl.BlockSpec((1, K, G, hd), lambda b, r, bt: (b, 0, 0, 0)),
            pl.BlockSpec((1, P, K, hd),
                         lambda b, r, bt: (bt[b, r], 0, 0, 0)),     # k page
            pl.BlockSpec((1, P, K, hd),
                         lambda b, r, bt: (bt[b, r], 0, 0, 0)),     # v page
            pl.BlockSpec((1, 1, 1, P), lambda b, r, bt: (b, r, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, K, G, hd), lambda b, r, bt: (b, 0, 0, 0)),
        scratch_shapes=scratch(K, G, hd),
    )
    return pl.pallas_call(
        functools.partial(_paged_split_kernel, width=width, scale=scale,
                          window=window, causal=causal, nblocks=nblocks,
                          K=K),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(bt, qpos, page_steps(steps, bt), q, k, v, split_pos(pos, P))
