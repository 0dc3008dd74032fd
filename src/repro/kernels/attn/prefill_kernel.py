"""Pallas TPU kernel: fused chunked-prefill attention over a packed KV pool.

A fixed-size chunk of ``C`` query positions attends **directly on the
pool's storage containers** — tiles of int8/int16 K/V mantissas stream
from HBM and are dequantized in-register against the per-layer/per-slot
power-of-two step, exactly like the flash-decode kernel
(:mod:`repro.kernels.attn.attn_kernel`) — plus its **own** chunk K/V in
f32, taken from the fresh projections rather than the pool so ring
eviction by the chunk's own write can never hide in-window keys.

Grid layout (compiled path)::

        grid = (B, nsplit + 1)           nsplit = ceil(W / block_w)

        p0/nv/steps                 -> SMEM (whole arrays, indexed by b)
        q     [B, K, C*G, hd]       -> tile [K, C*G, hd]     (all kv heads)
        k_new/v_new [B, C, K, hd]   -> tile [C, K, hd]       (f32 chunk KV)
        k/v   [B, W, K, hd]         -> tile [block_w, K, hd] (pool storage)
        pos   [B, ns, 1, block_w]   -> tile [1, block_w]
        out   [B, K, C*G, hd]       <- written on the last grid step

The wrapper moves the chunk's queries (and the output) to a head-major
``[B, K, C*G, hd]`` layout — a chunk-sized transpose, never a pool
copy — so every block keeps its last two dims whole, as Mosaic requires.
Splits ``0 .. nsplit-1`` walk the pool history (mask: ``0 <= pos < p0``,
window, ragged-tail bounds — all in-kernel, the pool is never padded or
copied); the final step ``nsplit`` scores the chunk against its own K/V
(causal ``j <= c``, ragged rows ``>= n_valid`` masked) and performs the
``acc / l`` reduction.  VMEM scratch carries the running
``(m, l, acc)`` per kv head with rows ``c * G + g``, combined across
steps with the standard flash correction.

Interpret mode (any non-TPU backend) runs ONE grid step on full-shape
blocks and executes :func:`repro.kernels.attn.ref.chunk_attend` verbatim
on the dequantized arrays — identical ops on identical shapes, making
the fused kernel **bit**-identical to the composite on CPU (the contract
every kernel family in this repo keeps).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref as R
from .attn_kernel import (_SMEM, COMPILER_PARAMS, _dequant, finish,
                          init_scratch, online_update, page_steps, scratch,
                          split_pos)


def _heads_major(q):
    """[B, C, K, G, hd] → [B, K, C*G, hd] (row ``c * G + g``)."""
    B, C, K, G, hd = q.shape
    return q.transpose(0, 2, 1, 3, 4).reshape(B, K, C * G, hd)


def _chunk_major(o, C: int):
    """Inverse of :func:`_heads_major`."""
    B, K, _, hd = o.shape
    return o.reshape(B, K, C, -1, hd).transpose(0, 2, 1, 3, 4)


def _chunk_step(q_ref, kn_ref, vn_ref, k_ref, v_ref, pos_ref, o_ref, m_ref,
                l_ref, acc_ref, *, p0, nv, k_step, v_step, r, nsplit: int,
                W, width, scale: float, window, causal: bool, C: int, G: int,
                K: int, block_w: int):
    """One grid step of either prefill kernel (history split or self)."""
    rows = C * G
    cidx = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // G

    @pl.when(r == 0)
    def _init():
        init_scratch(m_ref, l_ref, acc_ref)

    @pl.when(r < nsplit)
    def _history():
        pos = pos_ref[0, 0]                     # [1, block_w] int32
        d = (p0 + cidx) - pos                   # [rows, block_w]
        valid = (pos >= 0) & (pos < p0) & (cidx < nv)
        if causal:
            valid = valid & (d >= 0)
        if window:
            valid = valid & (d < window)
        inb = None
        if W is not None:                       # slot-major: ragged tail
            row = jax.lax.broadcasted_iota(jnp.int32, (block_w, 1), 0)
            inb = r * block_w + row < W
        for h in range(K):
            kf = _dequant(k_ref[0, :, h, :], k_step, width)
            vf = _dequant(v_ref[0, :, h, :], v_step, width)
            if inb is not None:
                vf = jnp.where(inb, vf, 0.0)
            online_update(m_ref, l_ref, acc_ref, h, q_ref[0, h], kf, vf,
                          valid, scale)

    @pl.when(r == nsplit)
    def _self_and_done():
        j = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
        dj = cidx - j                           # [rows, C]
        valid = (cidx < nv) & (j < nv)
        if causal:
            valid = valid & (dj >= 0)
        if window:
            valid = valid & (dj < window)
        for h in range(K):
            online_update(m_ref, l_ref, acc_ref, h, q_ref[0, h],
                          kn_ref[0, :, h, :], vn_ref[0, :, h, :], valid,
                          scale)
        o_ref[0] = finish(l_ref, acc_ref).astype(o_ref.dtype)


def _split_kernel(p0_ref, nv_ref, steps_ref, q_ref, kn_ref, vn_ref, k_ref,
                  v_ref, pos_ref, o_ref, m_ref, l_ref, acc_ref, *, width,
                  **kw):
    b = pl.program_id(0)
    k_step = v_step = None
    if width is not None:
        k_step, v_step = steps_ref[b, 0], steps_ref[b, 1]
    _chunk_step(q_ref, kn_ref, vn_ref, k_ref, v_ref, pos_ref, o_ref, m_ref,
                l_ref, acc_ref, p0=p0_ref[b], nv=nv_ref[b], k_step=k_step,
                v_step=v_step, r=pl.program_id(1), width=width, **kw)


def _batched_kernel(p0_ref, nv_ref, steps_ref, q_ref, kn_ref, vn_ref, k_ref,
                    v_ref, pos_ref, o_ref, *, width, scale: float, window,
                    causal: bool):
    """One grid step, full-shape blocks: ref.chunk_attend on loaded arrays."""
    exp = (slice(None), None, None, None)
    kf = _dequant(k_ref[...], steps_ref[...][:, 0][exp], width)
    vf = _dequant(v_ref[...], steps_ref[...][:, 1][exp], width)
    o_ref[...] = R.chunk_attend(q_ref[...], kf, vf, pos_ref[...],
                                kn_ref[...], vn_ref[...], p0_ref[...],
                                nv_ref[...], scale=scale, window=window,
                                causal=causal)


@functools.partial(jax.jit, static_argnames=(
    "width", "block_w", "scale", "window", "causal", "interpret"))
def flash_prefill_call(q, k_new, v_new, k, v, pos, p0, nv, steps, *, width,
                       block_w: int, scale: float, window, causal: bool,
                       interpret: bool):
    """Blocked chunked-prefill over the raw (unpadded) pool buffers.

    ``q``: f32 [B, C, K, G, hd] · ``k_new``/``v_new``: f32 [B, C, K, hd] ·
    ``k``/``v``: int8/int16/f32 [B, W, K, hd] · ``pos``: int32 [B, W] ·
    ``p0``/``nv``: int32 [B] · ``steps``: f32 [B, 2] dequant steps.
    Returns f32 [B, C, K, G, hd].  ``W`` need not be a ``block_w``
    multiple; ``block_w >= W`` in interpret mode runs the single-step
    full-shape body (bit-identical to ``ref.chunk_attend``).
    """
    B, C, K, G, hd = q.shape
    W = k.shape[1]

    if interpret and block_w >= W:
        return pl.pallas_call(
            functools.partial(_batched_kernel, width=width, scale=scale,
                              window=window, causal=causal),
            out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
            interpret=True,
        )(p0, nv, steps, q, k_new, v_new, k, v, pos)

    nsplit = pl.cdiv(W, block_w)
    # history splits walk the pool; the last grid step re-reads split
    # nsplit-1's tile (clamped index) but only touches the chunk's own KV
    last = nsplit - 1
    hist = lambda b, r: (b, jnp.minimum(r, last), 0, 0)
    whole = lambda b, r: (b, 0, 0, 0)
    out = pl.pallas_call(
        functools.partial(_split_kernel, width=width, scale=scale,
                          window=window, causal=causal, nsplit=nsplit,
                          C=C, G=G, K=K, block_w=block_w, W=W),
        grid=(B, nsplit + 1),
        in_specs=[
            _SMEM, _SMEM, _SMEM,                          # p0, nv, steps
            pl.BlockSpec((1, K, C * G, hd), whole),       # q
            pl.BlockSpec((1, C, K, hd), whole),           # kn
            pl.BlockSpec((1, C, K, hd), whole),           # vn
            pl.BlockSpec((1, block_w, K, hd), hist),      # k
            pl.BlockSpec((1, block_w, K, hd), hist),      # v
            pl.BlockSpec((1, 1, 1, block_w), hist),       # pos
        ],
        out_specs=pl.BlockSpec((1, K, C * G, hd), whole),
        out_shape=jax.ShapeDtypeStruct((B, K, C * G, hd), jnp.float32),
        scratch_shapes=scratch(K, C * G, hd),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(p0, nv, steps, _heads_major(q), k_new, v_new, k, v,
      split_pos(pos, block_w))
    return _chunk_major(out, C)


# -- paged variant: one extra block-table indirection ---------------------
#
# History splits walk the request's mapped pages (split r streams page
# bt[b, r] via a scalar-prefetch index_map) instead of its ring rows;
# masking is unchanged — rows the request never wrote, including the
# whole null page 0, carry pos == -1 — and the chunk's own K/V block
# (grid step nblocks) is identical to the slot-major kernel.


def _paged_split_kernel(bt_ref, p0_ref, nv_ref, steps_ref, q_ref, kn_ref,
                        vn_ref, k_ref, v_ref, pos_ref, o_ref, m_ref, l_ref,
                        acc_ref, *, width, nsplit: int, **kw):
    del bt_ref                                  # consumed by the index maps
    b, r = pl.program_id(0), pl.program_id(1)
    k_step = v_step = None
    if width is not None:
        blk = jnp.minimum(r, nsplit - 1)
        k_step, v_step = steps_ref[b, 2 * blk], steps_ref[b, 2 * blk + 1]
    _chunk_step(q_ref, kn_ref, vn_ref, k_ref, v_ref, pos_ref, o_ref, m_ref,
                l_ref, acc_ref, p0=p0_ref[b], nv=nv_ref[b], k_step=k_step,
                v_step=v_step, r=r, nsplit=nsplit, W=None, width=width, **kw)


def _paged_batched_kernel(bt_ref, p0_ref, nv_ref, steps_ref, q_ref, kn_ref,
                          vn_ref, k_ref, v_ref, pos_ref, o_ref, *, width,
                          scale: float, window, causal: bool):
    """One grid step, full shapes: the ref composite through the gather."""
    bt = bt_ref[...]
    kf = jnp.take(k_ref[...], bt, axis=0).astype(jnp.float32)
    vf = jnp.take(v_ref[...], bt, axis=0).astype(jnp.float32)
    if width is not None:
        kf = kf * jnp.take(steps_ref[...][:, 0], bt)[..., None, None, None]
        vf = vf * jnp.take(steps_ref[...][:, 1], bt)[..., None, None, None]
    B, nblocks, P = kf.shape[:3]
    shp = (B, nblocks * P) + kf.shape[3:]
    o_ref[...] = R.chunk_attend(q_ref[...], kf.reshape(shp), vf.reshape(shp),
                                pos_ref[...], kn_ref[...], vn_ref[...],
                                p0_ref[...], nv_ref[...], scale=scale,
                                window=window, causal=causal)


@functools.partial(jax.jit, static_argnames=(
    "width", "scale", "window", "causal", "interpret", "force_split"))
def flash_prefill_paged_call(q, k_new, v_new, k, v, bt, pos, p0, nv, steps,
                             *, width, scale: float, window, causal: bool,
                             interpret: bool, force_split: bool = False):
    """Blocked chunked-prefill through a per-request block table.

    ``q``: f32 [B, C, K, G, hd] · ``k_new``/``v_new``: f32 [B, C, K, hd] ·
    ``k``/``v``: int8/int16/f32 [n_pages, P, K, hd] page arenas · ``bt``:
    int32 [B, nblocks] · ``pos``: int32 [B, nblocks·P] · ``p0``/``nv``:
    int32 [B] · ``steps``: f32 [n_pages, 2] per-page dequant steps.
    Returns f32 [B, C, K, G, hd].  Interpret mode runs the full-shape
    gather body (bit-identical to ``ref.paged_prefill_attention_ref``)
    unless ``force_split`` exercises the scalar-prefetch split path.
    """
    B, C, K, G, hd = q.shape
    P = k.shape[1]
    nblocks = bt.shape[1]

    if interpret and not force_split:
        return pl.pallas_call(
            functools.partial(_paged_batched_kernel, width=width, scale=scale,
                              window=window, causal=causal),
            out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
            interpret=True,
        )(bt, p0, nv, steps, q, k_new, v_new, k, v, pos)

    last = nblocks - 1   # step nblocks re-reads a clamped page tile
    page = lambda b, r, bt: (bt[b, jnp.minimum(r, last)], 0, 0, 0)
    whole = lambda b, r, bt: (b, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nblocks + 1),
        in_specs=[
            _SMEM, _SMEM, _SMEM,                          # p0, nv, steps
            pl.BlockSpec((1, K, C * G, hd), whole),       # q
            pl.BlockSpec((1, C, K, hd), whole),           # kn
            pl.BlockSpec((1, C, K, hd), whole),           # vn
            pl.BlockSpec((1, P, K, hd), page),            # k page
            pl.BlockSpec((1, P, K, hd), page),            # v page
            pl.BlockSpec((1, 1, 1, P),
                         lambda b, r, bt: (b, jnp.minimum(r, last), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, K, C * G, hd), whole),
        scratch_shapes=scratch(K, C * G, hd),
    )
    out = pl.pallas_call(
        functools.partial(_paged_split_kernel, width=width, scale=scale,
                          window=window, causal=causal, nsplit=nblocks,
                          C=C, G=G, K=K, block_w=P),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, C * G, hd), jnp.float32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(bt, p0, nv, page_steps(steps, bt), _heads_major(q), k_new, v_new, k,
      v, split_pos(pos, P))
    return _chunk_major(out, C)
