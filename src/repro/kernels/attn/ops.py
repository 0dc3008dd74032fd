"""jit'd wrapper for the fused decode-attention kernel: shapes + dispatch.

Owns everything the kernel body stays agnostic of: backend detection
(compiled Pallas on TPU, interpret elsewhere —
:func:`repro.kernels._tiling.resolve_interpret`), split-size selection
through the dispatch layer's shape-bucketed autotune cache
(:func:`repro.kernels.dispatch.attn_blocks_for`), and the dequant-step
packing (``2**e`` built with the bit-exact
:func:`repro.core.quant.exact_pow2`, the same grid the codec's quantizer
used on append).  The K/V buffers are handed to the kernel **as stored**
— never padded or copied; a ragged last split is masked in-kernel by
slot index, because any host-side reshape of the pool would re-spend the
HBM round-trip the fusion saves.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.core.quant import exact_pow2
from repro.kernels import dispatch
from repro.kernels._tiling import resolve_interpret

from .attn_kernel import flash_decode_call, flash_decode_paged_call
from .prefill_kernel import flash_prefill_call, flash_prefill_paged_call

Array = jax.Array


def _tp_size(tp_axis: Optional[str], n_kv_heads: int) -> int:
    """Live TP degree for the fused kernels.

    Returns the ambient-mesh size of ``tp_axis`` when the axis exists,
    is larger than 1, and evenly divides the kv-head count; 0 otherwise
    — the caller then runs the unsharded kernel (same numerics, pool
    replicated by the sharding guard under the same condition).
    """
    if not tp_axis:
        return 0
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or tp_axis not in mesh.shape:
        return 0
    size = int(mesh.shape[tp_axis])
    return size if size > 1 and n_kv_heads % size == 0 else 0


def flash_decode(q: Array, k: Array, v: Array, pos: Array, q_pos: Array,
                 k_exp=None, v_exp=None, *, width: Optional[int] = None,
                 scale: float, window: Optional[int] = None,
                 causal: bool = True, block_w: Optional[int] = None,
                 interpret: Optional[bool] = None,
                 tp_axis: Optional[str] = None) -> Array:
    """Fused single-query GQA attention over a (packed) KV ring buffer.

    ``q``: [B, K, G, hd] (kv-head-major query groups, i.e.
    ``q4.reshape(B, K, G, hd)``) · ``k``/``v``: [B, W, K, hd] int8/int16
    mantissas (``width=8|16``) or raw floats (``width=None``) · ``pos``:
    int32 [B, W] ring positions (-1 = empty) · ``q_pos``: int32 [B] query
    positions · ``k_exp``/``v_exp``: f32 [B] log2-steps of the packed
    entries.  Returns f32 [B, K, G, hd]; numerics are the
    :func:`repro.kernels.attn.ref.decode_attention_ref` composite
    (bit-identical in interpret mode).

    With ``tp_axis`` naming a live ambient-mesh axis that divides ``K``,
    the call shard_maps itself over the kv-head axis — each shard runs
    this same function on its head slice, so per-head numerics are
    untouched (GQA never contracts across kv heads).
    """
    B, K, G, hd = q.shape
    tp = _tp_size(tp_axis, K)
    if tp:
        kw = dict(width=width, scale=scale, window=window, causal=causal,
                  block_w=block_w, interpret=interpret)
        h = PartitionSpec(None, tp_axis)
        kv = PartitionSpec(None, None, tp_axis)
        r = PartitionSpec()
        if width is None:
            return jax.shard_map(
                lambda q, k, v, pos, qp: flash_decode(q, k, v, pos, qp,
                                                      **kw),
                in_specs=(h, kv, kv, r, r), out_specs=h,
                check_vma=False)(q, k, v, pos, jnp.asarray(q_pos))
        return jax.shard_map(
            lambda q, k, v, pos, qp, ke, ve: flash_decode(
                q, k, v, pos, qp, ke, ve, **kw),
            in_specs=(h, kv, kv, r, r, r, r), out_specs=h,
            check_vma=False)(q, k, v, pos, jnp.asarray(q_pos),
                             jnp.asarray(k_exp, jnp.float32),
                             jnp.asarray(v_exp, jnp.float32))
    W = k.shape[1]
    interpret = resolve_interpret(interpret)
    if block_w is None:
        block_w = dispatch.attn_blocks_for(W, K, G, hd, width=width,
                                           interpret=interpret)
    block_w = min(block_w, W)

    if width is None:
        steps = jnp.ones((B, 2), jnp.float32)
    else:
        steps = jnp.stack([exact_pow2(jnp.asarray(k_exp, jnp.float32)),
                           exact_pow2(jnp.asarray(v_exp, jnp.float32))],
                          axis=-1)
    qpos = jnp.asarray(q_pos, jnp.int32).reshape(B)

    return flash_decode_call(q.astype(jnp.float32), k, v,
                             pos.astype(jnp.int32), qpos, steps, width=width,
                             block_w=block_w, scale=scale, window=window,
                             causal=causal, interpret=interpret)


def flash_prefill(q: Array, k_new: Array, v_new: Array, k: Array, v: Array,
                  pos: Array, p0: Array, n_valid: Array, k_exp=None,
                  v_exp=None, *, width: Optional[int] = None, scale: float,
                  window: Optional[int] = None, causal: bool = True,
                  block_w: Optional[int] = None,
                  interpret: Optional[bool] = None,
                  tp_axis: Optional[str] = None) -> Array:
    """Fused chunked-prefill GQA attention over a (packed) KV ring buffer.

    ``q``: [B, C, K, G, hd] kv-head-major query groups for a chunk of
    ``C`` positions starting at ``p0`` [B] · ``k_new``/``v_new``: f32
    [B, C, K, hd] the chunk's own fresh K/V (attended causally from
    registers, never from the pool) · ``k``/``v``: [B, W, K, hd]
    int8/int16 mantissas (``width=8|16``) or raw floats (``width=None``)
    — the pool's history, masked to ``0 <= pos < p0`` · ``n_valid``: [B]
    valid chunk rows (ragged final chunk).  Returns f32 [B, C, K, G, hd];
    numerics are :func:`repro.kernels.attn.ref.prefill_attention_ref`
    (bit-identical in interpret mode).

    ``tp_axis`` shard_maps over the kv-head axis exactly as in
    :func:`flash_decode`.
    """
    B, C, K, G, hd = q.shape
    tp = _tp_size(tp_axis, K)
    if tp:
        kw = dict(width=width, scale=scale, window=window, causal=causal,
                  block_w=block_w, interpret=interpret)
        h = PartitionSpec(None, None, tp_axis)
        r = PartitionSpec()
        args = (q, k_new, v_new, k, v, pos, jnp.asarray(p0),
                jnp.asarray(n_valid))
        if width is None:
            return jax.shard_map(
                lambda q, kn, vn, k, v, pos, p0, nv: flash_prefill(
                    q, kn, vn, k, v, pos, p0, nv, **kw),
                in_specs=(h, h, h, h, h, r, r, r), out_specs=h,
                check_vma=False)(*args)
        return jax.shard_map(
            lambda q, kn, vn, k, v, pos, p0, nv, ke, ve: flash_prefill(
                q, kn, vn, k, v, pos, p0, nv, ke, ve, **kw),
            in_specs=(h, h, h, h, h, r, r, r, r, r), out_specs=h,
            check_vma=False)(*args, jnp.asarray(k_exp, jnp.float32),
                             jnp.asarray(v_exp, jnp.float32))
    W = k.shape[1]
    interpret = resolve_interpret(interpret)
    if block_w is None:
        block_w = dispatch.prefill_blocks_for(W, C, K, G, hd, width=width,
                                              interpret=interpret)
    block_w = min(block_w, W)

    if width is None:
        steps = jnp.ones((B, 2), jnp.float32)
    else:
        steps = jnp.stack([exact_pow2(jnp.asarray(k_exp, jnp.float32)),
                           exact_pow2(jnp.asarray(v_exp, jnp.float32))],
                          axis=-1)
    p0 = jnp.asarray(p0, jnp.int32).reshape(B)
    nv = jnp.asarray(n_valid, jnp.int32).reshape(B)

    return flash_prefill_call(q.astype(jnp.float32),
                              k_new.astype(jnp.float32),
                              v_new.astype(jnp.float32), k, v,
                              pos.astype(jnp.int32), p0, nv, steps,
                              width=width, block_w=block_w, scale=scale,
                              window=window, causal=causal,
                              interpret=interpret)


def _paged_steps(n_pages: int, k_exp, v_exp, width: Optional[int]) -> Array:
    """Per-page dequant steps [n_pages, 2] (ones for ``width=None``)."""
    if width is None:
        return jnp.ones((n_pages, 2), jnp.float32)
    return jnp.stack([exact_pow2(jnp.asarray(k_exp, jnp.float32)),
                      exact_pow2(jnp.asarray(v_exp, jnp.float32))], axis=-1)


def flash_decode_paged(q: Array, k: Array, v: Array, bt: Array, pos: Array,
                       q_pos: Array, k_exp=None, v_exp=None, *,
                       width: Optional[int] = None, scale: float,
                       window: Optional[int] = None, causal: bool = True,
                       interpret: Optional[bool] = None,
                       force_split: bool = False,
                       tp_axis: Optional[str] = None) -> Array:
    """Fused single-query GQA attention through a per-request block table.

    ``q``: [B, K, G, hd] kv-head-major query groups · ``k``/``v``:
    [n_pages, P, K, hd] page arenas (int8/int16 mantissas or raw floats)
    · ``bt``: int32 [B, nblocks] block tables (0 = null page) · ``pos``:
    int32 [B, nblocks·P] logical positions (-1 = empty) · ``k_exp``/
    ``v_exp``: f32 [n_pages] per-PAGE log2-steps.  Returns f32
    [B, K, G, hd]; numerics are
    :func:`repro.kernels.attn.ref.paged_decode_attention_ref`
    (bit-identical in interpret mode).

    ``tp_axis`` shard_maps over the kv-head axis (page arenas carry it at
    axis 2) exactly as in :func:`flash_decode`; block tables, positions
    and per-page exponents stay replicated.
    """
    B, K, G, hd = q.shape
    tp = _tp_size(tp_axis, K)
    if tp:
        kw = dict(width=width, scale=scale, window=window, causal=causal,
                  interpret=interpret, force_split=force_split)
        h = PartitionSpec(None, tp_axis)
        arena = PartitionSpec(None, None, tp_axis)
        r = PartitionSpec()
        args = (q, k, v, bt, pos, jnp.asarray(q_pos))
        if width is None:
            return jax.shard_map(
                lambda q, k, v, bt, pos, qp: flash_decode_paged(
                    q, k, v, bt, pos, qp, **kw),
                in_specs=(h, arena, arena, r, r, r), out_specs=h,
                check_vma=False)(*args)
        return jax.shard_map(
            lambda q, k, v, bt, pos, qp, ke, ve: flash_decode_paged(
                q, k, v, bt, pos, qp, ke, ve, **kw),
            in_specs=(h, arena, arena, r, r, r, r, r), out_specs=h,
            check_vma=False)(*args, jnp.asarray(k_exp, jnp.float32),
                             jnp.asarray(v_exp, jnp.float32))
    n_pages, P = k.shape[:2]
    interpret = resolve_interpret(interpret)
    dispatch.paged_attn_blocks_for(P, K, G, hd, width=width,
                                   interpret=interpret)
    steps = _paged_steps(n_pages, k_exp, v_exp, width)
    qpos = jnp.asarray(q_pos, jnp.int32).reshape(B)
    return flash_decode_paged_call(q.astype(jnp.float32), k, v,
                                   bt.astype(jnp.int32),
                                   pos.astype(jnp.int32), qpos, steps,
                                   width=width, scale=scale, window=window,
                                   causal=causal, interpret=interpret,
                                   force_split=force_split)


def flash_prefill_paged(q: Array, k_new: Array, v_new: Array, k: Array,
                        v: Array, bt: Array, pos: Array, p0: Array,
                        n_valid: Array, k_exp=None, v_exp=None, *,
                        width: Optional[int] = None, scale: float,
                        window: Optional[int] = None, causal: bool = True,
                        interpret: Optional[bool] = None,
                        force_split: bool = False,
                        tp_axis: Optional[str] = None) -> Array:
    """Fused chunked-prefill GQA attention through a block table.

    ``q``: [B, C, K, G, hd] chunk query groups starting at ``p0`` [B] ·
    ``k_new``/``v_new``: f32 [B, C, K, hd] the chunk's own fresh K/V ·
    ``k``/``v``: [n_pages, P, K, hd] page arenas · ``bt``: int32
    [B, nblocks] · ``pos``: int32 [B, nblocks·P] · ``k_exp``/``v_exp``:
    f32 [n_pages] per-PAGE log2-steps.  Returns f32 [B, C, K, G, hd];
    numerics are
    :func:`repro.kernels.attn.ref.paged_prefill_attention_ref`
    (bit-identical in interpret mode).

    ``tp_axis`` shard_maps over the kv-head axis exactly as in
    :func:`flash_decode_paged`.
    """
    B, C, K, G, hd = q.shape
    tp = _tp_size(tp_axis, K)
    if tp:
        kw = dict(width=width, scale=scale, window=window, causal=causal,
                  interpret=interpret, force_split=force_split)
        h = PartitionSpec(None, None, tp_axis)
        arena = PartitionSpec(None, None, tp_axis)
        r = PartitionSpec()
        args = (q, k_new, v_new, k, v, bt, pos, jnp.asarray(p0),
                jnp.asarray(n_valid))
        if width is None:
            return jax.shard_map(
                lambda q, kn, vn, k, v, bt, pos, p0, nv:
                flash_prefill_paged(q, kn, vn, k, v, bt, pos, p0, nv, **kw),
                in_specs=(h, h, h, arena, arena, r, r, r, r), out_specs=h,
                check_vma=False)(*args)
        return jax.shard_map(
            lambda q, kn, vn, k, v, bt, pos, p0, nv, ke, ve:
            flash_prefill_paged(q, kn, vn, k, v, bt, pos, p0, nv, ke, ve,
                                **kw),
            in_specs=(h, h, h, arena, arena, r, r, r, r, r, r), out_specs=h,
            check_vma=False)(*args, jnp.asarray(k_exp, jnp.float32),
                             jnp.asarray(v_exp, jnp.float32))
    n_pages, P = k.shape[:2]
    interpret = resolve_interpret(interpret)
    dispatch.paged_prefill_blocks_for(P, C, K, G, hd, width=width,
                                      interpret=interpret)
    steps = _paged_steps(n_pages, k_exp, v_exp, width)
    p0 = jnp.asarray(p0, jnp.int32).reshape(B)
    nv = jnp.asarray(n_valid, jnp.int32).reshape(B)
    return flash_prefill_paged_call(q.astype(jnp.float32),
                                    k_new.astype(jnp.float32),
                                    v_new.astype(jnp.float32), k, v,
                                    bt.astype(jnp.int32),
                                    pos.astype(jnp.int32), p0, nv, steps,
                                    width=width, scale=scale, window=window,
                                    causal=causal, interpret=interpret,
                                    force_split=force_split)
