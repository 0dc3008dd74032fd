"""Reference composite for the fused decode-attention kernel.

This is the numerics contract of :mod:`repro.kernels.attn`: a single-query
GQA attention over a (possibly DFXP-packed) KV ring buffer, written as
plain jnp on the full ``[B, ...]`` shapes.  The Pallas kernel's
interpret-mode path executes :func:`attend` *verbatim* on its loaded
tiles (one grid step, full-shape blocks, dequantize first), which is what
lets CPU tests assert **bit**-equality between the fused kernel and this
composite — the same guarantee the qmatmul family gives against its
``ste_quant + jnp.matmul`` composite.

Masking semantics match ``repro.models.layers.attention_decode``:

* ``pos < 0`` marks an empty ring slot (never attended);
* causal: the query at ``q_pos`` sees keys with ``pos <= q_pos``;
* ``window``: only keys with ``q_pos - pos < window`` (None = global).

The softmax is the flash form — masked lanes contribute an exact ``0.0``
(``jnp.where`` before and after the exp), the max is subtracted per
(batch, kv-head, group) row, and the normalizer divides the *output*
(``o / l``), which is the order the split-K kernel reproduces.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.policy import MATMUL_PRECISION
from repro.core.quant import exact_pow2

Array = jax.Array


def valid_mask(pos: Array, q_pos: Array, *, window: Optional[int],
               causal: bool) -> Array:
    """[B, W] bool: which ring slots the query at ``q_pos`` [B] may see."""
    d = q_pos[:, None] - pos
    valid = pos >= 0
    if causal:
        valid = valid & (d >= 0)
    if window:
        valid = valid & (d < window)
    return valid


def attend(qf: Array, kf: Array, vf: Array, pos: Array, q_pos: Array, *,
           scale: float, window: Optional[int] = None,
           causal: bool = True) -> Array:
    """Single-query GQA attention on dequantized (f32) operands.

    ``qf``: [B, K, G, hd] · ``kf``/``vf``: [B, W, K, hd] · ``pos``: [B, W]
    int32 · ``q_pos``: [B] int32.  Returns [B, K, G, hd] float32.
    """
    s = jnp.einsum("bkgh,bwkh->bkgw", qf, kf,
                   precision=MATMUL_PRECISION,
                   preferred_element_type=jnp.float32) * scale
    v4 = valid_mask(pos, q_pos, window=window, causal=causal)[:, None, None, :]
    s = jnp.where(v4, s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(v4, jnp.exp(s - m), 0.0)
    el = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bkgw,bwkh->bkgh", p, vf,
                   precision=MATMUL_PRECISION,
                   preferred_element_type=jnp.float32)
    return o / jnp.maximum(el, 1e-30)


def chunk_attend(qf: Array, kf: Array, vf: Array, pos: Array, k_new: Array,
                 v_new: Array, p0: Array, n_valid: Array, *, scale: float,
                 window: Optional[int] = None, causal: bool = True) -> Array:
    """Chunked-prefill attention on dequantized (f32) operands.

    A chunk of ``C`` query positions starting at absolute position ``p0``
    attends (a) the already-written pool **history** — ring entries with
    ``0 <= pos < p0`` — and (b) its **own** chunk K/V causally, taken from
    the fresh f32 projections (never from the pool, so ring eviction by
    the chunk's own write can't hide in-window keys).  One joint flash
    softmax spans both score blocks, which is the order the split-K
    prefill kernel reproduces (history splits first, self block last).

    ``qf``: [B, C, K, G, hd] · ``kf``/``vf``: [B, W, K, hd] ·
    ``pos``: int32 [B, W] · ``k_new``/``v_new``: f32 [B, C, K, hd] ·
    ``p0``/``n_valid``: int32 [B] (``n_valid < C`` marks a ragged final
    chunk; rows past it are masked everywhere and their output is
    garbage-by-contract).  Returns f32 [B, C, K, G, hd].
    """
    B, C, K, G, hd = qf.shape
    W = kf.shape[1]
    cpos = jnp.arange(C, dtype=jnp.int32)
    q_pos = p0[:, None] + cpos[None, :]                    # [B, C]
    row_ok = cpos[None, :] < n_valid[:, None]              # [B, C]

    sh = jnp.einsum("bckgh,bwkh->bkgcw", qf, kf,
                    precision=MATMUL_PRECISION,
                    preferred_element_type=jnp.float32) * scale
    d = q_pos[:, :, None] - pos[:, None, :]                # [B, C, W]
    vh = (pos[:, None, :] >= 0) & (pos[:, None, :] < p0[:, None, None]) \
        & row_ok[:, :, None]
    if causal:
        vh = vh & (d >= 0)
    if window:
        vh = vh & (d < window)

    ss = jnp.einsum("bckgh,bjkh->bkgcj", qf, k_new,
                    precision=MATMUL_PRECISION,
                    preferred_element_type=jnp.float32) * scale
    dj = cpos[:, None] - cpos[None, :]                     # [C, C]
    vs = row_ok[:, :, None] & row_ok[:, None, :]
    if causal:
        vs = vs & (dj >= 0)[None]
    if window:
        vs = vs & (dj < window)[None]

    v4h = vh[:, None, None]                                # [B,1,1,C,W]
    v4s = vs[:, None, None]                                # [B,1,1,C,C]
    s = jnp.concatenate([jnp.where(v4h, sh, -1e30),
                         jnp.where(v4s, ss, -1e30)], axis=-1)
    m = jnp.max(s, axis=-1, keepdims=True)
    vcat = jnp.concatenate([jnp.broadcast_to(v4h, sh.shape),
                            jnp.broadcast_to(v4s, ss.shape)], axis=-1)
    p = jnp.where(vcat, jnp.exp(s - m), 0.0)
    el = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bkgcw,bwkh->bkgch", p[..., :W], vf,
                   precision=MATMUL_PRECISION,
                   preferred_element_type=jnp.float32) \
        + jnp.einsum("bkgcj,bjkh->bkgch", p[..., W:], v_new,
                     precision=MATMUL_PRECISION,
                     preferred_element_type=jnp.float32)
    o = o / jnp.maximum(el, 1e-30)
    return o.transpose(0, 3, 1, 2, 4)                      # [B, C, K, G, hd]


def dequant(m: Array, e: Array) -> Array:
    """[B, W, K, hd] mantissas × per-row exponents [B] → f32 values."""
    return m.astype(jnp.float32) * exact_pow2(e)[:, None, None, None]


def gather_pages(m: Array, e: Optional[Array], bt: Array,
                 width: Optional[int]) -> Array:
    """Block-table gather: paged storage → the slot-major wide layout.

    ``m``: [n_pages, P, K, hd] page arena (int mantissas when ``width``,
    raw floats otherwise) · ``e``: f32 [n_pages] per-page log2-steps ·
    ``bt``: int32 [B, nblocks] block table.  Returns f32
    [B, nblocks·P, K, hd] — logical row ``r`` is page ``bt[b, r // P]``
    offset ``r % P``, exactly the layout ``pos`` [B, nblocks·P] indexes,
    so :func:`attend`/:func:`chunk_attend` apply unchanged.
    """
    x = jnp.take(m, bt, axis=0).astype(jnp.float32)    # [B, nblocks, P, ...]
    if width is not None:
        x = x * exact_pow2(jnp.take(e, bt, axis=0))[..., None, None, None]
    B, nblocks, P = x.shape[:3]
    return x.reshape((B, nblocks * P) + x.shape[3:])


def paged_decode_attention_ref(q: Array, k: Array, v: Array, bt: Array,
                               pos: Array, q_pos: Array, *, k_exp=None,
                               v_exp=None, width: Optional[int] = None,
                               scale: float, window: Optional[int] = None,
                               causal: bool = True) -> Array:
    """Decode composite through the block-table gather.

    ``k``/``v`` are the [n_pages, P, K, hd] page arenas with per-**page**
    ``k_exp``/``v_exp`` [n_pages] (the
    :class:`repro.serve.paged.PagedKVCodec` layout, one layer); the rest
    matches :func:`decode_attention_ref`.
    """
    kf = gather_pages(k, k_exp, bt, width)
    vf = gather_pages(v, v_exp, bt, width)
    return attend(q.astype(jnp.float32), kf, vf, pos, q_pos, scale=scale,
                  window=window, causal=causal)


def paged_prefill_attention_ref(q: Array, k: Array, v: Array, bt: Array,
                                pos: Array, k_new: Array, v_new: Array,
                                p0: Array, n_valid: Array, *, k_exp=None,
                                v_exp=None, width: Optional[int] = None,
                                scale: float, window: Optional[int] = None,
                                causal: bool = True) -> Array:
    """Chunked-prefill composite through the block-table gather — the
    numerics contract of the paged flash-prefill kernel, in the
    :class:`repro.serve.paged.PagedKVCodec` entry layout (one layer)."""
    kf = gather_pages(k, k_exp, bt, width)
    vf = gather_pages(v, v_exp, bt, width)
    return chunk_attend(q.astype(jnp.float32), kf, vf, pos,
                        k_new.astype(jnp.float32), v_new.astype(jnp.float32),
                        p0, n_valid, scale=scale, window=window,
                        causal=causal)


def decode_attention_ref(q: Array, k: Array, v: Array, pos: Array,
                         q_pos: Array, *, k_exp=None, v_exp=None,
                         width: Optional[int] = None, scale: float,
                         window: Optional[int] = None,
                         causal: bool = True) -> Array:
    """The full composite: dequantize (when ``width``) then :func:`attend`.

    ``width=None`` takes ``k``/``v`` as raw float K/V (the f32-pool path);
    otherwise they are int8/int16 mantissas with ``k_exp``/``v_exp`` [B]
    log2-steps, exactly the :class:`repro.serve.kv_pool.PackedKVCodec`
    entry layout (one layer, leading layer dim stripped).
    """
    qf = q.astype(jnp.float32)
    if width is None:
        kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    else:
        kf, vf = dequant(k, k_exp), dequant(v, v_exp)
    return attend(qf, kf, vf, pos, q_pos, scale=scale, window=window,
                  causal=causal)


def prefill_attention_ref(q: Array, k: Array, v: Array, pos: Array,
                          k_new: Array, v_new: Array, p0: Array,
                          n_valid: Array, *, k_exp=None, v_exp=None,
                          width: Optional[int] = None, scale: float,
                          window: Optional[int] = None,
                          causal: bool = True) -> Array:
    """Chunked-prefill composite: dequantize (when ``width``) then
    :func:`chunk_attend` — the numerics contract of the flash-prefill
    kernel, in the :class:`repro.serve.kv_pool.PackedKVCodec` entry layout
    (one layer, leading layer dim stripped)."""
    qf = q.astype(jnp.float32)
    if width is None:
        kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    else:
        kf, vf = dequant(k, k_exp), dequant(v, v_exp)
    return chunk_attend(qf, kf, vf, pos, k_new.astype(jnp.float32),
                        v_new.astype(jnp.float32), p0, n_valid, scale=scale,
                        window=window, causal=causal)
