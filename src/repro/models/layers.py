"""Shared neural-net layers, quantization-aware (tape-threaded).

Every weighted sum goes through ``tape.dot`` (weight re-quantized to the
computation width at use time, wide f32 accumulation — the paper's §7
accumulator hypothesis == the TPU MXU contract) and every group boundary
through ``tape.act`` (forward value + backward cotangent quantized, overflow
stats recorded). With a float32 policy all of it is the identity.

Attention comes in three shapes:
  * ``attention_train``  — naive masked scores (seq ≤ ~8k; remat-friendly).
  * ``attention_prefill`` — online-softmax scan over KV chunks (no-grad
    inference path; peak memory ∝ chunk, required for 32k prefill).
  * ``attention_decode`` — single-query against a cache (O(S) memory).
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.policy import MATMUL_PRECISION
from repro.core.tape import QTape

Array = jax.Array


# ---------------------------------------------------------------------------
# basics
# ---------------------------------------------------------------------------

def rmsnorm(x: Array, w: Array, eps: float = 1e-6) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def init_dense(key, d_in: int, d_out: int, scale: Optional[float] = None) -> Array:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return jax.random.normal(key, (d_in, d_out), jnp.float32) * scale


# ---------------------------------------------------------------------------
# rotary embeddings (standard + M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> Array:
    return jnp.exp(
        -jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
        * jnp.log(jnp.float32(theta))
    )  # [hd/2]


def apply_rope(x: Array, positions: Array, theta: float,
               mrope_sections: Tuple[int, ...] = ()) -> Array:
    """``x``: [B, S, H, hd]. ``positions``: [B, S] or [3, B, S] for M-RoPE.

    M-RoPE (qwen2-vl): frequency dims are partitioned into (temporal, height,
    width) sections, each rotated by its own position stream.
    """
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # [hd/2]
    if positions.ndim == 3:  # M-RoPE
        if not mrope_sections:
            mrope_sections = (hd // 2,)
        sec_ids = jnp.repeat(
            jnp.arange(len(mrope_sections)),
            jnp.asarray(mrope_sections),
            total_repeat_length=hd // 2,
        )  # [hd/2] -> which position stream each freq dim uses
        pos = positions[sec_ids]                       # [hd/2, B, S]
        angle = jnp.einsum("fbs,f->bsf", pos.astype(jnp.float32), freqs,
                           precision=MATMUL_PRECISION)
    else:
        angle = positions.astype(jnp.float32)[..., None] * freqs  # [B, S, hd/2]
    cos = jnp.cos(angle)[:, :, None, :]
    sin = jnp.sin(angle)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnSpec:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    qk_norm: bool = False
    rope_theta: float = 1e4
    mrope_sections: Tuple[int, ...] = ()
    causal: bool = True
    use_rope: bool = True

    @property
    def q_dim(self):
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self):
        return self.num_kv_heads * self.head_dim


def init_attn(key, spec: AttnSpec) -> dict:
    ks = jax.random.split(key, 4)
    p = {
        "wq": init_dense(ks[0], spec.d_model, spec.q_dim),
        "wk": init_dense(ks[1], spec.d_model, spec.kv_dim),
        "wv": init_dense(ks[2], spec.d_model, spec.kv_dim),
        "wo": init_dense(ks[3], spec.q_dim, spec.d_model),
    }
    if spec.qk_norm:
        p["q_norm"] = jnp.ones((spec.head_dim,), jnp.float32)
        p["k_norm"] = jnp.ones((spec.head_dim,), jnp.float32)
    return p


def _qkv(params, spec: AttnSpec, x: Array, positions, tape: QTape, prefix: str):
    B, S, _ = x.shape
    q = tape.dot(f"{prefix}/wq", x, params["wq"]).reshape(
        B, S, spec.num_heads, spec.head_dim)
    k = tape.dot(f"{prefix}/wk", x, params["wk"]).reshape(
        B, S, spec.num_kv_heads, spec.head_dim)
    v = tape.dot(f"{prefix}/wv", x, params["wv"]).reshape(
        B, S, spec.num_kv_heads, spec.head_dim)
    if spec.qk_norm:
        q = rmsnorm(q, params["q_norm"])
        k = rmsnorm(k, params["k_norm"])
    if spec.use_rope:
        q = apply_rope(q, positions, spec.rope_theta, spec.mrope_sections)
        k = apply_rope(k, positions, spec.rope_theta, spec.mrope_sections)
    q = tape.act(f"{prefix}/qkv", q)
    k = tape.act(f"{prefix}/k", k)
    v = tape.act(f"{prefix}/v", v)
    return q, k, v


def _mask(q_pos: Array, k_pos: Array, window, causal: bool) -> Array:
    """[.., Sq, Sk] boolean validity mask. window==0 means global."""
    d = q_pos[..., :, None] - k_pos[..., None, :]
    m = (d >= 0) if causal else jnp.ones(d.shape, bool)
    if window is not None:
        w = jnp.asarray(window)
        m = m & ((w == 0) | (d < w))
    return m


def _sdpa(q, k, v, mask, scale) -> Array:
    """Naive scores; f32 softmax; GQA via head-group reshape."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, Sq, K, G, hd)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", qg, k,
                        precision=MATMUL_PRECISION,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p.astype(v.dtype), v,
                   precision=MATMUL_PRECISION,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


def attention_train(params, spec: AttnSpec, x: Array, positions: Array,
                    tape: QTape, prefix: str, window=None,
                    kv_source: Optional[Array] = None,
                    kv_positions: Optional[Array] = None) -> Array:
    """Training-path attention (naive masked). ``kv_source`` → cross-attn."""
    B, S, _ = x.shape
    if kv_source is None:
        q, k, v = _qkv(params, spec, x, positions, tape, prefix)
        k_pos = positions
        causal = spec.causal
    else:
        q = tape.dot(f"{prefix}/wq", x, params["wq"]).reshape(
            B, S, spec.num_heads, spec.head_dim)
        Sk = kv_source.shape[1]
        k = tape.dot(f"{prefix}/wk", kv_source, params["wk"]).reshape(
            B, Sk, spec.num_kv_heads, spec.head_dim)
        v = tape.dot(f"{prefix}/wv", kv_source, params["wv"]).reshape(
            B, Sk, spec.num_kv_heads, spec.head_dim)
        q = tape.act(f"{prefix}/qkv", q)
        k = tape.act(f"{prefix}/k", k)
        v = tape.act(f"{prefix}/v", v)
        k_pos = (kv_positions if kv_positions is not None
                 else jnp.broadcast_to(jnp.arange(Sk), (B, Sk)))
        causal = False

    q_pos = positions if positions.ndim == 2 else positions[0]
    k_pos2 = k_pos if k_pos.ndim == 2 else k_pos[0]
    mask = _mask(q_pos, k_pos2, window, causal)
    o = _sdpa(q, k, v, mask, 1.0 / math.sqrt(spec.head_dim))
    o = o.reshape(B, S, spec.q_dim)
    y = tape.dot(f"{prefix}/wo", o, params["wo"])
    return tape.act(f"{prefix}/out", y)


def attention_prefill(params, spec: AttnSpec, x: Array, positions: Array,
                      tape: QTape, prefix: str, window=None,
                      chunk: int = 1024):
    """Inference prefill: online-softmax over KV chunks; returns (y, (k, v)).

    Peak memory ∝ ``Sq × chunk`` instead of ``Sq × Sk`` — required for the
    32k/500k shapes. No autodiff support (inference only).
    """
    B, S, _ = x.shape
    q, k, v = _qkv(params, spec, x, positions, tape, prefix)
    H, K, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    G = H // K
    scale = 1.0 / math.sqrt(hd)
    q_pos = positions if positions.ndim == 2 else positions[0]

    n_chunks = -(-S // chunk)
    pad = n_chunks * chunk - S
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # pad positions must be invalid under the causal mask → large positive
    pos_p = jnp.pad(q_pos, ((0, 0), (0, pad)), constant_values=2 ** 30)
    kc = kp.reshape(B, n_chunks, chunk, K, hd).transpose(1, 0, 2, 3, 4)
    vc = vp.reshape(B, n_chunks, chunk, K, hd).transpose(1, 0, 2, 3, 4)
    pc = pos_p.reshape(B, n_chunks, chunk).transpose(1, 0, 2)

    qg = q.reshape(B, S, K, G, hd)

    def body(carry, xs):
        m, l, acc = carry
        kci, vci, pci = xs
        s = jnp.einsum("bqkgh,bckh->bkgqc", qg, kci,
                       precision=MATMUL_PRECISION,
                       preferred_element_type=jnp.float32) * scale
        valid = _mask(q_pos, pci, window, spec.causal)  # [B, S, chunk]
        vexp = valid[:, None, None, :, :]
        s = jnp.where(vexp, s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # fully-masked chunks: exp(-1e30 - (-1e30)) = 1 would leak — zero it
        p = jnp.where(vexp, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bkgqc,bckh->bkgqh", p, vci.astype(jnp.float32),
            precision=MATMUL_PRECISION,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((B, K, G, S), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, K, G, S), jnp.float32)
    a0 = jnp.zeros((B, K, G, S, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, pc))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    o = o.transpose(0, 3, 1, 2, 4).reshape(B, S, spec.q_dim).astype(x.dtype)
    y = tape.dot(f"{prefix}/wo", o, params["wo"])
    return tape.act(f"{prefix}/out", y), (k, v)


class RawKVCodec:
    """Float-container KV-cache codec: today's ring buffer, verbatim.

    The codec protocol is the decode cache's storage contract:
    ``append(entry, k_new, v_new, pos)`` writes one token's K/V into slot
    ``pos % W`` and returns the updated entry; ``load(entry)`` returns
    ``(k, v, pos)`` as wide arrays for the attention math. Alternative
    codecs (``repro.serve.kv_pool.PackedKVCodec``) store int mantissas +
    per-slot DFXP exponents and quantize/dequantize at this boundary.

    ``fused_decode`` is the codec *capability flag*
    ``attention_decode`` keys on: when set, the hot decode path skips
    ``load`` entirely and calls ``fused_attention`` — the Pallas
    flash-decode kernel reading the entry's storage containers directly
    (for this codec that is plain f32; for the packed codec, int
    mantissas dequantized in the tile loads). The default instance keeps
    it off, so every existing call site retains today's exact path.

    The flag is now a **read-only property** owned by
    :func:`repro.serve.kv_pool.make_kv_pool` (the factory decides the
    decode path together with the pool layout); passing the legacy
    ``fused_decode=`` constructor argument still works for one release
    but warns.  ``tp_axis`` names the mesh axis the pool's kv-head
    dimension is sharded over (serving tensor parallelism) — the fused
    kernels shard_map themselves over it.
    """

    def __init__(self, fused_decode: Optional[bool] = None, *,
                 tp_axis: Optional[str] = None):
        if fused_decode is not None:
            warnings.warn(
                "RawKVCodec(fused_decode=...) is deprecated; build pools "
                "through repro.serve.kv_pool.make_kv_pool, which owns the "
                "decode-path choice", DeprecationWarning, stacklevel=2)
        self._fused_decode = bool(fused_decode)
        self.tp_axis = tp_axis

    @property
    def fused_decode(self) -> bool:
        """Whether decode/prefill attention runs the fused Pallas kernels
        on this codec's containers (set by the pool factory)."""
        return self._fused_decode

    def append(self, entry: dict, k_new: Array, v_new: Array,
               pos: Array, mask: Optional[Array] = None) -> dict:
        """``k_new``/``v_new``: [B, K, hd]; ``pos``: [B] int32.

        ``mask`` (bool [B], optional) suppresses the append for masked-off
        rows entirely — the continuous-batching engine decodes all slots
        every step, and rows mid-chunked-prefill (or free) must not have
        garbage written into their ring.  ``mask=None`` keeps today's
        unconditional write, bit-for-bit.
        """
        W = entry["k"].shape[1]
        slot = (pos % W).astype(jnp.int32)
        bidx = jnp.arange(pos.shape[0])
        if mask is None:
            return {"k": entry["k"].at[bidx, slot].set(k_new),
                    "v": entry["v"].at[bidx, slot].set(v_new),
                    "pos": entry["pos"].at[bidx, slot].set(
                        pos.astype(jnp.int32))}
        # masked rows write out of bounds and are dropped
        slot = jnp.where(mask, slot, W)
        return {"k": entry["k"].at[bidx, slot].set(k_new, mode="drop"),
                "v": entry["v"].at[bidx, slot].set(v_new, mode="drop"),
                "pos": entry["pos"].at[bidx, slot].set(
                    pos.astype(jnp.int32), mode="drop")}

    def append_chunk(self, entry: dict, k_new: Array, v_new: Array,
                     p0: Array, n_valid: Array) -> dict:
        """Write a prefill chunk's K/V into the ring, raw f32.

        ``k_new``/``v_new``: [B, C, K, hd] — rows ``i`` land at absolute
        positions ``p0 + i``; rows ``>= n_valid`` (ragged final chunk) and
        rows the ring would evict within this same chunk (``C`` larger
        than a windowed cap) are dropped.  ``p0 == 0`` marks the
        admission chunk: the slot's stale ring positions reset to -1
        first, so a recycled slot never leaks its previous occupant.
        """
        W = entry["k"].shape[1]
        C = k_new.shape[1]
        idx = jnp.arange(C, dtype=jnp.int32)
        pos = p0[:, None] + idx[None, :]                          # [B, C]
        keep = (idx[None, :] < n_valid[:, None]) & \
            (pos >= p0[:, None] + n_valid[:, None] - W)
        slot = jnp.where(keep, pos % W, W).astype(jnp.int32)
        bidx = jnp.arange(pos.shape[0])[:, None]
        pos_buf = jnp.where((p0 == 0)[:, None], -1, entry["pos"])
        return {"k": entry["k"].at[bidx, slot].set(k_new, mode="drop"),
                "v": entry["v"].at[bidx, slot].set(v_new, mode="drop"),
                "pos": pos_buf.at[bidx, slot].set(pos, mode="drop")}

    def load(self, entry: dict):
        return entry["k"], entry["v"], entry["pos"]

    def fused_attention(self, entry: dict, qg: Array, q_pos: Array, *,
                        scale: float, window=None, causal: bool = True):
        """Flash-decode on the raw f32 ring buffers (``width=None``).

        ``qg``: [B, K, G, hd] kv-head-major query groups; returns
        f32 [B, K, G, hd].
        """
        from repro.kernels.attn.ops import flash_decode
        return flash_decode(qg, entry["k"], entry["v"], entry["pos"], q_pos,
                            width=None, scale=scale, window=window,
                            causal=causal, tp_axis=self.tp_axis)

    def fused_prefill(self, entry: dict, qg: Array, k_new: Array,
                      v_new: Array, p0: Array, n_valid: Array, *,
                      scale: float, window=None, causal: bool = True):
        """Flash-prefill on the raw f32 ring buffers (``width=None``).

        ``qg``: [B, C, K, G, hd] chunk query groups; the chunk's own K/V
        come from ``k_new``/``v_new`` (f32), history from the entry's
        buffers.  Returns f32 [B, C, K, G, hd].
        """
        from repro.kernels.attn.ops import flash_prefill
        return flash_prefill(qg, k_new, v_new, entry["k"], entry["v"],
                             entry["pos"], p0, n_valid, width=None,
                             scale=scale, window=window, causal=causal,
                             tp_axis=self.tp_axis)


RAW_KV_CODEC = RawKVCodec()


def _replicate_attn_out(o: Array, dist) -> Array:
    """Force the attention output replicated before the ``wo`` contraction.

    Under serving tensor parallelism the KV pool — and hence the per-head
    attention output — is sharded over kv heads, while ``wo`` contracts
    over the *full* head dimension.  Left to GSPMD that contraction runs
    as sharded partial sums + psum, whose float addition order differs
    from the single-device dot.  An explicit all-gather here keeps the
    contraction replicated, which is what makes the sharded engine's
    logits bit-identical to the unsharded run (per-head attention math is
    shard-local and exact; this is the only cross-head reduction).
    """
    if dist is None or not getattr(dist, "active", False):
        return o
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return o
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.lax.with_sharding_constraint(
        o, NamedSharding(mesh, PartitionSpec()))


def attention_prefill_chunk(params, spec: AttnSpec, x: Array,
                            positions: Array, cache: dict, tape: QTape,
                            prefix: str, *, n_valid: Array, window=None,
                            dist=None, codec=None):
    """One chunked-prefill step: ``C`` prompt positions against the pool.

    ``x``: [B, C, D] chunk activations at absolute positions ``positions``
    [B, C] (``positions[:, 0]`` is the chunk start ``p0``; ``p0 == 0``
    marks the admission chunk — see ``codec.append_chunk``).  ``n_valid``
    [B] masks a ragged final chunk in-kernel; rows past it carry padding
    whose outputs are garbage-by-contract.

    The chunk queries attend the slot's already-written history (ring
    entries ``0 <= pos < p0``) plus the chunk's **own** fresh K/V causally
    — the latter straight from the f32 projections, never from the pool,
    so a windowed ring cap smaller than the chunk can't evict in-window
    keys before they are attended.  The attend runs *before* the write
    (history is pre-chunk state); then ``codec.append_chunk`` quantizes
    the chunk's K/V into the pool — in packed mode the values go straight
    to int8/int16 mantissas, and with ``codec.fused_decode`` the attend is
    the Pallas flash-prefill kernel reading those containers directly, so
    f32 K/V never materializes in either direction.  Returns
    ``(y, cache')``.
    """
    codec = codec or RAW_KV_CODEC
    B, C, _ = x.shape
    q, k_new, v_new = _qkv(params, spec, x, positions, tape, prefix)
    H, K, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    G = H // K
    scale = 1.0 / math.sqrt(hd)
    p0 = positions[:, 0]
    qg = q.reshape(B, C, K, G, hd)
    kf = k_new.astype(jnp.float32)
    vf = v_new.astype(jnp.float32)
    if getattr(codec, "fused_decode", False):
        o = codec.fused_prefill(cache, qg, kf, vf, p0, n_valid, scale=scale,
                                window=window, causal=spec.causal)
    else:
        from repro.kernels.attn import ref as AR
        ck, cv, cpos = codec.load(cache)
        o = AR.chunk_attend(qg.astype(jnp.float32), ck.astype(jnp.float32),
                            cv.astype(jnp.float32), cpos, kf, vf, p0,
                            n_valid, scale=scale, window=window,
                            causal=spec.causal)
    cache = codec.append_chunk(cache, kf, vf, p0, n_valid)
    o = _replicate_attn_out(o, dist)
    o = o.reshape(B, C, spec.q_dim).astype(x.dtype)
    y = tape.dot(f"{prefix}/wo", o, params["wo"])
    return tape.act(f"{prefix}/out", y), cache


def attention_decode(params, spec: AttnSpec, x: Array, pos: Array,
                     cache: dict, tape: QTape, prefix: str, window=None,
                     dist=None, codec=None, append_mask=None):
    """One-token decode. ``x``: [B, 1, D]; ``cache``: a codec-owned entry
    (default: ``{"k","v","pos"}`` float ring buffers ``[B, W, ...]``).

    Appends the new token's K/V through the codec (slot ``pos % W``, so the
    token attends to itself), then attends over the whole buffer with a
    position-validity mask. ``pos`` may be a scalar or a per-sequence
    ``[B]``/``[B,1]`` vector — each slot decodes at its own position.
    ``append_mask`` (bool [B], optional) drops the codec append for
    masked-off rows — the chunked-prefill engine decodes all slots every
    step, and rows still mid-prefill must not be written to.
    Returns ``(y, cache')``.

    When the codec advertises ``fused_decode``, the attention runs as the
    fused Pallas flash-decode kernel (:mod:`repro.kernels.attn`) straight
    on the codec's storage containers — ``codec.load`` (and, for packed
    pools, the f32 K/V materialization it implies) never executes on the
    hot path.  The default ``RawKVCodec`` and f32 pools keep today's
    exact einsum path.

    When ``dist.cp_decode`` is set (long-context serving: the cache window
    axis is sharded over ``dist.cp_axis``), the global (non-windowed)
    attention runs context-parallel via
    :func:`repro.dist.cp_attention.cp_decode_attention` — each shard
    attends over its local slots and softmax statistics merge exactly.
    """
    codec = codec or RAW_KV_CODEC
    B = x.shape[0]
    if jnp.ndim(pos) == 0:
        positions = jnp.broadcast_to(pos, (B, 1))
    elif jnp.ndim(pos) == 1:
        positions = pos[:, None]
    else:
        positions = pos
    q, k_new, v_new = _qkv(params, spec, x, positions, tape, prefix)
    if append_mask is None:
        cache = codec.append(cache, k_new[:, 0], v_new[:, 0],
                             positions[:, 0])
    else:
        cache = codec.append(cache, k_new[:, 0], v_new[:, 0],
                             positions[:, 0], mask=append_mask)
    H, K, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    G = H // K
    scale = 1.0 / math.sqrt(hd)

    if (dist is not None and dist.active and dist.cp_decode and dist.cp_axis
            and window is None):
        from repro.dist.cp_attention import cp_decode_attention
        cache_k, cache_v, cache_pos = codec.load(cache)
        o = cp_decode_attention(q, cache_k, cache_v, cache_pos, positions,
                                num_heads=H, num_kv_heads=K, head_dim=hd,
                                cp_axes=dist.cp_axes).astype(x.dtype)
    elif getattr(codec, "fused_decode", False):
        # the fused kernel reads the pool's storage containers directly:
        # no codec.load, no f32 K/V materialization on the hot path
        qg = q.reshape(B, K, G, hd)
        o = codec.fused_attention(cache, qg, positions[:, 0], scale=scale,
                                  window=window, causal=spec.causal)
        o = o.reshape(B, 1, spec.q_dim).astype(x.dtype)
    else:
        cache_k, cache_v, cache_pos = codec.load(cache)
        qg = q.reshape(B, 1, K, G, hd)
        s = jnp.einsum("bqkgh,bskh->bkgqs", qg, cache_k,
                       precision=MATMUL_PRECISION,
                       preferred_element_type=jnp.float32) * scale
        q_pos = positions if positions.ndim == 2 else positions[0]
        valid = _mask(q_pos, cache_pos, window, spec.causal)  # [B, 1, W]
        valid = valid & (cache_pos >= 0)[:, None, :]          # -1 = empty slot
        s = jnp.where(valid[:, None, None, :, :], s, -1e30)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
        o = jnp.einsum("bkgqs,bskh->bqkgh", p, cache_v.astype(jnp.float32),
                       precision=MATMUL_PRECISION,
                       preferred_element_type=jnp.float32)
        o = o.reshape(B, 1, spec.q_dim).astype(x.dtype)
    o = _replicate_attn_out(o, dist)
    y = tape.dot(f"{prefix}/wo", o, params["wo"])
    return tape.act(f"{prefix}/out", y), cache


# ---------------------------------------------------------------------------
# feed-forward variants
# ---------------------------------------------------------------------------

def init_swiglu(key, d_model: int, d_ff: int) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": init_dense(k1, d_model, d_ff),
        "w_up": init_dense(k2, d_model, d_ff),
        "w_down": init_dense(k3, d_ff, d_model),
    }


def swiglu(params, x: Array, tape: QTape, prefix: str) -> Array:
    g = tape.dot(f"{prefix}/w_gate", x, params["w_gate"])
    u = tape.dot(f"{prefix}/w_up", x, params["w_up"])
    h = tape.act(f"{prefix}/pre", jax.nn.silu(g) * u)
    y = tape.dot(f"{prefix}/w_down", h, params["w_down"])
    return tape.act(f"{prefix}/out", y)


def init_gelu_ffn(key, d_model: int, d_ff: int) -> dict:
    k1, k2 = jax.random.split(key, 2)
    return {"w_in": init_dense(k1, d_model, d_ff),
            "w_out": init_dense(k2, d_ff, d_model),
            "b_in": jnp.zeros((d_ff,), jnp.float32),
            "b_out": jnp.zeros((d_model,), jnp.float32)}


def gelu_ffn(params, x: Array, tape: QTape, prefix: str) -> Array:
    h = tape.dot(f"{prefix}/w_in", x, params["w_in"]) + params["b_in"]
    h = tape.act(f"{prefix}/pre", jax.nn.gelu(h))
    y = tape.dot(f"{prefix}/w_out", h, params["w_out"]) + params["b_out"]
    return tape.act(f"{prefix}/out", y)


def init_maxout(key, d_in: int, d_out: int, k: int) -> dict:
    """Maxout unit (paper §2): max over k affine maps."""
    kw, = jax.random.split(key, 1)
    return {"w": jax.random.normal(kw, (k, d_in, d_out), jnp.float32)
            / math.sqrt(d_in),
            "b": jnp.zeros((k, d_out), jnp.float32)}


def maxout(params, x: Array, tape: QTape, prefix: str) -> Array:
    """h_i = max_j (b_ij + w_ij · x) — the paper's hidden unit.

    The k affine maps run as ONE [d_in, k·d_out] matmul (a single
    tile-friendly shape on the fused kernel path) followed by a
    reshape/max — same values and quantization statistics as k separate
    ``tape.dot`` calls, one kernel launch instead of k.
    """
    k, d_in, d_out = params["w"].shape
    w2 = params["w"].transpose(1, 0, 2).reshape(d_in, k * d_out)
    b2 = params["b"].reshape(k * d_out)
    z = tape.dot(f"{prefix}/w", x, w2) + b2
    h = jnp.max(z.reshape(z.shape[:-1] + (k, d_out)), axis=-2)
    return tape.act(f"{prefix}/out", h)


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------

def init_embed(key, vocab: int, d_model: int) -> Array:
    return jax.random.normal(key, (vocab, d_model), jnp.float32) * 0.02


def embed(table: Array, tokens: Array, tape: QTape) -> Array:
    t = tape.weight("emb/w", table)
    return tape.act("emb/out", jnp.take(t, tokens, axis=0))


def lm_head(table_or_w: Array, x: Array, tape: QTape, *, tied: bool) -> Array:
    """Vocabulary projection through ``tape.dot`` (fused-kernel capable).

    Tied heads contract against the embedding table's last dim
    (``transpose_b`` — the dgrad-layout kernel on the fused path)."""
    logits = tape.dot("head/w", x, table_or_w, transpose_b=tied)
    return tape.act("head/logits", logits)
