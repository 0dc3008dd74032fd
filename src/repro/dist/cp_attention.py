"""Context-parallel GQA decode attention (KV window sharded over the mesh).

At 500k-token contexts the decode KV cache dwarfs everything else on a
device; ``ShardingRules(seq_shard_cache=True)`` shards the cache *window*
axis over the data axis, and this module runs single-query attention
against that sharded window: each device computes attention over its local
slots only, and the partial softmax statistics ``(max, sum-exp, weighted
values)`` are combined **exactly** across devices with
``pmax``/``psum`` — the standard log-sum-exp merge, so the result is
bit-close to monolithic attention (the multidevice test pins 1e-4).

Empty ring-buffer slots carry position ``-1``; validity is
``pos >= 0 and q_pos >= pos`` (causality in absolute positions), evaluated
locally — a device whose whole shard is invalid contributes zero weight
through the ``exp(m_local - m_global)`` correction.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.policy import MATMUL_PRECISION

Array = jax.Array


def _partial_attention(q, ck, cv, pos, q_pos, *, num_heads: int,
                       num_kv_heads: int, head_dim: int):
    """Local softmax partials over a (shard of the) KV window.

    ``q``: [B, Sq, H, hd]; ``ck``/``cv``: [B, W, K, hd]; ``pos``: [B, W]
    (slot absolute positions, -1 = empty); ``q_pos``: [B, Sq].
    Returns ``(o, l, m)``: [B, K, G, Sq, hd], [B, K, G, Sq], [B, K, G, Sq].
    """
    B, Sq = q.shape[:2]
    K, G = num_kv_heads, num_heads // num_kv_heads
    qg = q.astype(jnp.float32).reshape(B, Sq, K, G, head_dim)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, ck.astype(jnp.float32),
                   precision=MATMUL_PRECISION,
                   preferred_element_type=jnp.float32)
    s = s / math.sqrt(head_dim)
    valid = (pos[:, None, :] >= 0) & (q_pos[:, :, None] - pos[:, None, :]
                                      >= 0)                       # [B,Sq,W]
    vexp = valid[:, None, None, :, :]                             # [B,1,1,Sq,W]
    s = jnp.where(vexp, s, -1e30)
    m = jnp.max(s, axis=-1)                                       # [B,K,G,Sq]
    # fully-masked shard: s - m == 0 everywhere would leak exp(0)=1 — the
    # explicit where() zeroes invalid slots regardless of m.
    p = jnp.where(vexp, jnp.exp(s - m[..., None]), 0.0)
    l = p.sum(axis=-1)                                            # [B,K,G,Sq]
    o = jnp.einsum("bkgqs,bskh->bkgqh", p, cv.astype(jnp.float32),
                   precision=MATMUL_PRECISION,
                   preferred_element_type=jnp.float32)
    return o, l, m


def _merge(o, l, m, axes: Tuple[str, ...]):
    """Exact cross-shard softmax merge: rescale partials to the global max."""
    m_glob = jax.lax.pmax(m, axes)
    alpha = jnp.exp(m - m_glob)
    l_glob = jax.lax.psum(l * alpha, axes)
    o_glob = jax.lax.psum(o * alpha[..., None], axes)
    return o_glob, l_glob


def _finish(o, l, B: int, Sq: int, num_heads: int, head_dim: int, dtype):
    out = o / jnp.maximum(l, 1e-30)[..., None]     # [B, K, G, Sq, hd]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, num_heads * head_dim)
    return out.astype(dtype)


def cp_decode_attention(q: Array, cache_k: Array, cache_v: Array,
                        cache_pos: Array, q_pos: Array, *, num_heads: int,
                        num_kv_heads: int, head_dim: int,
                        cp_axes: Tuple[str, ...] = ()) -> Array:
    """Single-query attention over a (possibly window-sharded) KV cache.

    ``q``: [B, Sq, H, hd] (decode: Sq == 1); ``cache_k``/``cache_v``:
    [B, W, K, hd]; ``cache_pos``: [B, W] absolute positions (-1 empty);
    ``q_pos``: [B, Sq]. Returns [B, Sq, H*hd].

    With ``cp_axes`` naming live mesh axes that evenly divide ``W``, the
    window is sharded over them inside a ``shard_map`` and the partial
    statistics are merged exactly; otherwise (no mesh, axis missing,
    indivisible window) it computes the identical monolithic result.
    """
    B, Sq = q.shape[:2]
    W = cache_k.shape[1]
    kw = dict(num_heads=num_heads, num_kv_heads=num_kv_heads,
              head_dim=head_dim)

    cp_axes = tuple(cp_axes)
    mesh = jax.sharding.get_abstract_mesh()
    cp_size = 0
    if cp_axes and all(a in mesh.shape for a in cp_axes):
        cp_size = 1
        for a in cp_axes:
            cp_size *= mesh.shape[a]
    if cp_size > 1 and W % cp_size == 0:
        def local(q, ck, cv, pos, q_pos):
            o, l, m = _partial_attention(q, ck, cv, pos, q_pos, **kw)
            o, l = _merge(o, l, m, cp_axes)
            return _finish(o, l, B, Sq, num_heads, head_dim, q.dtype)

        fn = jax.shard_map(
            local,
            in_specs=(P(), P(None, cp_axes, None, None),
                      P(None, cp_axes, None, None), P(None, cp_axes), P()),
            out_specs=P(),
            check_vma=False,
        )
        return fn(q, cache_k, cache_v, cache_pos, q_pos)

    o, l, _ = _partial_attention(q, cache_k, cache_v, cache_pos, q_pos, **kw)
    return _finish(o, l, B, Sq, num_heads, head_dim, q.dtype)
