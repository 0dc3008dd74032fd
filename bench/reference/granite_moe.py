"""Granite-3.0 MoE decoder, plain float32, one sequence at a time.

Every layer is pre-norm residual attention then a pre-norm residual
mixture of SwiGLU experts.  Attention: rotary positions (half-split
rotation, base ``rope_theta``), grouped queries (query head ``h`` reads
kv head ``h // (heads / kv_heads)``), causal softmax scaled by
``1/sqrt(head_dim)``.  Experts: a softmax router, the ``top_k`` largest
probabilities renormalised to sum to one, every expert computed densely
and mixed by its gate.  The head is tied to the embedding.

Expert capacity follows the serving path it checks: the prompt is
routed as one group, where an expert takes at most
``ceil(prompt_len * top_k / experts * capacity_factor)`` of its
(token, choice) pairs in token order and drops the rest; positions after
the prompt are decoded one at a time and never dropped.

``kv_bits`` rounds the keys and values that positions after the prompt
read (one power-of-two step per layer for K and for V, covering the
sequence's largest magnitude): the lower-precision control of a
quantized KV cache.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def shapes(m: dict) -> dict:
    """Parameter shapes, in the pytree layout the program takes
    (``params["stages"][stage]["stacked"][block]``, leading layer axis)."""
    D, L, V = m["hidden_size"], m["num_hidden_layers"], m["vocab_size"]
    hd = m["head_dim"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    E, F = m["num_local_experts"], m["intermediate_size"]
    attn = {"norm": (L, D), "wq": (L, D, q), "wk": (L, D, kv),
            "wv": (L, D, kv), "wo": (L, q, D)}
    moe = {"norm": (L, D), "router": (L, D, E), "w_gate": (L, E, D, F),
           "w_up": (L, E, D, F), "w_down": (L, E, F, D)}
    return {"stages": {"dec": {"stacked": {"0:attn": attn, "1:moe": moe},
                               "shared": {}}},
            "embed": (V, D), "final_norm": (D,)}


def rope(x, theta):
    """``x`` [T, heads, hd] at positions 0..T-1."""
    T, _, hd = x.shape
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def round_pow2(x, bits):
    """Round ``x`` to ``bits``-bit integers times one power-of-two step
    that covers max|x|."""
    qmax = 2.0 ** (bits - 1) - 1
    step = 2.0 ** jnp.ceil(jnp.log2(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
                                    / qmax))
    return jnp.clip(jnp.round(x / step), -qmax - 1, qmax) * step


def attention(p, h, m, prompt_len, kv_bits):
    T = h.shape[0]
    H, K, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                m["head_dim"])
    q = jnp.einsum("td,de->te", h, p["wq"], precision=HI).reshape(T, H, hd)
    k = jnp.einsum("td,de->te", h, p["wk"], precision=HI).reshape(T, K, hd)
    v = jnp.einsum("td,de->te", h, p["wv"], precision=HI).reshape(T, K, hd)
    q, k = rope(q, m["rope_theta"]), rope(k, m["rope_theta"])
    G = H // K
    causal = jnp.tril(jnp.ones((T, T), bool))

    def attend(kk, vv):
        s = jnp.einsum("tkgd,skd->kgts", q.reshape(T, K, G, hd), kk,
                       precision=HI) / math.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        o = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, -1), vv,
                       precision=HI)
        return o.reshape(T, H * hd)

    o = attend(k, v)
    if kv_bits:
        later = (jnp.arange(T) >= prompt_len)[:, None]
        o = jnp.where(later, attend(round_pow2(k, kv_bits),
                                    round_pow2(v, kv_bits)), o)
    return jnp.einsum("te,ed->td", o, p["wo"], precision=HI)


def experts(p, h, m, prompt_len):
    T = h.shape[0]
    E, k = m["num_local_experts"], m["num_experts_per_tok"]
    probs = jax.nn.softmax(
        jnp.einsum("td,de->te", h, p["router"], precision=HI), -1)
    gates, ids = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # capacity over the prompt's (token, choice) pairs in token order
    in_prompt = jnp.arange(T) < prompt_len
    onehot = jax.nn.one_hot(ids.reshape(-1), E, dtype=jnp.int32) \
        * jnp.repeat(in_prompt, k)[:, None].astype(jnp.int32)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, 0) - 1,
                               ids.reshape(-1, 1), 1).reshape(T, k)
    cap = jnp.ceil(prompt_len * k / E * m["capacity_factor"])
    keep = jnp.where(in_prompt[:, None], rank < cap, True)
    weight = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], ids].add(gates * keep)
    g = jnp.einsum("td,edf->tef", h, p["w_gate"], precision=HI)
    u = jnp.einsum("td,edf->tef", h, p["w_up"], precision=HI)
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, p["w_down"],
                   precision=HI)
    return jnp.einsum("ted,te->td", y, weight, precision=HI)


def logits(params, tokens, prompt_len, m, kv_bits=0):
    """Logits [T, vocab] at every position of ``tokens`` [T] (one
    sequence: a prompt of ``prompt_len`` then the served tokens, padded
    at the end)."""
    eps = m["rms_norm_eps"]
    x = params["embed"][tokens]
    blocks = params["stages"]["dec"]["stacked"]

    def layer(x, p):
        pa, pm = p
        x = x + attention(pa, rmsnorm(x, pa["norm"], eps), m, prompt_len,
                          kv_bits)
        x = x + experts(pm, rmsnorm(x, pm["norm"], eps), m, prompt_len)
        return x, None

    x, _ = jax.lax.scan(layer, x, (blocks["0:attn"], blocks["1:moe"]))
    x = rmsnorm(x, params["final_norm"], eps)
    return jnp.einsum("td,vd->tv", x, params["embed"], precision=HI)


def make_logits(m: dict, kv_bits: int = 0):
    """``logits`` jitted for configuration ``m``; one program per padded
    length."""
    return jax.jit(lambda params, tokens, prompt_len: logits(
        params, tokens, prompt_len, m, kv_bits))
