"""Mamba2 (SSD — state-space duality) block, chunked matmul form + decode.

Per head h with log-decay ``a_t = dt_t * A`` (A < 0), state ``h_t ∈ R^{P×N}``:

    h_t = exp(a_t) h_{t-1} + dt_t * x_t ⊗ B_t
    y_t = C_t · h_t + D * x_t

The chunked (SSD) form computes, per chunk of length Q, the intra-chunk
contribution as masked matmuls ``(C Bᵀ ⊙ decay) X`` and carries the chunk
state with a short ``lax.scan`` — MXU-friendly, O(S·Q) instead of O(S²).

DFXP integration: the recurrent state accumulates across the whole sequence
(like parameters across steps — paper §6), so it is quantized at the
*update* width at chunk boundaries (``tape.state``); everything else uses
the computation width.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.core.policy import MATMUL_PRECISION
from repro.core.tape import QTape

from .layers import init_dense, rmsnorm

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class SSMSpec:
    d_model: int
    state: int            # N
    headdim: int = 64     # P
    expand: int = 2
    conv_kernel: int = 4
    chunk: int = 128

    @property
    def d_inner(self):
        return self.expand * self.d_model

    @property
    def heads(self):
        return self.d_inner // self.headdim

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.state

    @property
    def in_proj_dim(self):
        # z (gate), x, B, C, dt
        return 2 * self.d_inner + 2 * self.state + self.heads


def init_ssm(key, spec: SSMSpec) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    H = spec.heads
    return {
        "in_proj": init_dense(k1, spec.d_model, spec.in_proj_dim),
        "conv_w": jax.random.normal(k2, (spec.conv_kernel, spec.conv_dim),
                                    jnp.float32) / math.sqrt(spec.conv_kernel),
        "conv_b": jnp.zeros((spec.conv_dim,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H, dtype=jnp.float32)),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(k3, (H,), jnp.float32,
                                       math.log(1e-3), math.log(1e-1))))),
        "norm_w": jnp.ones((spec.d_inner,), jnp.float32),
        "out_proj": init_dense(jax.random.fold_in(k1, 7), spec.d_inner,
                               spec.d_model),
    }


def _split_in_proj(spec: SSMSpec, zxbcdt: Array):
    di, N, H = spec.d_inner, spec.state, spec.heads
    z, x, B, C, dt = jnp.split(zxbcdt, [di, 2 * di, 2 * di + N,
                                        2 * di + 2 * N], axis=-1)
    return z, x, B, C, dt


def _causal_conv(x: Array, w: Array, b: Array) -> Array:
    """Depthwise causal conv; ``x``: [B, S, C], ``w``: [K, C].

    Expressed as a grouped ``lax.conv`` (one HBM pass) rather than K shifted
    reads — the shifted-add form cost 4× input traffic in the compiled HLO
    (EXPERIMENTS.md §Perf, zamba2 iteration 2).
    """
    K, C = w.shape
    y = jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w[:, None, :].astype(jnp.float32),
        window_strides=(1,), padding=[(K - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=C)
    return jax.nn.silu(y + b).astype(x.dtype)


def ssm_forward(params, spec: SSMSpec, u: Array, tape: QTape, prefix: str,
                return_cache: bool = False):
    """Training/prefill forward, chunked SSD. ``u``: [B, S, D].

    With ``return_cache``, also returns the decode cache (last ``K-1``
    pre-conv inputs + final SSM state) so decoding can continue.
    """
    B_, S, _ = u.shape
    H, P, N, Q = spec.heads, spec.headdim, spec.state, spec.chunk
    S_orig = S
    if S % Q:
        # pad to a chunk multiple; causality keeps real outputs unaffected,
        # and the pad positions' dt is masked to zero below so the final
        # chunk's state contribution (and hence the decode cache) is
        # exactly the state after S_orig real tokens
        pad = Q - S % Q
        u = jnp.pad(u, ((0, 0), (0, pad), (0, 0)))
        S = S + pad

    zxbcdt = tape.dot(f"{prefix}/in_proj", u, params["in_proj"])
    z, x_raw, B_raw, C_raw, dt = _split_in_proj(spec, zxbcdt)
    # conv per piece (same depthwise weights, sliced) — avoids the
    # concat→conv→split round-trip that dominated HBM traffic (§Perf)
    di = spec.d_inner
    w, b = params["conv_w"], params["conv_b"]
    x = _causal_conv(x_raw, w[:, :di], b[:di])
    Bm = _causal_conv(B_raw, w[:, di:di + N], b[di:di + N])
    Cm = _causal_conv(C_raw, w[:, di + N:], b[di + N:])
    x = tape.act(f"{prefix}/x", x)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # [B,S,H]
    A = -jnp.exp(params["A_log"].astype(jnp.float32))                # [H]
    if S != S_orig:
        # ragged tail: a pad token must neither decay the state (a = 0 →
        # exp(a) = 1) nor contribute to it (dt = 0 kills its x⊗B term);
        # valid positions' outputs are untouched (cumsum is a prefix op
        # and the intra-chunk mask is causal)
        valid = (jnp.arange(S) < S_orig)[None, :, None]
        dt = jnp.where(valid, dt, 0.0)
    a = dt * A                                                       # [B,S,H]

    nc = S // Q
    xc = x.reshape(B_, nc, Q, H, P).astype(jnp.float32)
    Bc = Bm.reshape(B_, nc, Q, N).astype(jnp.float32)
    Cc = Cm.reshape(B_, nc, Q, N).astype(jnp.float32)
    ac = a.reshape(B_, nc, Q, H)
    dtc = dt.reshape(B_, nc, Q, H)

    acum = jnp.cumsum(ac, axis=2)                                    # [B,nc,Q,H]

    # intra-chunk: Y[i] = sum_{j<=i} exp(acum_i - acum_j) (C_i·B_j) dt_j x_j
    G = jnp.einsum("bcin,bcjn->bcij", Cc, Bc,
                   precision=MATMUL_PRECISION,
                   preferred_element_type=jnp.float32)               # [B,nc,Q,Q]
    diff = acum[:, :, :, None, :] - acum[:, :, None, :, :]           # [B,nc,Q,Q,H]
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    # mask before the exp: above the diagonal diff > 0 and exp overflows
    # for long chunks, and a masked inf still turns the backward into
    # 0 * inf = NaN
    M = jnp.exp(jnp.where(causal[None, None, :, :, None], diff, -jnp.inf)
                ) * G[..., None] * dtc[:, :, None, :, :]
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", M, xc,
                         precision=MATMUL_PRECISION,
                         preferred_element_type=jnp.float32)

    # per-chunk final state contribution: sum_j exp(acum_Q - acum_j) dt_j B_j x_j^T
    decay_to_end = jnp.exp(acum[:, :, -1:, :] - acum)                # [B,nc,Q,H]
    hc = jnp.einsum("bcjh,bcjn,bcjhp->bchpn",
                    decay_to_end * dtc, Bc, xc,
                    precision=MATMUL_PRECISION,
                    preferred_element_type=jnp.float32)              # [B,nc,H,P,N]

    # carry chunk states
    def body(h_prev, xs):
        hc_i, a_end = xs                                             # a_end: [B,H]
        h_prev = tape.state(f"{prefix}/state", h_prev, record=False)
        h_new = jnp.exp(a_end)[:, :, None, None] * h_prev + hc_i
        return h_new, h_prev

    a_end = acum[:, :, -1, :]                                        # [B,nc,H]
    h0 = jnp.zeros((B_, H, P, N), jnp.float32)
    h_last, h_in = jax.lax.scan(
        body, h0,
        (hc.transpose(1, 0, 2, 3, 4), a_end.transpose(1, 0, 2)))
    h_in = h_in.transpose(1, 0, 2, 3, 4)                             # [B,nc,H,P,N]
    tape.record_state_stats(f"{prefix}/state", h_in)

    # inter-chunk: Y[i] += C_i · (exp(acum_i) h_prev_chunk)
    y_inter = jnp.einsum("bcin,bchpn,bcih->bcihp",
                         Cc, h_in, jnp.exp(acum),
                         precision=MATMUL_PRECISION,
                         preferred_element_type=jnp.float32)

    y = (y_intra + y_inter + params["D"][None, None, None, :, None]
         * xc).reshape(B_, S, spec.d_inner)
    y = y[:, :S_orig]
    y = tape.act(f"{prefix}/y", y.astype(u.dtype))
    y = rmsnorm(y * jax.nn.silu(z[:, :S_orig]), params["norm_w"])
    out = tape.dot(f"{prefix}/out_proj", y, params["out_proj"])
    out = tape.act(f"{prefix}/out", out)
    if return_cache:
        K = spec.conv_kernel
        need = K - 1
        take = min(need, S_orig)   # the last *real* pre-conv inputs
        lo = S_orig - take
        tail = jnp.concatenate(
            [x_raw[:, lo:S_orig], B_raw[:, lo:S_orig],
             C_raw[:, lo:S_orig]], axis=-1)
        if take < need:            # very short prompt: fresh-state zeros
            tail = jnp.pad(tail, ((0, 0), (need - take, 0), (0, 0)))
        return out, {"conv": tail, "state": h_last}
    return out, None


def init_ssm_cache(spec: SSMSpec, batch: int) -> dict:
    return {
        "conv": jnp.zeros((batch, spec.conv_kernel - 1, spec.conv_dim),
                          jnp.float32),
        "state": jnp.zeros((batch, spec.heads, spec.headdim, spec.state),
                           jnp.float32),
    }


def ssm_decode(params, spec: SSMSpec, u: Array, cache: dict, tape: QTape,
               prefix: str):
    """One-token recurrent step. ``u``: [B, 1, D] → (y [B,1,D], cache')."""
    B_ = u.shape[0]
    H, P, N = spec.heads, spec.headdim, spec.state

    zxbcdt = tape.dot(f"{prefix}/in_proj", u, params["in_proj"])
    z, x, Bm, Cm, dt = _split_in_proj(spec, zxbcdt)

    xbc = jnp.concatenate([x, Bm, Cm], axis=-1)                      # [B,1,conv]
    conv_buf = jnp.concatenate([cache["conv"], xbc], axis=1)         # [B,K,conv]
    w = params["conv_w"]
    out = jnp.einsum("bkc,kc->bc", conv_buf, w,
                     precision=MATMUL_PRECISION) + params["conv_b"]
    xbc1 = jax.nn.silu(out)[:, None, :]
    x, Bm, Cm = jnp.split(xbc1, [spec.d_inner, spec.d_inner + N], axis=-1)
    x = tape.act(f"{prefix}/x", x)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])[:, 0]  # [B,H]
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    a = dt * A                                                       # [B,H]

    xh = x[:, 0].reshape(B_, H, P).astype(jnp.float32)
    Bv = Bm[:, 0].astype(jnp.float32)                                # [B,N]
    Cv = Cm[:, 0].astype(jnp.float32)

    h = tape.state(f"{prefix}/state", cache["state"])
    h = (jnp.exp(a)[:, :, None, None] * h
         + jnp.einsum("bh,bhp,bn->bhpn", dt, xh, Bv,
                      precision=MATMUL_PRECISION))
    y = (jnp.einsum("bn,bhpn->bhp", Cv, h, precision=MATMUL_PRECISION)
         + params["D"][None, :, None] * xh)
    y = y.reshape(B_, 1, spec.d_inner).astype(u.dtype)
    y = tape.act(f"{prefix}/y", y)
    y = rmsnorm(y * jax.nn.silu(z), params["norm_w"])
    out = tape.dot(f"{prefix}/out_proj", y, params["out_proj"])
    out = tape.act(f"{prefix}/out", out)
    return out, {"conv": conv_buf[:, 1:], "state": h}
