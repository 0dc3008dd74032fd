"""Mamba-2 language model, plain float32, with its SGD training steps.

The SSM is written in its quadratic ("attention") form over the whole
sequence (arXiv:2405.21060, section 6): for head ``h``

    y_i = sum_{j<=i} (C_i . B_j) exp(sum_{t=j+1..i} dt_t A_h) dt_j x_j + D_h x_i

with ``dt = softplus(dt_raw + dt_bias)`` and ``A_h = -exp(A_log_h)``,
after a depthwise causal convolution and SiLU on ``x``, ``B`` and ``C``;
the output is gated by ``silu(z)``, RMS-normalised, and projected.  Each
layer is pre-norm residual; the head is tied to the embedding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _dims(m):
    di = m["expand"] * m["d_model"]
    return di, di // m["headdim"], m["ngroups"] * m["d_state"]


def shapes(m: dict) -> dict:
    """Parameter shapes, in the pytree layout the program takes
    (``params["stages"][stage]["stacked"][block]``, leading layer axis)."""
    D, L, V = m["d_model"], m["n_layer"], m["vocab_size"]
    di, H, N = _dims(m)
    conv_dim = di + 2 * N
    block = {"norm": (L, D), "in_proj": (L, D, 2 * di + 2 * N + H),
             "conv_w": (L, m["d_conv"], conv_dim), "conv_b": (L, conv_dim),
             "A_log": (L, H), "D": (L, H), "dt_bias": (L, H),
             "norm_w": (L, di), "out_proj": (L, di, D)}
    return {"stages": {"dec": {"stacked": {"0:mamba": block},
                               "shared": {}}},
            "embed": (V, D), "final_norm": (D,)}


def mamba_layer(p, u, m, eps):
    """One Mamba-2 mixer on ``u`` [B, S, D]."""
    B_, S, _ = u.shape
    di, H, N = _dims(m)
    P = m["headdim"]
    zxbcdt = jnp.einsum("bsd,de->bse", u, p["in_proj"], precision=HI)
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:2 * di + 2 * N]
    dt = zxbcdt[..., 2 * di + 2 * N:]
    K = p["conv_w"].shape[0]
    xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(xp[:, k:k + S] * p["conv_w"][k] for k in range(K))
    xbc = jax.nn.silu(conv + p["conv_b"])
    x, Bm, Cm = xbc[..., :di], xbc[..., di:di + N], xbc[..., di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])                 # [B, S, H]
    a = dt * -jnp.exp(p["A_log"])                          # [B, S, H]
    cs = jnp.cumsum(a, axis=1)
    seg = cs[:, :, None, :] - cs[:, None, :, :]             # [B, i, j, H]
    causal = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("bin,bjn->bij", Cm, Bm, precision=HI)
    xh = x.reshape(B_, S, H, P)
    y = jnp.einsum("bij,bijh,bjh,bjhp->bihp", cb, decay, dt, xh,
                   precision=HI)
    y = y + p["D"][None, None, :, None] * xh
    y = rmsnorm(y.reshape(B_, S, di) * jax.nn.silu(z), p["norm_w"], eps)
    return jnp.einsum("bse,ed->bsd", y, p["out_proj"], precision=HI)


def loss(params, tokens, labels, m):
    """Mean next-token cross-entropy of ``tokens`` [B, S] against
    ``labels`` [B, S]."""
    eps = m["rms_norm_eps"]
    x = params["embed"][tokens]

    @jax.checkpoint
    def layer(x, p):
        return x + mamba_layer(p, rmsnorm(x, p["norm"], eps), m, eps), None

    x, _ = jax.lax.scan(layer, x,
                        params["stages"]["dec"]["stacked"]["0:mamba"])
    x = rmsnorm(x, params["final_norm"], eps)
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"], precision=HI)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


def sgd_schedule(opt: dict, step: int):
    """(learning rate, momentum) of SGD step ``step``: linear decay of
    the rate to ``lr_min_factor``, momentum rising linearly from
    ``momentum_init`` to ``momentum_final`` (the configuration's
    optimizer, paper section 8.1)."""
    lr = opt["lr"] * min(max(1.0 - step / opt["lr_decay_steps"],
                             opt["lr_min_factor"]), 1.0)
    t = min(max(step / opt["momentum_sat_steps"], 0.0), 1.0)
    mom = opt["momentum_init"] + (opt["momentum_final"]
                                  - opt["momentum_init"]) * t
    return lr, mom


def storage_steps(tree, width: int, margin_bits: int, maxima=None):
    """Power-of-two storage steps of the configuration's parameter
    format (DFXP, paper section 5): per layer for a stacked leaf (leading
    layer axis), per leaf otherwise, each the step at which the group's
    largest magnitude fits ``width`` bits with ``margin_bits`` to spare.
    ``maxima`` (same tree) overrides the magnitudes."""
    qmax = 2.0 ** (width - 1) - 1

    def one(path, x, mx):
        stacked = any(getattr(p, "key", None) == "stacked" for p in path)
        if mx is None:
            axes = tuple(range(1, x.ndim)) if stacked and x.ndim else None
            mx = jnp.max(jnp.abs(x), axis=axes)
        e = jnp.ceil(jnp.log2(jnp.maximum(mx, 1e-20) / qmax)) + margin_bits
        e = e.reshape(e.shape + (1,) * (x.ndim - e.ndim))
        return 2.0 ** e

    if maxima is None:
        return jax.tree_util.tree_map_with_path(lambda p, x: one(p, x, None),
                                                tree)
    return jax.tree_util.tree_map_with_path(one, tree, maxima)


def group_max(tree):
    """Largest magnitude per storage group (see :func:`storage_steps`)."""
    def one(path, x):
        stacked = any(getattr(p, "key", None) == "stacked" for p in path)
        axes = tuple(range(1, x.ndim)) if stacked and x.ndim else None
        return jnp.max(jnp.abs(x), axis=axes)
    return jax.tree_util.tree_map_with_path(one, tree)


def round_to(tree, steps, width: int):
    """Round to nearest on the storage grid, saturating at ``width``
    bits."""
    lo, hi = -2.0 ** (width - 1), 2.0 ** (width - 1) - 1
    return jax.tree.map(lambda x, s: jnp.clip(jnp.round(x / s), lo, hi) * s,
                        tree, steps)


def make_step(m: dict, compute_loss=loss):
    """One jitted SGD-with-momentum step of the plain model."""

    @jax.jit
    def step(params, mom_tree, batch, lr, mom):
        value, grads = jax.value_and_grad(compute_loss)(
            params, batch["tokens"], batch["labels"], m)
        mom_tree = jax.tree.map(lambda v, g: mom * v + g, mom_tree, grads)
        params = jax.tree.map(lambda p, v: p - lr * v, params, mom_tree)
        return params, mom_tree, value, grads

    return step


def calibrate(params, batches, m: dict, opt: dict, compute_loss=loss):
    """The configuration's calibration (paper section 9.3): plain SGD
    steps from ``params``, returning the largest magnitude each storage
    group of the parameters and of the momentum reached."""
    step = make_step(m, compute_loss)
    mom_tree = jax.tree.map(jnp.zeros_like, params)
    pmax = mmax = None
    for i, batch in enumerate(batches):
        lr, mom = sgd_schedule(opt, i)
        params, mom_tree, _, grads = step(params, mom_tree, batch,
                                          jnp.float32(lr), jnp.float32(mom))
        del grads
        pm, mm = group_max(params), group_max(mom_tree)
        pmax = pm if pmax is None else jax.tree.map(jnp.maximum, pmax, pm)
        mmax = mm if mmax is None else jax.tree.map(jnp.maximum, mmax, mm)
    return pmax, mmax


def train(params, batches, m: dict, opt: dict, compute_loss=loss,
          storage: dict | None = None, calibration_batches=()):
    """Follow the program's first ``len(batches)`` SGD steps from
    ``params``.  With ``storage`` (``width``, ``margin_bits``) the
    parameters and the momentum are kept in the configuration's storage
    format, its steps set by :func:`calibrate` over
    ``calibration_batches``.  Returns the losses, the first step's
    gradient as the optimizer took it (the momentum after one step from
    zero), the stored parameters before the first step and after the
    last."""
    step = make_step(m, compute_loss)
    w = storage["width"] if storage else None
    if storage:
        pmax, mmax = calibrate(params, calibration_batches, m, opt,
                               compute_loss)
        psteps = storage_steps(params, w, storage["margin_bits"], pmax)
        msteps = storage_steps(params, w, storage["margin_bits"], mmax)
        params = round_to(params, psteps, w)
    p0 = jax.tree.map(jnp.copy, params)
    mom_tree = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for i, batch in enumerate(batches):
        lr, mom = sgd_schedule(opt, i)
        params, mom_tree, value, grads = step(
            params, mom_tree, batch, jnp.float32(lr), jnp.float32(mom))
        del grads
        if storage:
            mom_tree = round_to(mom_tree, msteps, w)
            params = round_to(params, psteps, w)
        losses.append(float(value))
        if first_grad is None:
            first_grad = jax.tree.map(jnp.copy, mom_tree)
    return losses, first_grad, p0, params
