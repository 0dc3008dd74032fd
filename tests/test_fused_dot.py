"""Differentiable fused DFXP matmul: dispatch layer + QTape.dot + train step.

Bit-equality contract (interpret mode): the fused custom-VJP path —
forward, input gradient (dgrad kernel), weight gradient (wgrad kernel) —
produces exactly the bits of the jnp composite / ``jax.grad`` of the
differentiable oracle, across widths, non-128-aligned and batched shapes.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import DFXP_10_12
from repro.core.quant import new_sink
from repro.core.tape import QTape
from repro.kernels import dispatch
from repro.kernels.qmatmul.ops import qmm
from repro.kernels.qmatmul.ref import qmatmul_ref

WIDTHS = [8, 10, 12, 16]
MKN = [(64, 128, 256), (100, 130, 50), (8, 128, 128), (33, 65, 7)]


def _abr(key, M, K, N):
    ka, kb, kr = jax.random.split(jax.random.PRNGKey(key), 3)
    return (jax.random.normal(ka, (M, K)), jax.random.normal(kb, (K, N)) * 0.5,
            jax.random.normal(kr, (M, N)))


# ---------------------------------------------------------------------------
# kernel level: fused_dot vs jax.grad of the oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("mkn", MKN)
def test_fused_dot_fwd_and_grads_bit_equal(width, mkn):
    M, K, N = mkn
    a, b, r = _abr(0, M, K, N)
    e_a, e_b, e_g = jnp.float32(-6), jnp.float32(-7), jnp.float32(-5)

    def fused(a, b):
        return jnp.vdot(dispatch.fused_dot(
            a, b, e_a, e_b, width=width, grad_width=width, e_g=e_g,
            interpret=True), r)

    def ref(a, b):
        return jnp.vdot(qmatmul_ref(
            a, b, e_a, e_b, width=width, grad_width=width, e_g=e_g), r)

    yf = dispatch.fused_dot(a, b, e_a, e_b, width=width, interpret=True)
    yr = qmatmul_ref(a, b, e_a, e_b, width=width)
    np.testing.assert_array_equal(np.asarray(yf), np.asarray(yr))

    (da_f, db_f) = jax.grad(fused, (0, 1))(a, b)
    (da_r, db_r) = jax.grad(ref, (0, 1))(a, b)
    np.testing.assert_array_equal(np.asarray(da_f), np.asarray(da_r))
    np.testing.assert_array_equal(np.asarray(db_f), np.asarray(db_r))


def test_fused_dot_batched_and_transposed():
    B, S, D, V = 3, 37, 72, 56
    kx, kw, kr = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(kx, (B, S, D))
    w = jax.random.normal(kw, (V, D))
    r = jax.random.normal(kr, (B, S, V))
    e = jnp.float32(-6)

    def fused(x, w):
        return jnp.vdot(dispatch.fused_dot(
            x, w, e, e, width=10, grad_width=10, e_g=e, transpose_b=True,
            interpret=True), r)

    def ref(x, w):
        return jnp.vdot(qmatmul_ref(
            x.reshape(-1, D), w, e, e, width=10, grad_width=10, e_g=e,
            transpose_b=True), r.reshape(-1, V))

    yf = dispatch.fused_dot(x, w, e, e, width=10, transpose_b=True,
                            interpret=True)
    assert yf.shape == (B, S, V)
    yr = qmatmul_ref(x.reshape(-1, D), w, e, e, width=10, transpose_b=True)
    np.testing.assert_array_equal(np.asarray(yf).reshape(-1, V),
                                  np.asarray(yr))
    (dx_f, dw_f) = jax.grad(fused, (0, 1))(x, w)
    (dx_r, dw_r) = jax.grad(ref, (0, 1))(x, w)
    np.testing.assert_array_equal(np.asarray(dx_f), np.asarray(dx_r))
    np.testing.assert_array_equal(np.asarray(dw_f), np.asarray(dw_r))


def test_blocked_reduction_accumulator():
    """Multi-step reduction grid (VMEM accumulator path), quantized operands:
    the integer-grid products make blocked accumulation exact."""
    M, K, N = 48, 256, 64
    a, b, _ = _abr(3, M, K, N)
    e = jnp.float32(-5)
    c = qmm(a, b, e, e, kind="nn", width_a=10, width_b=10,
            blocks=(16, 64, 64), interpret=True)
    cr = qmatmul_ref(a, b, e, e, width=10)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(cr))


# ---------------------------------------------------------------------------
# dispatch: backend detection + autotune cache
# ---------------------------------------------------------------------------

def test_backend_detection_interpret_on_cpu():
    assert dispatch.default_interpret() is True  # CI/dev containers: no TPU


def test_blocks_interpret_mode_exact_shapes():
    assert dispatch.blocks_for("nn", 100, 50, 130, interpret=True) \
        == (100, 50, 130)


def test_autotune_cache_bucketing():
    dispatch.reset_autotune()
    try:
        dispatch.set_autotune(measure=False)
        b1 = dispatch.blocks_for("nn", 120, 250, 70, interpret=False)
        assert dispatch.autotune_cache() == {("nn", 128, 256, 128): b1}
        # same bucket → cache hit; an injected entry wins
        dispatch.autotune_cache()[("nn", 128, 256, 128)] = (8, 128, 128)
        assert dispatch.blocks_for("nn", 100, 140, 100, interpret=False) \
            == (8, 128, 128)
        # different bucket → new entry
        dispatch.blocks_for("tn", 120, 250, 70, interpret=False)
        assert len(dispatch.autotune_cache()) == 2
    finally:
        dispatch.reset_autotune()
        dispatch.set_autotune(measure=True)


def test_autotune_counts_refused_and_raises_when_all_refused(monkeypatch):
    """A tiling the compiler refuses is counted, not hidden; a bucket
    whose every candidate is refused raises with the compiler's message
    instead of caching a heuristic nobody compiled."""
    tried = []

    def picky_qmm(a, b, e_a, e_b, *, blocks, **kw):
        tried.append(blocks)
        if blocks != (128, 128, 128):
            raise ValueError("block shape refused")
        return jnp.zeros((1,), jnp.float32)

    def refuse_all(*a, **kw):
        raise ValueError("Mosaic refused this tiling")

    monkeypatch.setattr(dispatch, "qmm", picky_qmm)
    monkeypatch.setattr(dispatch, "save_autotune", lambda *a, **kw: None)
    saved = dict(dispatch._BLOCK_CACHE)
    dispatch.reset_autotune()
    dispatch.reset_profile()
    dispatch.profile_enable(True)
    try:
        assert dispatch.blocks_for("nn", 256, 256, 256,
                                   interpret=False) == (128, 128, 128)
        st = dispatch.profile_stats()[("nn", 256, 256, 256)]
        assert st["compiles"] == 1
        assert st["refused"] == len(set(tried)) - 1 > 0
        assert "refused" in dispatch.profile_table()

        monkeypatch.setattr(dispatch, "qmm", refuse_all)
        with pytest.raises(RuntimeError, match="Mosaic refused"):
            dispatch.blocks_for("tn", 256, 256, 256, interpret=False)
        assert ("tn", 256, 256, 256) not in dispatch.autotune_cache()
        assert dispatch.profile_stats()[("tn", 256, 256, 256)]["refused"] > 0
    finally:
        dispatch.profile_enable(False)
        dispatch.reset_profile()
        dispatch.reset_autotune()
        dispatch._BLOCK_CACHE.update(saved)


def test_autotune_runs_candidates_eagerly_while_a_jit_traces(monkeypatch):
    """Block selection runs while a jit traces the kernel's caller, under
    the serving mesh when sharded.  The candidates must still get real
    arrays on one device: traced ones would time the tracing and never
    meet a compile error, and mesh-sharded ones a Mosaic kernel refuses."""
    from jax.sharding import SingleDeviceSharding

    from repro.launch.mesh import make_serve_mesh

    seen = []

    def eager_qmm(a, b, e_a, e_b, *, blocks, **kw):
        traced = isinstance(a, jax.core.Tracer)
        seen.append((traced, None if traced else a.sharding))
        return a[:1, 0] + b[:1, 0]

    monkeypatch.setattr(dispatch, "qmm", eager_qmm)
    monkeypatch.setattr(dispatch, "save_autotune", lambda *a, **kw: None)
    saved = dict(dispatch._BLOCK_CACHE)
    dispatch.reset_autotune()

    @jax.jit
    def step(x):
        return x + dispatch.blocks_for("nn", 256, 256, 256,
                                       interpret=False)[0]

    try:
        with jax.set_mesh(make_serve_mesh(tp=1, cp=1)):
            step(jnp.float32(0.0))
        assert seen
        for traced, sharding in seen:
            assert not traced
            assert isinstance(sharding, SingleDeviceSharding), sharding
    finally:
        dispatch.reset_autotune()
        dispatch._BLOCK_CACHE.update(saved)


# ---------------------------------------------------------------------------
# QTape.dot: fused vs jnp composite, bit-identical
# ---------------------------------------------------------------------------

POL_C = DFXP_10_12
POL_F = dataclasses.replace(DFXP_10_12, fused_matmul=True)


def _tape_run(pol, x, w, r, transpose_b):
    def loss(x, w):
        tape = QTape(pol, {"w:d": jnp.float32(-5)}, {"g:d": new_sink()})
        y = tape.dot("d", x, w, transpose_b=transpose_b)
        return jnp.vdot(y, r), (y, tape.stats)

    (_, (y, stats)), (dx, dw) = jax.value_and_grad(
        loss, (0, 1), has_aux=True)(x, w)
    return y, dx, dw, stats


@pytest.mark.parametrize("shape,n,transpose_b", [
    ((6, 40, 72), 56, False),
    ((6, 40, 72), 56, True),
    ((2, 500, 64), 64, False),
    ((13, 130), 100, False),
])
def test_tape_dot_fused_bit_identical(shape, n, transpose_b):
    kx, kw, kr = jax.random.split(jax.random.PRNGKey(4), 3)
    K = shape[-1]
    x = jax.random.normal(kx, shape)
    w = jax.random.normal(kw, (n, K) if transpose_b else (K, n))
    r = jax.random.normal(kr, shape[:-1] + (n,))
    yc, dxc, dwc, stc = _tape_run(POL_C, x, w, r, transpose_b)
    yf, dxf, dwf, stf = _tape_run(POL_F, x, w, r, transpose_b)
    np.testing.assert_array_equal(np.asarray(yc), np.asarray(yf))
    np.testing.assert_array_equal(np.asarray(dxc), np.asarray(dxf))
    np.testing.assert_array_equal(np.asarray(stc["w:d"]),
                                  np.asarray(stf["w:d"]))
    # wgrad reduces over the M collapsed rows, and XLA:CPU sums the
    # composite's dot in another order than the kernel's.  Two f32 sums of
    # the same M terms differ by at most 2 * gamma_M * sum|term|, with
    # gamma_M ~= M * 2**-24: a bound of M ulps of the absolute sum.
    x2 = np.asarray(x, np.float64).reshape(-1, K)
    r2 = np.asarray(r, np.float64).reshape(-1, n)
    bound = x2.shape[0] * np.finfo(np.float32).eps * (np.abs(x2).T
                                                      @ np.abs(r2))
    if transpose_b:
        bound = bound.T
    diff = np.abs(np.asarray(dwf, np.float64) - np.asarray(dwc, np.float64))
    assert np.all(diff <= bound), float(np.max(diff - bound))


def test_maxout_fused_matches_per_piece_loop():
    """The single [d_in, k·d_out] maxout matmul reproduces the k-loop bits."""
    from repro.models import layers as L
    pol = POL_C
    km, kx = jax.random.split(jax.random.PRNGKey(5))
    p = L.init_maxout(km, 72, 24, 3)
    x = jax.random.normal(kx, (5, 72))
    scales = {"w:m/w": jnp.float32(-5)}
    tape = QTape(pol, scales, {})
    h = L.maxout(p, x, tape, "m")
    tape2 = QTape(pol, scales, {})
    outs = [tape2.dot("m/w", x, p["w"][j]) + p["b"][j] for j in range(3)]
    h_ref = tape2.act("m/out", jnp.max(jnp.stack(outs, 0), axis=0))
    np.testing.assert_array_equal(np.asarray(h), np.asarray(h_ref))
    np.testing.assert_array_equal(np.asarray(tape.stats["w:m/w"]),
                                  np.asarray(tape2.stats["w:m/w"]))


# ---------------------------------------------------------------------------
# train step: 2-step loss bit-identity, fused on vs off (DFXP-10 policy)
# ---------------------------------------------------------------------------

def _two_step_losses(policy):
    from benchmarks.kernels_bench import (make_tiny_maxout_step,
                                          tiny_maxout_batch)

    step, state = make_tiny_maxout_step(policy)
    losses = []
    for i in range(2):
        state, m = step(state, tiny_maxout_batch(i), jax.random.PRNGKey(i))
        losses.append(np.asarray(m["loss"]))
    return losses, state


def test_train_step_loss_bit_identity_fused_on_off():
    losses_c, state_c = _two_step_losses(POL_C)
    losses_f, state_f = _two_step_losses(POL_F)
    np.testing.assert_array_equal(losses_c[0], losses_f[0])
    np.testing.assert_array_equal(losses_c[1], losses_f[1])
    # parameters after two updates agree bit-for-bit too
    flat_c = jax.tree_util.tree_leaves(state_c.params)
    flat_f = jax.tree_util.tree_leaves(state_f.params)
    for c, f in zip(flat_c, flat_f):
        np.testing.assert_array_equal(np.asarray(c), np.asarray(f))
