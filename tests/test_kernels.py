"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.dfxp.ops import dfxp_quantize
from repro.kernels.dfxp.ref import dfxp_quantize_ref
from repro.kernels.qmatmul.ops import qmatmul
from repro.kernels.qmatmul.ref import qmatmul_ref

SHAPES_Q = [(8, 128), (256, 512), (3, 7), (1000,), (4, 33, 65), (2, 2, 2, 130)]
WIDTHS = [4, 8, 10, 12, 16]
DTYPES = [jnp.float32, jnp.bfloat16, jnp.float16]


@pytest.mark.parametrize("shape", SHAPES_Q)
@pytest.mark.parametrize("width", [8, 10])
def test_dfxp_quantize_matches_ref_shapes(shape, width):
    x = jax.random.normal(jax.random.PRNGKey(0), shape) * 4.0
    e = jnp.float32(-4)
    y, st = dfxp_quantize(x, e, width=width, interpret=True)
    yr, str_ = dfxp_quantize_ref(x, e, width=width)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))
    np.testing.assert_array_equal(np.asarray(st), np.asarray(str_))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("width", WIDTHS)
def test_dfxp_quantize_dtypes(dtype, width):
    x = (jax.random.normal(jax.random.PRNGKey(1), (64, 256)) * 10).astype(dtype)
    e = jnp.float32(-3)
    y, st = dfxp_quantize(x, e, width=width, interpret=True)
    yr, str_ = dfxp_quantize_ref(x, e, width=width)
    assert y.dtype == dtype
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(yr, np.float32))
    np.testing.assert_array_equal(np.asarray(st), np.asarray(str_))


def test_dfxp_quantize_extreme_exponents():
    x = jax.random.normal(jax.random.PRNGKey(2), (32, 128)) * 1e-6
    for e in (-30.0, -20.0, 0.0, 10.0):
        y, st = dfxp_quantize(x, jnp.float32(e), width=10, interpret=True)
        yr, sr = dfxp_quantize_ref(x, jnp.float32(e), width=10)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))
        np.testing.assert_array_equal(np.asarray(st), np.asarray(sr))


MM_SHAPES = [(128, 128, 128), (256, 384, 128), (64, 128, 256), (100, 130, 50),
             (8, 128, 128)]


@pytest.mark.parametrize("mkn", MM_SHAPES)
def test_qmatmul_matches_ref(mkn):
    M, K, N = mkn
    ka, kb = jax.random.split(jax.random.PRNGKey(3))
    a = jax.random.normal(ka, (M, K))
    b = jax.random.normal(kb, (K, N)) * 0.5
    e_a, e_b = jnp.float32(-6), jnp.float32(-7)
    c = qmatmul(a, b, e_a, e_b, width=10, interpret=True)
    cr = qmatmul_ref(a, b, e_a, e_b, width=10)
    np.testing.assert_allclose(np.asarray(c), np.asarray(cr),
                               rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("width", [4, 8, 12])
def test_qmatmul_widths(width):
    ka, kb = jax.random.split(jax.random.PRNGKey(4))
    a = jax.random.normal(ka, (64, 128)) * 8
    b = jax.random.normal(kb, (128, 128))
    c = qmatmul(a, b, jnp.float32(-2), jnp.float32(-5), width=width,
                interpret=True)
    cr = qmatmul_ref(a, b, jnp.float32(-2), jnp.float32(-5), width=width)
    np.testing.assert_allclose(np.asarray(c), np.asarray(cr),
                               rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("kind", ["nn", "nt", "tn"])
def test_qmatmul_kernel_dot_at_full_precision(kind,
                                              assert_dots_full_precision):
    """The kernel's dot keeps f32 products: one bf16 MXU pass would round
    10-bit operands to 8 significant bits on a TPU."""
    from repro.kernels.qmatmul.ops import qmm
    a = jnp.zeros((128, 128), jnp.float32)
    assert_dots_full_precision(lambda: qmm(a, a, 0.0, 0.0, kind=kind,
                                           width_a=10, width_b=10,
                                           blocks=(128, 128, 128),
                                           interpret=True))


def test_qmatmul_quantization_actually_applied():
    # identity scales wide enough that quantization is a no-op vs exact matmul
    a = jnp.round(jax.random.normal(jax.random.PRNGKey(5), (64, 128)) * 4)
    b = jnp.round(jax.random.normal(jax.random.PRNGKey(6), (128, 128)) * 4)
    c = qmatmul(a, b, jnp.float32(0), jnp.float32(0), width=16,
                interpret=True)
    np.testing.assert_allclose(np.asarray(c), np.asarray(a @ b), rtol=1e-6)
    # and with a coarse grid it differs (quantization visible)
    c2 = qmatmul(a * 0.1, b, jnp.float32(0), jnp.float32(0), width=16,
                 interpret=True)
    assert not np.allclose(np.asarray(c2), np.asarray((a * 0.1) @ b))
