"""Compile the main path's kernels, train step and serve steps for a TPU v5e.

Each test lowers and compiles against a described ``v5e:2x2`` topology
(``jax.experimental.topologies``): the TPU compiler refuses here what it
would refuse on the chip — block shapes Mosaic cannot tile, VMEM
overflows, programs larger than the device's memory.  Nothing runs, so
these say nothing about results or speed.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core.policy import PrecisionPolicy
from repro.kernels import _tiling, dispatch
from repro.kernels.attn import ops as A
from repro.kernels.dfxp.dfxp_kernel import dfxp_quantize_2d
from repro.kernels.qmatmul.ops import qmm

HBM_BYTES = int(15.75 * 2**30)   # v5e: 16 GB, less what the runtime keeps

# (arch, tokens R, reduction D, output C) of one projection per config
MM_SHAPES = {"mamba2_370m": (512, 1024, 4480),    # in_proj, batch 2x256
             "granite_moe_1b": (512, 1024, 1024)}  # attention q/o proj
# (K, G, hd): granite_moe_1b and llama3_8b attention widths
ATTN_WIDTHS = {"granite": (8, 2, 64), "llama3_8b": (8, 4, 128)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _sds(one_chip):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


@pytest.mark.parametrize("arch", sorted(MM_SHAPES))
@pytest.mark.parametrize("kind", ["nn", "nt", "tn"])
def test_qmatmul_compiles(one_chip, arch, kind):
    R, D, C = MM_SHAPES[arch]
    a_shape = {"nn": (R, D), "nt": (R, D), "tn": (D, R)}[kind]
    b_shape = {"nn": (D, C), "nt": (C, D), "tn": (D, C)}[kind]
    S = _sds(one_chip)
    for blocks in {(128, 128, 128), _tiling.mm_blocks(kind, R, C, D)}:
        _compile(lambda a, b, e: qmm(a, b, e, e, kind=kind, width_a=10,
                                     width_b=10, blocks=blocks,
                                     interpret=False),
                 S(a_shape, jnp.float32), S(b_shape, jnp.float32),
                 S((), jnp.float32))


# (kind, R, C, D, width_b) of mamba2_370m's qmatmuls at 512 tokens, as
# tape_dot calls them: raw activations and cotangents (width_a None), the
# weight rounded in the forward and dgrad, both raw in the wgrad.
MAMBA2_QMM = [("nn", 512, 4384, 1024, 10), ("nt", 512, 1024, 4384, 10),
              ("tn", 1024, 4384, 512, None), ("nn", 512, 1024, 2048, 10),
              ("nt", 512, 2048, 1024, 10), ("tn", 2048, 1024, 512, None),
              ("nt", 512, 50432, 1024, 10), ("nn", 512, 1024, 50432, 10),
              ("tn", 50432, 1024, 512, None)]


@pytest.mark.parametrize("kind,R,C,D,width_b", MAMBA2_QMM)
def test_qmatmul_ranked_tiles_compile(one_chip, kind, R, C, D, width_b):
    """The largest tile the dispatch layer offers a shape (its first in
    tie-break order) fits the chip's VMEM at the kernel's scoped limit."""
    key = dispatch._qmm_key(kind, R, C, D)
    blocks = dispatch._qmm_candidates(*key)[0]
    assert blocks[0] * blocks[1] * blocks[2] >= 64 * 2**20
    a_shape = {"nn": (R, D), "nt": (R, D), "tn": (D, R)}[kind]
    b_shape = {"nn": (D, C), "nt": (C, D), "tn": (D, C)}[kind]
    S = _sds(one_chip)
    _compile(lambda a, b, e: qmm(a, b, e, e, kind=kind, width_a=None,
                                 width_b=width_b, blocks=blocks,
                                 interpret=False),
             S(a_shape, jnp.float32), S(b_shape, jnp.float32),
             S((), jnp.float32))


def test_dfxp_quantize_compiles(one_chip):
    S = _sds(one_chip)
    _compile(lambda x, s, i: dfxp_quantize_2d(x, s, i, width=10,
                                              interpret=False),
             S((1024, 2048), jnp.float32), S((), jnp.float32),
             S((), jnp.float32))


def _pool(S, B, W, K, hd, width):
    dt = {8: jnp.int8, None: jnp.float32}[width]
    return S((B, W, K, hd), dt)


@pytest.mark.parametrize("width", [8, None], ids=["int8", "f32"])
@pytest.mark.parametrize("arch", sorted(ATTN_WIDTHS))
def test_flash_decode_compiles(one_chip, arch, width):
    K, G, hd = ATTN_WIDTHS[arch]
    B, W = 4, 4096
    S = _sds(one_chip)
    block_w = dispatch._default_split(
        W, lambda c: dispatch._attn_fits(c, K, G, hd, width))[0]
    for bw in {block_w, 128}:
        _compile(lambda q, k, v, pos, qp, e: A.flash_decode(
            q, k, v, pos, qp, e, e, width=width, scale=0.125, block_w=bw,
            interpret=False),
            S((B, K, G, hd), jnp.float32), _pool(S, B, W, K, hd, width),
            _pool(S, B, W, K, hd, width), S((B, W), jnp.int32),
            S((B,), jnp.int32), S((B,), jnp.float32))


@pytest.mark.parametrize("width", [8, None], ids=["int8", "f32"])
@pytest.mark.parametrize("arch", sorted(ATTN_WIDTHS))
def test_flash_prefill_compiles(one_chip, arch, width):
    K, G, hd = ATTN_WIDTHS[arch]
    B, W, C = 2, 4096, 64
    S = _sds(one_chip)
    block_w = dispatch._default_split(
        W, lambda c: dispatch._prefill_fits(c, C, K, G, hd, width))[0]
    _compile(lambda q, kn, vn, k, v, pos, p0, nv, e: A.flash_prefill(
        q, kn, vn, k, v, pos, p0, nv, e, e, width=width, scale=0.125,
        block_w=block_w, interpret=False),
        S((B, C, K, G, hd), jnp.float32), S((B, C, K, hd), jnp.float32),
        S((B, C, K, hd), jnp.float32), _pool(S, B, W, K, hd, width),
        _pool(S, B, W, K, hd, width), S((B, W), jnp.int32),
        S((B,), jnp.int32), S((B,), jnp.int32), S((B,), jnp.float32))


@pytest.mark.parametrize("width", [8, None], ids=["int8", "f32"])
@pytest.mark.parametrize("arch", sorted(ATTN_WIDTHS))
def test_flash_decode_paged_compiles(one_chip, arch, width):
    K, G, hd = ATTN_WIDTHS[arch]
    B, P, nblocks, n_pages = 4, 16, 32, 129
    S = _sds(one_chip)
    _compile(lambda q, k, v, bt, pos, qp, e: A.flash_decode_paged(
        q, k, v, bt, pos, qp, e, e, width=width, scale=0.125,
        interpret=False),
        S((B, K, G, hd), jnp.float32), _pool(S, n_pages, P, K, hd, width),
        _pool(S, n_pages, P, K, hd, width), S((B, nblocks), jnp.int32),
        S((B, nblocks * P), jnp.int32), S((B,), jnp.int32),
        S((n_pages,), jnp.float32))


@pytest.mark.parametrize("width", [8, None], ids=["int8", "f32"])
@pytest.mark.parametrize("arch", sorted(ATTN_WIDTHS))
def test_flash_prefill_paged_compiles(one_chip, arch, width):
    K, G, hd = ATTN_WIDTHS[arch]
    B, P, nblocks, n_pages, C = 2, 16, 32, 65, 16
    S = _sds(one_chip)
    _compile(lambda q, kn, vn, k, v, bt, pos, p0, nv, e:
             A.flash_prefill_paged(q, kn, vn, k, v, bt, pos, p0, nv, e, e,
                                   width=width, scale=0.125,
                                   interpret=False),
             S((B, C, K, G, hd), jnp.float32), S((B, C, K, hd), jnp.float32),
             S((B, C, K, hd), jnp.float32),
             _pool(S, n_pages, P, K, hd, width),
             _pool(S, n_pages, P, K, hd, width), S((B, nblocks), jnp.int32),
             S((B, nblocks * P), jnp.int32), S((B,), jnp.int32),
             S((B,), jnp.int32), S((n_pages,), jnp.float32))


@pytest.mark.parametrize("width", [8, None], ids=["int8", "f32"])
@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_paged_kernels_compile_at_serving_size(one_chip, kernel, width):
    """64 slots x 8192 tokens of context in 16-token pages at granite
    widths: the block table and the per-page steps the kernels keep in
    SMEM grow with slots x pages, so a serving-size table must fit."""
    K, G, hd = ATTN_WIDTHS["granite"]
    B, P, C = 64, 16, 16
    nblocks = 8192 // P
    n_pages = B * nblocks + 1
    S = _sds(one_chip)
    pool = _pool(S, n_pages, P, K, hd, width)
    tables = (S((B, nblocks), jnp.int32), S((B, nblocks * P), jnp.int32))
    if kernel == "decode":
        _compile(lambda q, k, v, bt, pos, qp, e: A.flash_decode_paged(
            q, k, v, bt, pos, qp, e, e, width=width, scale=0.125,
            interpret=False),
            S((B, K, G, hd), jnp.float32), pool, pool, *tables,
            S((B,), jnp.int32), S((n_pages,), jnp.float32))
    else:
        _compile(lambda q, kn, vn, k, v, bt, pos, p0, nv, e:
                 A.flash_prefill_paged(q, kn, vn, k, v, bt, pos, p0, nv, e,
                                       e, width=width, scale=0.125,
                                       interpret=False),
                 S((B, C, K, G, hd), jnp.float32),
                 S((B, C, K, hd), jnp.float32),
                 S((B, C, K, hd), jnp.float32), pool, pool, *tables,
                 S((B,), jnp.int32), S((B,), jnp.int32),
                 S((n_pages,), jnp.float32))


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Steer whole programs' kernels onto their compiled path, with
    heuristic tiles (the autotuner would run candidates on this CPU)."""
    monkeypatch.setitem(_tiling._BACKEND, "interpret", False)
    saved = dict(dispatch._BLOCK_CACHE)
    dispatch.set_autotune(measure=False)
    try:
        yield
    finally:
        dispatch.set_autotune(measure=True)
        dispatch.reset_autotune()
        dispatch._BLOCK_CACHE.update(saved)


def _place(one_chip, tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                       sharding=one_chip),
                        tree)


def test_mamba2_train_step_fits_one_chip(one_chip, compiled_kernels):
    """The full-width DFXP 10/12 train step that ``chip_smoke.py`` runs
    (global batch 2 x 256, fused qmatmul) compiles and fits in HBM."""
    from repro.models import transformer as T
    from repro.optim.opt import OptConfig, sgd_init
    from repro.train import benign_injection, init_train_state
    from repro.train.step import make_train_step

    cfg = configs.get("mamba2_370m")
    policy = PrecisionPolicy(arithmetic="dfxp", comp_width=10,
                             update_width=12, fused_matmul=True)
    gs = T.group_shapes(cfg)
    step = make_train_step(
        lambda p, b, s, e: T.loss_fn(cfg, policy, p, b, e, s), gs,
        policy, OptConfig(kind="sgd", lr=0.01), supervise=True)

    def make_state():
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        return init_train_state(params, sgd_init(params), gs, policy)

    S = _sds(one_chip)
    batch = {"tokens": S((2, 256), jnp.int32),
             "labels": S((2, 256), jnp.int32)}
    compiled = _compile(step, _place(one_chip, jax.eval_shape(make_state)),
                        batch, S((2,), jnp.uint32), {},
                        _place(one_chip, jax.eval_shape(benign_injection)))
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total <= HBM_BYTES, f"{total / 2**30:.2f} GiB > 15.75 GiB"


def test_granite_serve_steps_compile(one_chip, compiled_kernels):
    """The serve programs ``chip_smoke.py`` runs — whole-prompt prefill of
    a 128-token prompt, its pool insert, and a fused int8 decode step of
    full-width ``granite_moe_1b`` — compile.  (The prefill once built its
    KV ring with sibling scatters, which crashed the TPU compiler.)"""
    from repro.models import transformer as T
    from repro.serve import EngineOptions, ServeEngine

    cfg = configs.get("granite_moe_1b")
    params = jax.eval_shape(lambda: T.init_params(cfg,
                                                  jax.random.PRNGKey(0)))
    eng = ServeEngine(cfg, PrecisionPolicy("dfxp", fused_decode=True),
                      params, max_slots=4, max_len=144,
                      options=EngineOptions(cache_bits=8))
    S = _sds(one_chip)
    w, pool = _place(one_chip, eng._w), _place(one_chip, eng._pool)
    keys = S((1, 2), jnp.uint32)
    prefill = (w, S((1, 128), jnp.int32), keys)
    eng._prefill.lower(*prefill).compile()
    entry = _place(one_chip, jax.eval_shape(eng._prefill, *prefill)[2])
    eng._insert.lower(pool, entry, S((1,), jnp.int32), keys).compile()
    _compile(eng._decode, w, pool, S((4,), jnp.int32), S((4,), jnp.int32),
             S((4, 2), jnp.uint32), S((4,), jnp.bool_))
