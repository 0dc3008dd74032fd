"""Fused flash-decode attention: kernel bit-equality, dispatch, serve path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.policy import PrecisionPolicy
from repro.kernels import dispatch
from repro.kernels.attn import ref as R
from repro.kernels.attn.ops import flash_decode
from repro.models import transformer as T
from repro.serve import (CacheQuantConfig, EngineOptions, PackedKVCodec,
                         ServeEngine)


def _case(key, B, W, K, G, hd, width, n_valid=None, holes=False):
    """Random (q, k, v, pos, q_pos, k_exp, v_exp) in the codec layout."""
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (B, K, G, hd), jnp.float32)
    if width is None:
        k = jax.random.normal(ks[1], (B, W, K, hd), jnp.float32)
        v = jax.random.normal(ks[2], (B, W, K, hd), jnp.float32)
        ke = ve = None
    else:
        from repro.core.packed import container_dtype, qrange
        qmax, qmin = qrange(width)
        dt = container_dtype(width)
        k = jax.random.randint(ks[1], (B, W, K, hd), int(qmin),
                               int(qmax) + 1).astype(dt)
        v = jax.random.randint(ks[2], (B, W, K, hd), int(qmin),
                               int(qmax) + 1).astype(dt)
        ke = jax.random.randint(ks[3], (B,), -8, -2).astype(jnp.float32)
        ve = jax.random.randint(ks[4], (B,), -8, -2).astype(jnp.float32)
    n_valid = W if n_valid is None else n_valid
    pos = jnp.where(jnp.arange(W) < n_valid, jnp.arange(W), -1)
    pos = jnp.broadcast_to(pos, (B, W)).astype(jnp.int32)
    if holes:  # scattered empty slots, different per row
        gap = jax.random.bernoulli(ks[3] if width is None else ks[0],
                                   0.3, (B, W))
        pos = jnp.where(gap, -1, pos)
    # per-row query positions (unequal: continuous batching decodes each
    # slot at its own position)
    q_pos = jnp.maximum(jnp.max(pos, axis=1), 0).astype(jnp.int32)
    return q, k, v, pos, q_pos, ke, ve


def _both(case, width, scale=0.25, window=None, causal=True, block_w=None):
    q, k, v, pos, q_pos, ke, ve = case
    out = flash_decode(q, k, v, pos, q_pos, ke, ve, width=width, scale=scale,
                       window=window, causal=causal, block_w=block_w,
                       interpret=True)
    ref = R.decode_attention_ref(q, k, v, pos, q_pos, k_exp=ke, v_exp=ve,
                                 width=width, scale=scale, window=window,
                                 causal=causal)
    return np.asarray(out), np.asarray(ref)


# ---------------------------------------------------------------------------
# acceptance: interpret-mode bit-equality vs the ref composite
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("width", [8, 16, None], ids=["int8", "int16", "f32"])
def test_bit_equal_vs_ref(width):
    case = _case(jax.random.PRNGKey(0), B=2, W=12, K=2, G=2, hd=8, width=width)
    out, ref = _both(case, width)
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("K,G", [(1, 1), (1, 4), (2, 2), (4, 1)])
def test_gqa_groupings(K, G):
    """MHA (G=1), MQA (K=1) and grouped layouts all hit the same math."""
    case = _case(jax.random.PRNGKey(1), B=2, W=9, K=K, G=G, hd=4, width=8)
    out, ref = _both(case, 8)
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("W", [1, 5, 17, 33, 130])
def test_unaligned_window_lengths(W):
    case = _case(jax.random.PRNGKey(2), B=2, W=W, K=2, G=2, hd=4, width=16,
                 n_valid=max(1, W - 2))
    out, ref = _both(case, 16)
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("width", [8, None], ids=["int8", "f32"])
def test_per_slot_position_masks(width):
    """Scattered empty slots + per-row query positions mask exactly."""
    case = _case(jax.random.PRNGKey(3), B=3, W=15, K=2, G=2, hd=4,
                 width=width, holes=True)
    out, ref = _both(case, width)
    np.testing.assert_array_equal(out, ref)
    assert np.all(np.isfinite(out))


def test_sliding_window_mask():
    case = _case(jax.random.PRNGKey(4), B=2, W=16, K=2, G=2, hd=4, width=8)
    for window in (1, 4, 7):
        out, ref = _both(case, 8, window=window)
        np.testing.assert_array_equal(out, ref)


# ---------------------------------------------------------------------------
# split-K path (the compiled-TPU grid, run in interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("width", [8, 16, None], ids=["int8", "int16", "f32"])
@pytest.mark.parametrize("block_w", [4, 5, 16])
def test_split_k_matches_ref(width, block_w):
    """Forced split sizes (aligned, unaligned, > valid range) reproduce the
    composite through the partial max/denominator/numerator combine."""
    case = _case(jax.random.PRNGKey(5), B=2, W=13, K=2, G=2, hd=8,
                 width=width, n_valid=11)
    out, ref = _both(case, width, block_w=block_w)
    np.testing.assert_allclose(out, ref, rtol=2e-6, atol=2e-6)


def test_split_k_fully_masked_block():
    """A split whose every slot is empty/future must contribute exactly 0
    (no NaN from the -inf running max, no probability leak)."""
    case = _case(jax.random.PRNGKey(6), B=2, W=12, K=1, G=2, hd=4, width=8,
                 n_valid=3)   # splits 2 and 3 all empty
    out, ref = _both(case, 8, block_w=3)
    np.testing.assert_allclose(out, ref, rtol=2e-6, atol=2e-6)
    assert np.all(np.isfinite(out))


@pytest.mark.parametrize("kernel", ["decode", "prefill", "decode_paged",
                                    "prefill_paged"])
def test_split_kernels_run_dots_at_full_precision(
        kernel, assert_dots_full_precision):
    """The compiled-path kernel bodies keep f32 products: one bf16 MXU
    pass would be off by ~2e-3 of the output on a TPU."""
    from repro.kernels.attn import ops as A
    B, K, G, hd, W, C, P, nb = 2, 2, 2, 8, 24, 4, 8, 3
    z = jnp.zeros
    q, qc = z((B, K, G, hd)), z((B, C, K, G, hd))
    new = z((B, C, K, hd))
    e = z((B,))
    idx = jnp.zeros((B,), jnp.int32)
    kw = dict(width=8, scale=0.5, interpret=True)
    pool, pages = z((B, W, K, hd), jnp.int8), z((1 + B * nb, P, K, hd),
                                                jnp.int8)
    bt = jnp.ones((B, nb), jnp.int32)
    pos, ppos = z((B, W), jnp.int32), z((B, nb * P), jnp.int32)
    pe = z((1 + B * nb,))
    fn = {
        "decode": lambda: A.flash_decode(q, pool, pool, pos, idx, e, e,
                                         block_w=8, **kw),
        "prefill": lambda: A.flash_prefill(qc, new, new, pool, pool, pos,
                                           idx, idx + C, e, e, block_w=8,
                                           **kw),
        "decode_paged": lambda: A.flash_decode_paged(
            q, pages, pages, bt, ppos, idx, pe, pe, force_split=True, **kw),
        "prefill_paged": lambda: A.flash_prefill_paged(
            qc, new, new, pages, pages, bt, ppos, idx, idx + C, pe, pe,
            force_split=True, **kw),
    }[kernel]
    assert_dots_full_precision(fn)


# ---------------------------------------------------------------------------
# dispatch: split selection + persisted autotune table
# ---------------------------------------------------------------------------

def test_attn_blocks_interpret_is_whole_window():
    assert dispatch.attn_blocks_for(300, 8, 4, 64, width=8,
                                    interpret=True) == 300


def test_autotune_persistence_roundtrip(tmp_path):
    """Measured entries survive save → reset → load; heuristics don't."""
    path = str(tmp_path / "autotune.json")
    saved_cache = dict(dispatch._BLOCK_CACHE)
    saved_meas = set(dispatch._MEASURED)
    try:
        dispatch.reset_autotune()
        dispatch._BLOCK_CACHE[("nn", 256, 256, 512)] = (128, 128, 256)
        dispatch._BLOCK_CACHE[("attn", 4096, 8, 4, 64, 8)] = (512,)
        dispatch._MEASURED.update(dispatch._BLOCK_CACHE)
        dispatch._BLOCK_CACHE[("nt", 64, 64, 64)] = (64, 64, 64)  # heuristic
        assert dispatch.save_autotune(path) == path
        dispatch.reset_autotune()
        assert dispatch.load_autotune(path) == 2
        assert dispatch._BLOCK_CACHE[("nn", 256, 256, 512)] == (128, 128, 256)
        assert ("nt", 64, 64, 64) not in dispatch._BLOCK_CACHE
        # loaded measurement short-circuits blocks_for without re-measuring
        assert dispatch.blocks_for("nn", 200, 200, 500,
                                   interpret=False) == (128, 128, 256)
        # and the attn bucket resolves to the persisted split
        dispatch.set_autotune(measure=False)
        assert dispatch.attn_blocks_for(4000, 8, 4, 64, width=8,
                                        interpret=False) == 512
    finally:
        dispatch.reset_autotune()
        dispatch.set_autotune(measure=True)
        dispatch._BLOCK_CACHE.update(saved_cache)
        dispatch._MEASURED.update(saved_meas)


def test_autotune_load_missing_or_corrupt(tmp_path):
    assert dispatch.load_autotune(str(tmp_path / "nope.json")) == 0
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert dispatch.load_autotune(str(bad)) == 0
    bad.write_text("[1, 2, 3]")            # valid JSON, wrong shape
    assert dispatch.load_autotune(str(bad)) == 0


def test_autotune_save_merges_and_load_validates(tmp_path):
    """Successive processes contribute different buckets without
    clobbering, and semantically-invalid persisted entries are skipped
    (a trusted-forever bad entry would break every call in its bucket)."""
    import json
    path = str(tmp_path / "autotune.json")
    saved_cache = dict(dispatch._BLOCK_CACHE)
    saved_meas = set(dispatch._MEASURED)
    try:
        dispatch.reset_autotune()          # "process A" measures one bucket
        dispatch._BLOCK_CACHE[("nn", 256, 256, 512)] = (128, 128, 256)
        dispatch._MEASURED.add(("nn", 256, 256, 512))
        dispatch.save_autotune(path)
        dispatch.reset_autotune()          # "process B" measures another
        dispatch._BLOCK_CACHE[("attn", 4096, 8, 4, 64, 8)] = (512,)
        dispatch._MEASURED.add(("attn", 4096, 8, 4, 64, 8))
        dispatch.save_autotune(path)
        dispatch.reset_autotune()
        assert dispatch.load_autotune(path) == 2   # both survived
        # zero blocks / over-budget split / wrong arity / unknown kind
        json.dump({"nn|256|256|512": [0, 0, 0],
                   "attn|4096|8|4|64|8": [1 << 20],
                   "nt|64|64": [64, 64, 64],
                   "bogus|1": [1]}, open(path, "w"))
        dispatch.reset_autotune()
        assert dispatch.load_autotune(path) == 0
    finally:
        dispatch.reset_autotune()
        dispatch._BLOCK_CACHE.update(saved_cache)
        dispatch._MEASURED.update(saved_meas)


# ---------------------------------------------------------------------------
# serve-level: --fused-decode is invisible in the token stream
# ---------------------------------------------------------------------------

POL = PrecisionPolicy("float32")
POL_FUSED = PrecisionPolicy("float32", fused_decode=True)


@pytest.fixture(scope="module")
def model():
    cfg = configs.get_smoke("llama3_8b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def prompts(model):
    cfg, _ = model
    return [np.asarray(jax.random.randint(jax.random.PRNGKey(1), (n,), 0,
                                          cfg.vocab_size))
            for n in (8, 5)]


def _serve(cfg, params, prompts, policy, bits, max_new=6):
    eng = ServeEngine(cfg, policy, params, max_slots=2, max_len=24,
                      options=EngineOptions(cache_bits=bits))
    uids = [eng.submit(p, max_new=max_new) for p in prompts]
    out = eng.run()
    return [out[u] for u in uids], eng


@pytest.mark.parametrize("bits", [8, 16, 0], ids=["int8", "int16", "f32"])
def test_fused_decode_tokens_match_unfused(model, prompts, bits):
    """Mixed-length greedy decodes are token-for-token identical with
    --fused-decode on, for packed AND raw pools."""
    cfg, params = model
    ref, _ = _serve(cfg, params, prompts, POL, bits)
    got, eng = _serve(cfg, params, prompts, POL_FUSED, bits)
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(g, r)
    if bits:
        assert eng.codec.fused_decode
        assert eng.cache_stats()["cache_appends_quantized"] > 0


def test_fused_decode_never_calls_codec_load(model, prompts, monkeypatch):
    """Acceptance: no f32 K/V materialization on the fused hot path —
    decode must succeed with ``PackedKVCodec.load`` booby-trapped."""
    cfg, params = model

    def boom(self, entry):
        raise AssertionError("codec.load materialized f32 K/V on the "
                             "fused decode path")

    monkeypatch.setattr(PackedKVCodec, "load", boom)
    got, _ = _serve(cfg, params, prompts, POL_FUSED, 8, max_new=4)
    assert [len(g) for g in got] == [4, 4]
    with pytest.raises(Exception):   # and the trap itself is live
        _serve(cfg, params, prompts, POL, 8, max_new=2)


def test_fused_decode_windowed_arch():
    """Local (sliding-window) attention layers engage the kernel's window
    mask: gemma3-style 5:1 local:global smoke decodes identically."""
    cfg = configs.get_smoke("gemma3_27b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    prompts = [np.asarray(jax.random.randint(jax.random.PRNGKey(2), (6,), 0,
                                             cfg.vocab_size))]
    ref, _ = _serve(cfg, params, prompts, POL, 8, max_new=5)
    got, _ = _serve(cfg, params, prompts, POL_FUSED, 8, max_new=5)
    np.testing.assert_array_equal(got[0], ref[0])


def test_fused_decode_stochastic_cache(model, prompts):
    """Gupta-2015 stochastic appends draw identical streams under the
    fused path (append is untouched; only the attend changed)."""
    cfg, params = model
    outs = []
    for pol in (POL, POL_FUSED):
        eng = ServeEngine(cfg, pol, params, max_slots=2, max_len=24,
                          options=EngineOptions(
                              cache_bits=8,
                              cache_cfg=CacheQuantConfig(width=8,
                                                         stochastic=True),
                              seed=7))
        uids = [eng.submit(p, max_new=5) for p in prompts]
        out = eng.run()
        outs.append([out[u] for u in uids])
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)
