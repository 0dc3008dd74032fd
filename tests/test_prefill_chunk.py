"""Chunked prefill: kernel bit-equality, codec chunk-append, scheduler."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.packed import container_dtype, qrange
from repro.core.policy import PrecisionPolicy
from repro.kernels import dispatch
from repro.kernels.attn import ref as R
from repro.kernels.attn.ops import flash_prefill
from repro.launch.serve import Engine as LockstepEngine
from repro.models import transformer as T
from repro.serve import (
    CacheQuantConfig,
    EngineOptions,
    PackedKVCodec,
    SamplerConfig,
    ServeEngine,
)

POL = PrecisionPolicy("float32")


def _case(key, B, C, W, K, G, hd, width, n_valid=None, p0v=6, holes=False):
    """Random flash-prefill operands in the codec entry layout."""
    ks = jax.random.split(key, 8)
    q = jax.random.normal(ks[0], (B, C, K, G, hd), jnp.float32)
    kn = jax.random.normal(ks[5], (B, C, K, hd), jnp.float32)
    vn = jax.random.normal(ks[6], (B, C, K, hd), jnp.float32)
    if width is None:
        k = jax.random.normal(ks[1], (B, W, K, hd), jnp.float32)
        v = jax.random.normal(ks[2], (B, W, K, hd), jnp.float32)
        ke = ve = None
    else:
        qmax, qmin = qrange(width)
        dt = container_dtype(width)
        k = jax.random.randint(ks[1], (B, W, K, hd), int(qmin),
                               int(qmax) + 1).astype(dt)
        v = jax.random.randint(ks[2], (B, W, K, hd), int(qmin),
                               int(qmax) + 1).astype(dt)
        ke = jax.random.randint(ks[3], (B,), -8, -2).astype(jnp.float32)
        ve = jax.random.randint(ks[4], (B,), -8, -2).astype(jnp.float32)
    pos = jnp.where(jnp.arange(W) < p0v, jnp.arange(W), -1)
    pos = jnp.broadcast_to(pos, (B, W)).astype(jnp.int32)
    if holes:
        gap = jax.random.bernoulli(ks[7], 0.3, (B, W))
        pos = jnp.where(gap, -1, pos)
    p0 = jnp.full((B,), p0v, jnp.int32)
    nv = jnp.full((B,), n_valid if n_valid is not None else C, jnp.int32)
    return q, kn, vn, k, v, pos, p0, nv, ke, ve


def _both(case, width, scale=0.25, window=None, block_w=None):
    q, kn, vn, k, v, pos, p0, nv, ke, ve = case
    out = flash_prefill(q, kn, vn, k, v, pos, p0, nv, ke, ve, width=width,
                        scale=scale, window=window, block_w=block_w,
                        interpret=True)
    # the ref is jitted: the interpret kernel body and the model's inline
    # composite both run under jit, and unjitted XLA dispatch may pick a
    # different (1-ULP-off) contraction for degenerate chunk shapes
    reff = jax.jit(functools.partial(R.prefill_attention_ref, width=width,
                                     scale=scale, window=window))
    ref = reff(q, k, v, pos, kn, vn, p0, nv, k_exp=ke, v_exp=ve)
    return np.asarray(out), np.asarray(ref)


# ---------------------------------------------------------------------------
# acceptance: interpret-mode bit-equality vs the chunked ref composite
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("width", [8, 16, None], ids=["int8", "int16", "f32"])
def test_bit_equal_vs_chunk_ref(width):
    case = _case(jax.random.PRNGKey(0), B=2, C=4, W=12, K=2, G=2, hd=8,
                 width=width)
    out, ref = _both(case, width)
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("width", [8, None], ids=["int8", "f32"])
def test_ragged_tail_and_holes(width):
    """Ragged final chunks (n_valid < C) and scattered empty history slots
    mask exactly; garbage rows stay finite."""
    case = _case(jax.random.PRNGKey(1), B=3, C=5, W=15, K=2, G=2, hd=4,
                 width=width, n_valid=3, holes=True)
    out, ref = _both(case, width)
    np.testing.assert_array_equal(out, ref)
    assert np.all(np.isfinite(out))


def test_sliding_window_spans_history_and_chunk():
    case = _case(jax.random.PRNGKey(2), B=2, C=6, W=16, K=2, G=2, hd=4,
                 width=8)
    for window in (1, 3, 8):
        out, ref = _both(case, 8, window=window)
        np.testing.assert_array_equal(out, ref)


def test_admission_chunk_empty_history():
    """p0 == 0: every history lane is masked; only the self block scores."""
    case = _case(jax.random.PRNGKey(3), B=2, C=4, W=10, K=1, G=2, hd=4,
                 width=8, p0v=0)
    out, ref = _both(case, 8)
    np.testing.assert_array_equal(out, ref)
    assert np.all(np.isfinite(out))


@pytest.mark.parametrize("width", [8, 16, None], ids=["int8", "int16", "f32"])
@pytest.mark.parametrize("block_w", [3, 5, 16])
def test_split_k_matches_ref(width, block_w):
    """Forced history splits (aligned, unaligned, >W) reproduce the joint
    flash combine across history splits + the final self block."""
    case = _case(jax.random.PRNGKey(4), B=2, C=4, W=13, K=2, G=2, hd=8,
                 width=width, p0v=11)
    out, ref = _both(case, width, block_w=block_w)
    np.testing.assert_allclose(out, ref, rtol=2e-6, atol=2e-6)


def test_split_k_fully_masked_history():
    """All-empty history splits (p0 == 0) contribute exactly 0 through the
    running-max combine — no NaN, the self block alone decides."""
    case = _case(jax.random.PRNGKey(5), B=2, C=3, W=12, K=1, G=2, hd=4,
                 width=8, p0v=0)
    out, ref = _both(case, 8, block_w=4)
    np.testing.assert_allclose(out, ref, rtol=2e-6, atol=2e-6)
    assert np.all(np.isfinite(out))


# ---------------------------------------------------------------------------
# dispatch: prefill buckets share the persisted autotune table
# ---------------------------------------------------------------------------

def test_prefill_blocks_interpret_is_whole_window():
    assert dispatch.prefill_blocks_for(300, 8, 8, 4, 64, width=8,
                                       interpret=True) == 300


def test_prefill_bucket_persistence_roundtrip(tmp_path):
    path = str(tmp_path / "autotune.json")
    saved_cache = dict(dispatch._BLOCK_CACHE)
    saved_meas = set(dispatch._MEASURED)
    try:
        dispatch.reset_autotune()
        dispatch._BLOCK_CACHE[("prefill", 64, 8, 4, 64, 8)] = (512,)
        dispatch._MEASURED.add(("prefill", 64, 8, 4, 64, 8))
        assert dispatch.save_autotune(path) == path
        dispatch.reset_autotune()
        assert dispatch.load_autotune(path) == 1
        dispatch.set_autotune(measure=False)
        assert dispatch.prefill_blocks_for(4000, 64, 8, 4, 64, width=8,
                                           interpret=False) == 512
        # semantic validation: an over-VMEM split is rejected on load
        import json
        json.dump({"prefill|64|8|4|64|8": [1 << 20]}, open(path, "w"))
        dispatch.reset_autotune()
        assert dispatch.load_autotune(path) == 0
    finally:
        dispatch.reset_autotune()
        dispatch.set_autotune(measure=True)
        dispatch._BLOCK_CACHE.update(saved_cache)
        dispatch._MEASURED.update(saved_meas)


# ---------------------------------------------------------------------------
# codec: chunk append == per-token appends; masking; admission reset
# ---------------------------------------------------------------------------

def _packed_entry(key, B=2, W=10, K=2, hd=4, width=8, n_valid=4):
    """A calibrated packed entry (layer dim stripped) with n_valid slots."""
    codec = PackedKVCodec(CacheQuantConfig(width=width))
    kk, kv = jax.random.split(key)
    pos = jnp.where(jnp.arange(W) < n_valid, jnp.arange(W), -1)
    raw = {"k": jax.random.normal(kk, (1, B, W, K, hd)),
           "v": jax.random.normal(kv, (1, B, W, K, hd)),
           "pos": jnp.broadcast_to(pos, (1, B, W)).astype(jnp.int32)}
    return codec, jax.tree_util.tree_map(lambda x: x[0],
                                         codec.pack_entry(raw))


def test_append_chunk_equals_token_appends():
    """A C-token chunk write lands the same mantissas/positions/stats as C
    sequential per-token appends (below the controller interval)."""
    codec, entry = _packed_entry(jax.random.PRNGKey(0))
    C = 3
    k_new = jax.random.normal(jax.random.PRNGKey(1), (2, C, 2, 4)) * 0.3
    v_new = jax.random.normal(jax.random.PRNGKey(2), (2, C, 2, 4)) * 0.3
    p0 = jnp.full((2,), 4, jnp.int32)
    chunked = codec.append_chunk(dict(entry), k_new, v_new, p0,
                                 jnp.full((2,), C, jnp.int32))
    stepped = dict(entry)
    for i in range(C):
        stepped = codec.append(stepped, k_new[:, i], v_new[:, i], p0 + i)
    for f in ("k_m", "v_m", "pos", "k_e", "v_e", "n_app", "acc_k", "acc_v",
              "tot_k", "tot_v"):
        np.testing.assert_array_equal(np.asarray(chunked[f]),
                                      np.asarray(stepped[f]), err_msg=f)


def test_append_chunk_ragged_rows_dropped():
    codec, entry = _packed_entry(jax.random.PRNGKey(3))
    C, nv = 4, 2
    k_new = jax.random.normal(jax.random.PRNGKey(4), (2, C, 2, 4)) * 0.3
    p0 = jnp.full((2,), 4, jnp.int32)
    out = codec.append_chunk(dict(entry), k_new, k_new, p0,
                             jnp.full((2,), nv, jnp.int32))
    pos = np.asarray(out["pos"])
    assert np.all(pos[:, 4:6] == [4, 5])       # valid rows written
    assert np.all(pos[:, 6:] == -1)            # ragged tail dropped
    assert np.all(np.asarray(out["n_app"]) == nv)


def test_admission_chunk_resets_recycled_slot():
    """p0 == 0 behaves like pack_entry: stale ring positions vanish,
    exponents recalibrate from the chunk, counters restart."""
    codec, entry = _packed_entry(jax.random.PRNGKey(5), n_valid=9)
    entry = dict(entry)
    entry["n_app"] = entry["n_app"] + 7.0          # stale occupant state
    big = jax.random.normal(jax.random.PRNGKey(6), (2, 3, 2, 4)) * 40.0
    out = codec.append_chunk(entry, big, big, jnp.zeros((2,), jnp.int32),
                             jnp.full((2,), 3, jnp.int32))
    pos = np.asarray(out["pos"])
    assert np.all(pos[:, :3] == [0, 1, 2])
    assert np.all(pos[:, 3:] == -1)                # previous occupant gone
    assert np.all(np.asarray(out["n_app"]) == 0.0)
    assert np.all(np.asarray(out["tot_k"]) == 0.0)
    # exponents refit the chunk's magnitude (40 >> the stale calibration)
    step = 2.0 ** np.asarray(out["k_e"])
    assert np.all(step * 127 >= 40.0)
    km = np.asarray(out["k_m"][:, :3], np.float32)
    err = np.abs(km * step[:, None, None, None] - np.asarray(big))
    assert np.all(err <= step[:, None, None, None] / 2 + 1e-6)


def test_masked_append_leaves_rows_untouched():
    """mask=False rows keep every field bit-identical (no write, no stats,
    no counter, no controller) while mask=True rows match the unmasked
    append — the invariant that keeps mid-prefill slots solo-exact."""
    codec, entry = _packed_entry(jax.random.PRNGKey(7))
    k_new = jax.random.normal(jax.random.PRNGKey(8), (2, 2, 4)) * 0.3
    pos = jnp.full((2,), 4, jnp.int32)
    mask = jnp.asarray([True, False])
    out = codec.append(dict(entry), k_new, k_new, pos, mask=mask)
    ref = codec.append(dict(entry), k_new, k_new, pos)
    for f in ("k_m", "v_m", "pos", "k_e", "v_e", "n_app", "acc_k", "tot_k"):
        np.testing.assert_array_equal(np.asarray(out[f])[0],
                                      np.asarray(ref[f])[0], err_msg=f)
        np.testing.assert_array_equal(np.asarray(out[f])[1],
                                      np.asarray(entry[f])[1], err_msg=f)


# ---------------------------------------------------------------------------
# scheduler: chunked == whole-prompt, one jit, immediate admission
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    cfg = configs.get_smoke("llama3_8b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def prompts(model):
    cfg, _ = model
    return [np.asarray(jax.random.randint(jax.random.PRNGKey(100 + i),
                                          (n,), 0, cfg.vocab_size))
            for i, n in enumerate((5, 9, 13))]


def _drive(cfg, params, prompts, *, bits, chunk, fused=False, max_new=6,
           slots=2, max_len=32):
    pol = PrecisionPolicy("float32", fused_decode=fused,
                          prefill_chunk=chunk)
    eng = ServeEngine(cfg, pol, params, max_slots=slots, max_len=max_len,
                      options=EngineOptions(cache_bits=bits))
    uids = [eng.submit(p, max_new=max_new) for p in prompts]
    out = eng.run()
    return [out[u] for u in uids], eng


def _match(cfg, params, prompts, got, ref, bits, near_f32):
    """Chunked vs whole-prompt streams: identical on f32/int16 pools.  An
    int8 pool calibrates its slot exponent from the first chunk instead
    of the whole prompt, so its entries round differently: each stream
    is held to the float32 model's logits instead."""
    for p, g, r in zip(prompts, got, ref):
        if bits == 8:
            near_f32(cfg, params, p, g)
            near_f32(cfg, params, p, r)
        else:
            np.testing.assert_array_equal(g, r)


@pytest.mark.parametrize("bits", [0, 8, 16], ids=["f32", "int8", "int16"])
def test_chunked_tokens_match_whole_prompt(model, prompts, bits,
                                           assert_near_f32_greedy):
    """Acceptance: greedy streams agree chunked vs whole-prompt on
    f32/int8/int16 pools — no equal-length partner anywhere."""
    cfg, params = model
    ref, _ = _drive(cfg, params, prompts, bits=bits, chunk=0)
    got, eng = _drive(cfg, params, prompts, bits=bits, chunk=4)
    _match(cfg, params, prompts, got, ref, bits, assert_near_f32_greedy)
    assert eng.prefill_chunk == 4


@pytest.mark.parametrize("bits", [0, 8], ids=["f32", "int8"])
def test_chunked_fused_tokens_match_whole_prompt(model, prompts, bits,
                                                 assert_near_f32_greedy):
    """The flash-prefill kernel path (fused_decode) is invisible in the
    token stream too: fused == unfused chunked exactly."""
    cfg, params = model
    ref, _ = _drive(cfg, params, prompts, bits=bits, chunk=0)
    unfused, _ = _drive(cfg, params, prompts, bits=bits, chunk=4)
    got, _ = _drive(cfg, params, prompts, bits=bits, chunk=4, fused=True)
    for g, u in zip(got, unfused):
        np.testing.assert_array_equal(g, u)
    _match(cfg, params, prompts, got, ref, bits, assert_near_f32_greedy)


def test_one_prefill_jit_across_mixed_lengths(model, prompts):
    """Acceptance: exactly one prefill compilation for a mixed-length
    stream (whole-prompt mode compiles one per (g, L) pair)."""
    cfg, params = model
    _, eng = _drive(cfg, params, prompts, bits=8, chunk=4)
    assert eng._chunk._cache_size() == 1
    assert eng._prefill._cache_size() == 0      # grouped path never ran
    _, eng0 = _drive(cfg, params, prompts, bits=8, chunk=0)
    assert eng0._prefill._cache_size() == len({len(p) for p in prompts})


def test_immediate_admission_without_length_partner(model, prompts):
    """Mixed lengths admit into free slots on the first step — nobody
    waits for an equal-length partner, and TTFT ordering shows the long
    prompt's chunks interleaving with the short request's decode."""
    cfg, params = model
    pol = PrecisionPolicy("float32", prefill_chunk=4)
    eng = ServeEngine(cfg, pol, params, max_slots=2, max_len=32)
    u_short = eng.submit(prompts[0], max_new=2)          # 5 tokens
    u_long = eng.submit(prompts[2], max_new=2)           # 13 tokens
    eng.step()
    tr = eng.metrics.traces
    assert tr[u_short].t_admit is not None
    assert tr[u_long].t_admit is not None                # no partner wait
    eng.run()
    # FIFO chunking: the short prompt (2 chunks) finished prefill and
    # decoded while the long prompt (4 chunks) was still prefilling
    assert tr[u_short].t_first < tr[u_long].t_first
    assert tr[u_long].prefill_chunks == 4
    assert tr[u_short].prefill_chunks == 2
    # and each stream equals its solo run
    solo, _ = _drive(cfg, params, [prompts[2]], bits=0, chunk=4, max_new=2)
    np.testing.assert_array_equal(eng._results[u_long], solo[0])


def test_chunked_admission_into_freed_slot_matches_solo(model, prompts):
    """3 requests, 2 slots: the queued request chunk-prefills into a slot
    freed mid-decode and reproduces its run-alone tokens exactly."""
    cfg, params = model
    reqs = [(prompts[0], 3), (prompts[1], 8), (prompts[0][:5], 5)]
    pol = PrecisionPolicy("float32", prefill_chunk=4)
    eng = ServeEngine(cfg, pol, params, max_slots=2, max_len=24,
                      options=EngineOptions(cache_bits=8))
    uids = [eng.submit(p, max_new=m) for p, m in reqs]
    out = eng.run()
    solo, _ = _drive(cfg, params, [prompts[0][:5]], bits=8, chunk=4,
                     max_new=5, max_len=24)
    np.testing.assert_array_equal(out[uids[2]], solo[0])


def test_chunked_windowed_arch_chunk_larger_than_window():
    """gemma3-style local layers: a chunk larger than the window cap
    (in-chunk ring eviction) still matches whole-prompt exactly."""
    cfg = configs.get_smoke("gemma3_27b")     # window 16
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    prompts = [np.asarray(jax.random.randint(jax.random.PRNGKey(7 + i),
                                             (n,), 0, cfg.vocab_size))
               for i, n in enumerate((6, 21))]
    for fused in (False, True):
        ref, _ = _drive(cfg, params, prompts, bits=8, chunk=0, max_new=5)
        got, _ = _drive(cfg, params, prompts, bits=8, chunk=24,
                        fused=fused, max_new=5)
        for g, r in zip(got, ref):
            np.testing.assert_array_equal(g, r)


def test_chunked_stochastic_topk_solo_equals_batched(model, prompts):
    """Per-request PRNG streams survive chunked admission: stochastic
    cache + top-k sampling draw identical tokens solo vs batched."""
    cfg, params = model
    kw = dict(max_slots=2, max_len=24, options=EngineOptions(
        cache_bits=8,
        cache_cfg=CacheQuantConfig(width=8, stochastic=True),
        sampler_cfg=SamplerConfig("top_k", temperature=0.9, top_k=8),
        seed=7))
    pol = PrecisionPolicy("float32", prefill_chunk=3)
    a = ServeEngine(cfg, pol, params, **kw)
    uids = [a.submit(p, max_new=4) for p in prompts[:2]]
    out = a.run()
    b = ServeEngine(cfg, pol, params, **kw)
    u = b.submit(prompts[0], max_new=4)
    np.testing.assert_array_equal(out[uids[0]], b.run()[u])


def test_chunked_fused_never_calls_codec_load(model, prompts, monkeypatch):
    """Acceptance: no f32 K/V materialization in either direction — a
    chunked + fused engine must survive a booby-trapped codec.load."""
    cfg, params = model

    def boom(self, entry):
        raise AssertionError("codec.load materialized f32 K/V on the "
                             "fused chunked-prefill path")

    monkeypatch.setattr(PackedKVCodec, "load", boom)
    got, _ = _drive(cfg, params, prompts[:2], bits=8, chunk=4, fused=True,
                    max_new=4)
    assert [len(g) for g in got] == [4, 4]
    with pytest.raises(Exception):      # and the trap itself is live
        _drive(cfg, params, prompts[:2], bits=8, chunk=4, max_new=2)


def test_moe_keeps_whole_prompt_carveout():
    """MoE expert capacity couples a prompt's tokens: prefill_chunk is
    ignored and the solo whole-prompt admission path stays in force."""
    cfg = configs.get_smoke("granite_moe_1b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    pol = PrecisionPolicy("float32", prefill_chunk=4)
    eng = ServeEngine(cfg, pol, params, max_slots=2, max_len=16)
    assert eng.prefill_chunk == 0
    assert eng._admit_group_cap == 1


# ---------------------------------------------------------------------------
# ssm ragged-tail fix (submit no longer demands ssm_chunk alignment)
# ---------------------------------------------------------------------------

def test_ssm_ragged_prompt_serves_and_matches_lockstep():
    cfg = configs.get_smoke("mamba2_370m")    # ssm_chunk 16
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (19,), 0,
                                           cfg.vocab_size))
    ref = np.asarray(LockstepEngine(cfg, POL, params, max_len=32)
                     .generate(jnp.asarray(prompt[None]), max_new=5))
    eng = ServeEngine(cfg, POL, params, max_slots=1, max_len=32)
    uid = eng.submit(prompt, max_new=5)       # 19 % 16 != 0: now accepted
    np.testing.assert_array_equal(eng.run()[uid], ref[0])


def test_ssm_ragged_prefill_state_matches_decode_steps():
    """The masked final chunk's cache equals aligned prefill + per-token
    decode over the ragged tail (the state after exactly L real tokens)."""
    from repro.core import ScaleState
    cfg = configs.get_smoke("mamba2_370m")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    gs = T.group_shapes(cfg)
    exps = ScaleState.create(gs, -6.0).exps
    sinks = {n: jnp.zeros(s + (3,), jnp.float32)
             for n, s in gs.items() if n.startswith("g:")}
    toks = jax.random.randint(jax.random.PRNGKey(5), (1, 19), 0,
                              cfg.vocab_size)
    logits_r, _, cache_r = T.prefill(cfg, POL, params, {"tokens": toks},
                                     exps, sinks, max_cache_len=32)
    logits_a, _, cache_a = T.prefill(cfg, POL, params,
                                     {"tokens": toks[:, :16]}, exps, sinks,
                                     max_cache_len=32)
    for i in range(16, 19):
        logits_a, _, cache_a = T.decode_step(cfg, POL, params, cache_a,
                                             toks[:, i], jnp.int32(i),
                                             exps, sinks)
    np.testing.assert_allclose(np.asarray(logits_r), np.asarray(logits_a),
                               rtol=2e-4, atol=2e-5)
    for bkey, e in cache_r["dec"].items():
        for f in e:
            np.testing.assert_allclose(
                np.asarray(e[f]), np.asarray(cache_a["dec"][bkey][f]),
                rtol=2e-4, atol=1e-5, err_msg=f"{bkey}/{f}")
