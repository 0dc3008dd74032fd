"""repro.serve: equivalence, continuous batching, sampler, packed pool."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.policy import PrecisionPolicy
from repro.launch.serve import Engine as LockstepEngine
from repro.models import transformer as T
from repro.serve import (
    CacheQuantConfig,
    EngineOptions,
    PackedKVCodec,
    SamplerConfig,
    ServeEngine,
    sample,
)

POL = PrecisionPolicy("float32")


@pytest.fixture(scope="module")
def model():
    cfg = configs.get_smoke("llama3_8b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def prompts(model):
    cfg, _ = model
    return np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                         cfg.vocab_size))


@pytest.fixture(scope="module")
def f32_eng(model):
    """One greedy f32 engine reused across waves (jits compile once)."""
    cfg, params = model
    return ServeEngine(cfg, POL, params, max_slots=2, max_len=24)


def _wave(eng, reqs):
    """``reqs``: [(prompt, max_new)]. Returns outputs in submit order."""
    uids = [eng.submit(p, max_new=m) for p, m in reqs]
    out = eng.run()
    return [out[u] for u in uids], uids


# ---------------------------------------------------------------------------
# acceptance: equivalences
# ---------------------------------------------------------------------------

def test_f32_engine_matches_lockstep_bitwise(model, prompts, f32_eng):
    """Equal-length prompts: serve engine == lockstep greedy, bit-for-bit."""
    cfg, params = model
    ref = np.asarray(LockstepEngine(cfg, POL, params, max_len=24)
                     .generate(jnp.asarray(prompts), max_new=6))
    out, _ = _wave(f32_eng, [(p, 6) for p in prompts])
    np.testing.assert_array_equal(np.stack(out), ref)


def test_packed_cache_matches_f32_greedy(model, prompts, f32_eng,
                                        assert_near_f32_greedy):
    """Packed-pool greedy over 8 steps: int16 == f32-pool greedy exactly;
    int8 picks, at every step, a token within the stated logit tolerance
    of the float32 model's best (its rounding may fork a near-tie)."""
    cfg, params = model
    ref, _ = _wave(f32_eng, [(p, 8) for p in prompts])
    for bits in (8, 16):
        eng = ServeEngine(cfg, POL, params, max_slots=2, max_len=24,
                          options=EngineOptions(cache_bits=bits))
        out, _ = _wave(eng, [(p, 8) for p in prompts])
        for p, o, r in zip(prompts, out, ref):
            if bits == 16:
                np.testing.assert_array_equal(o, r)
            assert_near_f32_greedy(cfg, params, p, o)
        # every decode append on both slots was quantized and accounted
        assert eng.cache_stats()["cache_appends_quantized"] > 0


def test_queued_request_admitted_into_freed_slot(prompts, f32_eng):
    """2 slots, 3 requests: the queued one decodes mid-stream in a freed
    slot and reproduces its run-alone tokens exactly."""
    short = prompts[0][:5]
    out, (u0, u1, u2) = _wave(f32_eng, [(prompts[0], 3), (prompts[1], 8),
                                        (short, 5)])
    assert [len(o) for o in out] == [3, 8, 5]
    # the queued request was admitted mid-decode: after the first slot
    # freed, before the long request finished
    tr = f32_eng.metrics.traces
    assert tr[u2].t_admit > tr[u0].t_finish
    assert tr[u2].t_first < tr[u1].t_finish

    solo, _ = _wave(f32_eng, [(short, 5)])
    np.testing.assert_array_equal(out[2], solo[0])


def test_slot_reuse_many_waves(prompts, f32_eng):
    """More requests than slots, differing budgets: all finish and match
    their solo decodes (slot state fully recycled between occupants)."""
    reqs = [(prompts[0], 4), (prompts[1], 6), (prompts[0][:5], 3),
            (prompts[1][:5], 5), (prompts[0], 2)]
    out, _ = _wave(f32_eng, reqs)
    assert [len(o) for o in out] == [m for _, m in reqs]
    for got, req in zip(out, reqs):
        solo, _ = _wave(f32_eng, [req])
        np.testing.assert_array_equal(got, solo[0])


def test_submit_validation(model):
    cfg, params = model
    eng = ServeEngine(cfg, POL, params, max_slots=1, max_len=8)
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit(np.zeros(5, np.int32), max_new=4)
    with pytest.raises(ValueError, match="empty"):
        eng.submit(np.zeros(0, np.int32), max_new=1)
    with pytest.raises(ValueError, match="max_new"):
        eng.submit(np.zeros(2, np.int32), max_new=0)


def test_moe_request_independent_of_batchmates():
    """MoE prefill capacity couples a batch's routing: the engine must
    admit MoE requests one per prefill so solo == shared exactly."""
    cfg = configs.get_smoke("granite_moe_1b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 6),
                                            0, cfg.vocab_size))
    eng = ServeEngine(cfg, POL, params, max_slots=2, max_len=16)
    assert eng._admit_group_cap == 1
    shared, _ = _wave(eng, [(p, 4) for p in prompts])
    solo, _ = _wave(eng, [(prompts[0], 4)])
    np.testing.assert_array_equal(shared[0], solo[0])


# ---------------------------------------------------------------------------
# sampler
# ---------------------------------------------------------------------------

def _keys(n, base=0):
    return jnp.stack([jax.random.PRNGKey(base + i) for i in range(n)])


def test_sampler_greedy_is_argmax():
    logits = jnp.asarray(np.random.RandomState(0).randn(3, 17), jnp.float32)
    toks = sample(logits, _keys(3), SamplerConfig("greedy"))
    np.testing.assert_array_equal(np.asarray(toks),
                                  np.argmax(np.asarray(logits), -1))


def test_sampler_top_k_stays_in_top_k():
    logits = jnp.asarray(np.random.RandomState(1).randn(4, 50), jnp.float32)
    cfg = SamplerConfig("top_k", temperature=1.5, top_k=5)
    top5 = np.argsort(-np.asarray(logits), -1)[:, :5]
    for s in range(20):
        toks = np.asarray(sample(logits, _keys(4, base=4 * s), cfg))
        for b in range(4):
            assert toks[b] in top5[b]


def test_stochastic_sampling_solo_equals_batched(model, prompts):
    """Per-request PRNG streams: a top-k request draws the same tokens
    alone as when batched with another request (stochastic cache too)."""
    cfg, params = model
    kw = dict(max_slots=2, max_len=24, options=EngineOptions(
        cache_bits=8,
        cache_cfg=CacheQuantConfig(width=8, stochastic=True),
        sampler_cfg=SamplerConfig("top_k", temperature=0.9, top_k=8),
        seed=7))
    a = ServeEngine(cfg, POL, params, **kw)
    batched, _ = _wave(a, [(p, 4) for p in prompts])
    b = ServeEngine(cfg, POL, params, **kw)
    solo, _ = _wave(b, [(prompts[0], 4)])
    np.testing.assert_array_equal(batched[0], solo[0])


# ---------------------------------------------------------------------------
# packed pool mechanics (no model)
# ---------------------------------------------------------------------------

def _raw_entry(key, n=2, g=1, w=6, k=2, hd=4, n_valid=4, scale=1.0):
    kk, kv = jax.random.split(key)
    pos = jnp.where(jnp.arange(w) < n_valid, jnp.arange(w), -1)
    return {"k": jax.random.normal(kk, (n, g, w, k, hd)) * scale,
            "v": jax.random.normal(kv, (n, g, w, k, hd)) * scale,
            "pos": jnp.broadcast_to(pos, (n, g, w)).astype(jnp.int32)}


def test_pack_entry_roundtrip_accuracy():
    codec = PackedKVCodec(CacheQuantConfig(width=8))
    raw = _raw_entry(jax.random.PRNGKey(2))
    entry = codec.pack_entry(raw)
    k, v, pos = codec.load(entry)
    np.testing.assert_array_equal(np.asarray(pos), np.asarray(raw["pos"]))
    step = 2.0 ** np.asarray(entry["k_e"])[..., None, None, None]
    valid = (np.asarray(raw["pos"]) >= 0)[..., None, None]
    err = np.abs(np.asarray(k) - np.asarray(raw["k"])) * valid
    assert np.all(err <= step / 2 + 1e-7)


def test_controller_adapts_slot_exponent_on_append_overflow():
    """Appends far beyond the calibrated range overflow until the per-slot
    controller raises the exponent; stored mantissas rescale in place."""
    qcfg = CacheQuantConfig(width=8, update_interval=3)
    codec = PackedKVCodec(qcfg)
    raw = _raw_entry(jax.random.PRNGKey(3), w=8, n_valid=2, scale=0.1)
    # strip the layer dim as the layer scan does
    entry = jax.tree_util.tree_map(lambda x: x[0], codec.pack_entry(raw))
    e0 = float(entry["k_e"][0])
    pre = np.asarray(codec.load(entry)[0])[0, 0]    # slot 0, before
    k_big = jnp.full((1, 2, 4), 30.0)               # >> qmax * 2**e0
    v_new = jnp.zeros((1, 2, 4))
    for i in range(2 * qcfg.update_interval):       # slots 2..7: 0 untouched
        entry = codec.append(entry, k_big, v_new,
                             jnp.asarray([2 + i], jnp.int32))
    e1 = float(entry["k_e"][0])
    assert e1 > e0                                  # paper rule: scale x2
    assert float(entry["tot_k"][0, 0]) > 0          # overflows were counted
    # the untouched slot's values survived the rescale within the new step
    now = np.asarray(codec.load(entry)[0])[0, 0]
    assert np.all(np.abs(now - pre) <= 2.0 ** e1 + 1e-7)


def test_stochastic_append_diverges_then_reproduces():
    """Stochastic appends draw from the entry's own key chain: two equal
    entries produce identical appends, a reseeded one differs."""
    qcfg = CacheQuantConfig(width=8, stochastic=True)
    codec = PackedKVCodec(qcfg)
    raw = _raw_entry(jax.random.PRNGKey(4))
    keys = jnp.stack([jax.random.PRNGKey(11)])
    e1 = jax.tree_util.tree_map(lambda x: x[0],
                                codec.pack_entry(raw, slot_keys=keys))
    e2 = jax.tree_util.tree_map(lambda x: x[0],
                                codec.pack_entry(raw, slot_keys=keys))
    k_new = jax.random.normal(jax.random.PRNGKey(5), (1, 2, 4)) * 0.3
    a = codec.append(dict(e1), k_new, k_new, jnp.asarray([4], jnp.int32))
    b = codec.append(dict(e2), k_new, k_new, jnp.asarray([4], jnp.int32))
    np.testing.assert_array_equal(np.asarray(a["k_m"]), np.asarray(b["k_m"]))
    keys3 = jnp.stack([jax.random.PRNGKey(12)])
    e3 = jax.tree_util.tree_map(lambda x: x[0],
                                codec.pack_entry(raw, slot_keys=keys3))
    c = codec.append(dict(e3), k_new, k_new, jnp.asarray([4], jnp.int32))
    assert not np.array_equal(np.asarray(a["k_m"]), np.asarray(c["k_m"]))


def test_f32_pool_is_init_cache(model):
    from repro.serve import make_pool
    cfg, _ = model
    a = make_pool(cfg, 2, 16, None)
    b = T.init_cache(cfg, 2, 16)
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x),
                                                   np.asarray(y)), a, b)
