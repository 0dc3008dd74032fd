"""repro.obs: tracer + validator, metrics registry, numerics timeline,
dispatch profiling, and the zero-cost-when-disabled contract."""
import json
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.policy import PrecisionPolicy
from repro.kernels import dispatch
from repro.models import transformer as T
from repro.obs import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NumericsLog,
    Tracer,
    count_moves,
    read_jsonl,
    serve_records,
    span,
    start_http_server,
    train_records,
    validate_trace,
)
from repro.serve import CacheQuantConfig, EngineOptions, ServeEngine
from repro.serve.metrics import ServeMetrics


# ---------------------------------------------------------------------------
# tracer + Chrome-trace validator
# ---------------------------------------------------------------------------

def _fake_clock():
    t = [0.0]

    def clock():
        return t[0]
    return t, clock


def test_span_nesting_and_export():
    t, clock = _fake_clock()
    tr = Tracer(clock=clock)
    tr.begin("outer", n=1)
    t[0] = 1e-3
    tr.begin("inner")
    t[0] = 2e-3
    tr.end()                      # inner: [1000, 2000) us
    t[0] = 4e-3
    tr.end(extra=7)               # outer: [0, 4000) us
    tr.instant("mark", tid="requests", uid=3)
    tr.counter("queue", {"depth": 2, "active": 1.0})

    obj = tr.to_chrome()
    validate_trace(obj)
    xs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in xs] == ["outer", "inner"]   # parent first
    outer, inner = xs
    assert outer["ts"] == 0.0 and outer["dur"] == pytest.approx(4000.0)
    assert inner["ts"] == pytest.approx(1000.0)
    assert inner["dur"] == pytest.approx(1000.0)
    assert outer["args"] == {"n": 1, "extra": 7}
    mark, = [e for e in obj["traceEvents"] if e["ph"] == "i"]
    assert mark["tid"] == "requests" and mark["s"] == "t"
    ctr, = [e for e in obj["traceEvents"] if e["ph"] == "C"]
    assert ctr["args"] == {"depth": 2.0, "active": 1.0}
    # every track got a thread_name metadata event
    meta_tids = {e["tid"] for e in obj["traceEvents"] if e["ph"] == "M"
                 and e["name"] == "thread_name"}
    assert {"engine", "requests", "counters"} <= meta_tids


def test_export_roundtrip(tmp_path):
    tr = Tracer()
    with span("decode_step", tr, n_active=2):
        tr.instant("submit", tid="requests")
    path = tr.export(str(tmp_path / "t.json"))
    obj = json.load(open(path))
    validate_trace(obj)
    assert tr.span_names() == ["decode_step"]
    assert len(tr.find("submit", "i")) == 1


def test_end_without_begin_raises():
    with pytest.raises(RuntimeError):
        Tracer().end()


def test_unclosed_span_closed_at_export():
    tr = Tracer()
    tr.begin("open_ended")
    obj = tr.to_chrome()
    validate_trace(obj)
    ev, = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    assert ev["args"]["unclosed_at_export"] is True


def _ev(name="e", ph="X", ts=0.0, dur=1.0, tid="t", **kw):
    e = {"name": name, "ph": ph, "ts": ts, "pid": 0, "tid": tid, **kw}
    if ph == "X":
        e.setdefault("dur", dur)
    return e


@pytest.mark.parametrize("bad", [
    [],                                           # not a dict
    {"traceEvents": 3},                           # traceEvents not a list
    {"traceEvents": [{"ph": "X", "ts": 0.0}]},    # no name
    {"traceEvents": [_ev(ph="B")]},               # phase not emitted here
    {"traceEvents": [_ev(dur=None)]},             # X without numeric dur
    {"traceEvents": [_ev(ts=-1.0)]},              # negative ts
    {"traceEvents": [_ev(ph="C", args={})]},      # counter without series
    {"traceEvents": [_ev(ph="C", args={"a": "hi"})]},   # non-numeric
    {"traceEvents": [_ev(ts=5.0), _ev(ts=1.0)]},  # out of ts order
    {"traceEvents": [_ev(ts=0.0, dur=4.0),        # overlap, not nested
                     _ev(ts=2.0, dur=4.0)]},
])
def test_validate_rejects(bad):
    with pytest.raises(ValueError):
        validate_trace(bad)


def test_validate_accepts_nested_and_disjoint():
    validate_trace({"traceEvents": [
        _ev(ts=0.0, dur=10.0), _ev(ts=0.5, dur=100.0, tid="other"),
        _ev(ts=1.0, dur=2.0), _ev(ts=4.0, dur=6.0), _ev(ts=12.0, dur=1.0),
    ]})


# ---------------------------------------------------------------------------
# metrics: counters, gauges, log-bucketed histograms, registry outputs
# ---------------------------------------------------------------------------

def test_counter_and_gauge():
    c = Counter("c")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = Gauge("g")
    g.set(5)
    g.set(2)
    assert g.value == 2 and g.peak == 5


def test_histogram_bucket_edges():
    h = Histogram("h", lo=1.0, n_buckets=3, base=2.0)
    assert h.edges == [1.0, 2.0, 4.0, 8.0]
    # exact power-of-2 edges land in the bucket they open (half-open)
    for v, want in [(0.5, 0), (1.0, 1), (1.999, 1), (2.0, 2), (3.999, 2),
                    (4.0, 3), (7.999, 3), (8.0, 4), (100.0, 4)]:
        before = list(h.counts)
        h.observe(v)
        got = [i for i, (a, b) in enumerate(zip(before, h.counts)) if b > a]
        assert got == [want], f"observe({v}) -> bucket {got}, want {want}"
    assert h.count == 9
    assert h.min == 0.5 and h.max == 100.0
    assert h.sum == pytest.approx(0.5 + 1.0 + 1.999 + 2.0 + 3.999 + 4.0
                                  + 7.999 + 8.0 + 100.0)
    assert h.quantile(0.0) == 0.5
    assert h.quantile(1.0) == 100.0
    assert 1.0 <= h.quantile(0.5) <= 8.0


def test_histogram_rejects_bad_params():
    for kw in ({"lo": 0.0}, {"base": 1.0}, {"n_buckets": 0}):
        with pytest.raises(ValueError):
            Histogram("h", **kw)


def test_registry_get_or_create_and_type_clash():
    r = MetricsRegistry()
    assert r.counter("a") is r.counter("a")
    with pytest.raises(TypeError):
        r.gauge("a")
    assert "a" in r and "b" not in r


def test_registry_snapshot_and_prometheus():
    r = MetricsRegistry()
    r.counter("reqs", "total requests").inc(3)
    r.gauge("depth").set(4)
    h = r.histogram("lat", "latency", lo=1.0, n_buckets=2, base=2.0)
    for v in (0.5, 1.5, 3.0, 9.0):
        h.observe(v)
    snap = r.snapshot()
    assert snap["reqs"] == {"type": "counter", "value": 3}
    assert snap["depth"]["peak"] == 4
    assert snap["lat"]["counts"] == [1, 1, 1, 1]

    text = r.prometheus_text()
    assert "# TYPE reqs counter" in text and "reqs 3" in text
    assert "depth_peak 4" in text
    # cumulative buckets: le=2 covers underflow+bucket1, +Inf == count
    assert 'lat_bucket{le="2"} 2' in text
    assert 'lat_bucket{le="4"} 3' in text
    assert 'lat_bucket{le="+Inf"} 4' in text
    assert "lat_count 4" in text


def test_snapshot_jsonl(tmp_path):
    r = MetricsRegistry()
    r.counter("c").inc()
    p = str(tmp_path / "m.jsonl")
    r.snapshot_jsonl(p, {"step": 1})
    r.snapshot_jsonl(p, {"step": 2})
    recs = read_jsonl(p)
    assert [x["step"] for x in recs] == [1, 2]
    assert recs[0]["metrics"]["c"]["value"] == 1
    assert "t" in recs[0]


def test_http_metrics_endpoint():
    r = MetricsRegistry()
    r.counter("up").inc()
    server = start_http_server(r, port=0)
    try:
        port = server.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        assert "up 1" in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"http://127.0.0.1:{port}/nope",
                                   timeout=5)
    finally:
        server.shutdown()


def test_serve_metrics_summary_schema_and_registry():
    m = ServeMetrics()
    m.on_submit(0, 8)
    m.observe_queue_depth(1)
    m.on_admit(0)
    m.on_decode_step()
    m.on_token(0)
    m.on_decode_step()
    m.on_token(0)
    m.on_finish(0, "ok")
    m.on_submit(1, 4)
    m.on_reject(1)
    s = m.summary(extra={"cache": 1})
    assert set(s) == {
        "requests_submitted", "requests_finished", "requests_rejected",
        "requests_timed_out", "requests_failed", "preemptions",
        "queue_depth_peak", "new_tokens", "decode_steps", "wall_s",
        "tok_per_s", "ttft_mean_s", "ttft_max_s", "queue_wait_mean_s",
        "queue_wait_max_s", "prefill_chunks", "cache"}
    assert s["requests_submitted"] == 2 and s["requests_finished"] == 1
    assert s["requests_rejected"] == 1 and s["new_tokens"] == 2
    assert s["decode_steps"] == 2 and s["queue_depth_peak"] == 1
    assert s["ttft_mean_s"] > 0
    # the same hooks fed the obs registry
    r = m.registry
    assert r.counter("serve_new_tokens").value == 2
    assert r.histogram("serve_ttft_seconds").count == 1
    assert r.histogram("serve_queue_wait_seconds").count == 1
    assert r.histogram("serve_decode_step_seconds").count == 1  # 2 steps
    assert r.histogram("serve_request_tok_per_s").count == 1


# ---------------------------------------------------------------------------
# numerics timeline
# ---------------------------------------------------------------------------

def _snap(k_e, v_e, ovf, tot):
    return {"dec/0:attn": {"k_e": k_e, "v_e": v_e, "ovf": ovf,
                           "half": [[0.0] * len(k_e[0])] * len(k_e),
                           "tot": tot}}


def test_serve_records_first_sample_and_moves():
    cur = _snap([[-4.0, -3.0]], [[-4.0, -4.0]],
                [[2.0, 0.0]], [[10.0, 10.0]])
    first = serve_records(cur, None, step=4, t=0.1, slot_uids={0: 7, 1: 9})
    assert len(first) == 2
    assert first[0]["k_move"] is None and first[0]["uid"] == 7
    assert first[0]["ovf_rate"] == [0.2]

    nxt = _snap([[-3.0, -3.0]], [[-5.0, -4.0]],
                [[2.0, 0.0]], [[20.0, 20.0]])
    recs = serve_records(nxt, cur, step=8, t=0.2, slot_uids={0: 7, 1: 9})
    assert recs[0]["k_move"] == [1]       # exponent grew: scale-up
    assert recs[0]["v_move"] == [-1]      # exponent shrank: scale-down
    assert recs[1]["k_move"] == [0] and recs[1]["v_move"] == [0]
    assert count_moves(recs) == 2
    assert count_moves(first) == 0


def test_serve_records_skips_out_of_range_slots():
    cur = _snap([[-4.0]], [[-4.0]], [[0.0]], [[1.0]])
    recs = serve_records(cur, None, step=1, t=0.0, slot_uids={0: 1, 5: 2})
    assert [r["slot"] for r in recs] == [0]


def test_train_records_aggregates_by_class():
    prev = {"a:h0": [-4.0, -4.0], "w:dense": -6.0}
    new = {"a:h0": [-3.0, -4.0], "w:dense": -7.0}
    acc = {"a:h0": [[3.0, 5.0, 100.0], [0.0, 0.0, 100.0]],
           "w:dense": [0.0, 1.0, 50.0]}
    recs = train_records(prev, new, acc, step=20, t=1.5)
    by_cls = {r["class"]: r for r in recs}
    assert set(by_cls) == {"activation", "weight"}
    act = by_cls["activation"]
    assert act["n_groups"] == 2 and act["moves_up"] == 1
    assert act["moves_down"] == 0
    assert act["ovf_rate"] == pytest.approx(3.0 / 200.0)
    w = by_cls["weight"]
    assert w["moves_down"] == 1 and w["exp_mean"] == -7.0
    assert count_moves(recs) == 2


def test_numerics_log_jsonl_roundtrip(tmp_path):
    p = str(tmp_path / "n.jsonl")
    with NumericsLog(p) as log:
        log.record({"kind": "serve", "step": 1})
        log.record({"kind": "train", "step": 2, "moves_up": 1,
                    "moves_down": 0})
    assert [r["step"] for r in read_jsonl(p)] == [1, 2]
    assert len(log.records) == 2


def test_train_numerics_tap_end_to_end():
    """The jit-side tap feeds train_records with real controller state."""
    from repro.models import maxout as MX
    from repro.optim.opt import OptConfig, sgd_init
    from repro.train import init_train_state, make_train_step

    cfg = MX.MaxoutConfig(hidden=(16, 16), pieces=2)
    gs = MX.group_shapes(cfg)
    policy = PrecisionPolicy("dfxp", update_interval=4)
    params = MX.init_params(cfg, jax.random.PRNGKey(0))
    state = init_train_state(params, sgd_init(params), gs, policy,
                             init_exp=-8.0)

    def loss_fn(p, b, s, exps):
        return MX.loss_fn(cfg, policy, p, b, exps, s,
                          rng=jax.random.PRNGKey(1))

    step = jax.jit(make_train_step(
        loss_fn, gs, policy, OptConfig(kind="sgd", lr=0.1),
        numerics_tap=True))
    from repro.data import SyntheticImages
    data = SyntheticImages()
    log = NumericsLog()
    for i in range(8):
        b = data.batch(i, 32)
        state, m = step(state, {"x": jnp.asarray(b["x"]),
                                "y": jnp.asarray(b["y"])},
                        jax.random.PRNGKey(i))
        if (i + 1) % 4 == 0:
            tap = jax.device_get(m["numerics"])
            for rec in train_records(tap["prev_exps"], tap["exps"],
                                     tap["acc"], step=i + 1, t=float(i)):
                log.record(rec)
    assert log.records, "tap produced no records"
    classes = {r["class"] for r in log.records}
    assert "activation" in classes
    for r in log.records:
        assert 0.0 <= r["ovf_rate"] <= 1.0
        assert r["n_groups"] >= 1


# ---------------------------------------------------------------------------
# engine integration: trace spans, serve numerics, greedy bit-identity
# ---------------------------------------------------------------------------

POL_CHUNK = PrecisionPolicy("float32", prefill_chunk=4)


@pytest.fixture(scope="module")
def model():
    cfg = configs.get_smoke("llama3_8b")
    return cfg, T.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def prompts(model):
    cfg, _ = model
    return np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                         cfg.vocab_size))


def _run_wave(eng, prompts, max_new=8):
    uids = [eng.submit(p, max_new=max_new) for p in prompts]
    out = eng.run()
    return [out[u] for u in uids]


@pytest.fixture(scope="module")
def traced_run(model, prompts):
    cfg, params = model
    tracer = Tracer()
    nlog = NumericsLog()
    eng = ServeEngine(cfg, POL_CHUNK, params, max_slots=2, max_len=24,
                      options=EngineOptions(
                          cache_bits=8,
                          cache_cfg=CacheQuantConfig(width=8,
                                                     update_interval=2),
                          tracer=tracer, numerics_log=nlog,
                          numerics_every=2))
    out = _run_wave(eng, prompts)
    return eng, tracer, nlog, out


def test_engine_trace_spans_validate(traced_run, tmp_path):
    _, tracer, _, _ = traced_run
    names = set(tracer.span_names())
    assert {"admit", "prefill_chunk", "decode_step"} <= names
    for inst in ("submit", "admitted", "finish"):
        assert tracer.find(inst, "i"), f"missing {inst} instant"
    assert tracer.find("queue", "C"), "missing queue counter samples"
    path = tracer.export(str(tmp_path / "engine.json"))
    validate_trace(json.load(open(path)))


def test_engine_numerics_timeline(traced_run):
    _, _, nlog, _ = traced_run
    assert nlog.records, "no serve numerics samples on controller cadence"
    rec = nlog.records[0]
    assert rec["kind"] == "serve"
    assert len(rec["k_e"]) >= 1 and len(rec["v_e"]) == len(rec["k_e"])
    for r in nlog.records:
        for rate in r["ovf_rate"] + r["half_rate"]:
            assert 0.0 <= rate <= 1.0
        assert r["uid"] in (0, 1)


def test_traced_tokens_bit_identical_to_untraced(model, prompts, traced_run):
    cfg, params = model
    _, _, _, traced_out = traced_run
    plain = ServeEngine(cfg, POL_CHUNK, params, max_slots=2, max_len=24,
                        options=EngineOptions(
                            cache_bits=8,
                            cache_cfg=CacheQuantConfig(width=8,
                                                       update_interval=2)))
    plain_out = _run_wave(plain, prompts)
    for a, b in zip(traced_out, plain_out):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# zero-cost-when-disabled: no extra device syncs, no obs code on hot path
# ---------------------------------------------------------------------------

def test_disabled_obs_adds_no_device_syncs(model, prompts, monkeypatch):
    """Booby-trap: with observability off, a pure decode step performs
    EXACTLY the 3 device fetches (nxt, bad, rate) it did before repro.obs
    existed, and no tracer/numerics code runs at all."""
    import repro.serve.engine as eng_mod

    cfg, params = model
    eng = ServeEngine(cfg, PrecisionPolicy("float32"), params, max_slots=2,
                      max_len=64)
    assert eng._tracer is None and eng._numerics is None
    uids = [eng.submit(p, max_new=40) for p in prompts]

    # any obs entry point reached with obs disabled trips the trap
    for meth in ("begin", "end", "instant", "counter"):
        monkeypatch.setattr(
            Tracer, meth,
            lambda *a, _m=meth, **k: (_ for _ in ()).throw(
                AssertionError(f"Tracer.{_m} called with obs disabled")))
    monkeypatch.setattr(
        eng_mod.kv_pool, "numerics_snapshot",
        lambda *a, **k: (_ for _ in ()).throw(
            AssertionError("numerics_snapshot called with obs disabled")))

    real_asarray = np.asarray
    fetches = [0]

    def counting_asarray(x, *a, **k):
        if isinstance(x, jax.Array):
            fetches[0] += 1
        return real_asarray(x, *a, **k)

    eng.step()                    # admission + first decode (prefill syncs)
    monkeypatch.setattr(eng_mod.np, "asarray", counting_asarray)
    for _ in range(5):            # pure decode steps: nothing admits/ends
        eng.step()
    monkeypatch.setattr(eng_mod.np, "asarray", real_asarray)
    assert fetches[0] == 3 * 5, (
        f"expected 3 device fetches per pure decode step, got "
        f"{fetches[0]} over 5 steps")
    out = eng.run()
    assert all(len(out[u]) == 40 for u in uids)


# ---------------------------------------------------------------------------
# dispatch profiling
# ---------------------------------------------------------------------------

def test_dispatch_profile_disabled_records_nothing():
    dispatch.reset_profile()
    dispatch.profile_enable(False)
    dispatch.blocks_for("fwd", 8, 8, 8, interpret=True)
    assert dispatch.profile_stats() == {}


def test_dispatch_profile_records_and_renders():
    dispatch.reset_profile()
    dispatch.profile_enable(True)
    try:
        for _ in range(3):
            blocks = dispatch.blocks_for("fwd", 8, 16, 32, interpret=True)
        assert blocks == (8, 16, 32)
        w = dispatch.attn_blocks_for(64, 2, 4, 8, interpret=True)
        assert w == 64
        stats = dispatch.profile_stats()
        mm = stats[("mm", "fwd", "interp")]
        assert mm["calls"] == 3 and mm["hits"] == 3 and mm["misses"] == 0
        assert mm["blocks"] == (8, 16, 32)
        assert ("attn", "interp") in stats

        table = dispatch.profile_table()
        assert "mm|fwd|interp" in table and "calls" in table

        tr = Tracer()
        dispatch.profile_trace_counters(tr)
        assert tr.find("dispatch/mm|fwd|interp", "C")
        validate_trace(tr.to_chrome())
    finally:
        dispatch.profile_enable(False)
        dispatch.reset_profile()
