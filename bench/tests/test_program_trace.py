"""The reduction of the program's own marks (``bench/program_trace.py``)
and the three readers built on it, on small recorded traces.  Run with
``python -m pytest bench/tests``."""
from __future__ import annotations

import pathlib

import pytest

from bench import program_trace, trace_reduce
from bench import run as R
from bench.tests.test_yardstick import ev, recorded

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "mamba2_370m.train.b1s512"


def marked():
    """``recorded()`` with the program's ``repro:`` spans added on the
    host (scopes come apart, from :data:`SCOPES_OF`)."""
    host, d0, other, d1 = recorded()
    pname, [(line, events)] = host
    events = events + [
        ev("repro:train.step", 0, 960, cursor=4, fetches=2),
        ev("repro:train.batch", 0, 100, cursor=4),
        ev("repro:train.launch", 100, 300, cursor=4),
        ev("repro:train.wait", 400, 500, cursor=4),
        ev("repro:train.record", 900, 50, cursor=4)]
    return [(pname, [(line, events)]), d0, other, d1]


# the HLO of device 0's program, as the trace's metadata plane gives it
SCOPES_OF = {"jit_decode(123)": {"fusion.1": "fwd_bwd",
                                 "qmm_2d.7": "dfxp_store",
                                 "fusion.2": "dfxp_store"}}


def test_trace_reduce_readings_unchanged_by_program_marks():
    """The existing reduction reads the same with ``repro:`` spans in
    the trace (the numbers of ``test_trace_reduce_on_recorded_trace``)."""
    plain = trace_reduce.reduce_planes(recorded())
    s = trace_reduce.reduce_planes(marked())
    assert s.window_s == pytest.approx(1e-6)
    assert s.busy_s == pytest.approx(475e-9)
    assert s.idle_share == pytest.approx(0.525)
    assert s.op_seconds(r"qmm") == pytest.approx(150e-9 / 2)
    assert s.program_seconds(r"decode") == pytest.approx(
        (200 + 150 + 150 + 100 + 500) * 1e-9 / 2)
    assert s.idle_by_phase["engine_step"] == pytest.approx(100e-9 / 2)
    assert s.idle_by_phase["idle"] == pytest.approx(450e-9 / 2)
    assert s.idle_by_phase["submit"] == pytest.approx(500e-9 / 2)
    for field in ("window_s", "busy_s", "devices", "gaps",
                  "idle_by_phase"):
        assert getattr(s, field) == getattr(plain, field), field
    assert s.ops == plain.ops
    assert s.breakdown() == plain.breakdown()


def test_program_trace_fields():
    pt = program_trace.reduce_planes(marked(), SCOPES_OF)
    assert pt.window_s == pytest.approx(1e-6) and pt.devices == 2
    assert pt.spans["train.step"] == (1, pytest.approx(960e-9))
    assert pt.spans["train.wait"] == (1, pytest.approx(500e-9))
    # device 0 idle 0..100 (batch), 400..850 (wait); device 1 idle
    # 500..1000: wait to 900, record to 950, the step's own to 960,
    # outside any span to 1000
    idle = pt.idle_by_program_phase
    assert idle["train.batch"] == pytest.approx(100e-9 / 2)
    assert idle["train.wait"] == pytest.approx((450 + 400) * 1e-9 / 2)
    assert idle["train.record"] == pytest.approx(50e-9 / 2)
    assert idle["train.step"] == pytest.approx(10e-9 / 2)
    assert idle["outside"] == pytest.approx(40e-9 / 2)
    s = trace_reduce.reduce_planes(marked())
    assert sum(idle.values()) == pytest.approx(s.idle_share * s.window_s)
    # fusion.1 100..300 (fwd_bwd; device 1's launch jit_decode(9) takes
    # the one program of its base name, 0..500), qmm 250..400 and
    # fusion.2 900..1000 (clipped) in dfxp_store; the while is control
    # flow and has no scope
    assert pt.scope_seconds == {"fwd_bwd": pytest.approx(700e-9 / 2),
                                "dfxp_store": pytest.approx(250e-9 / 2)}
    assert pt.steps == 1 and pt.step_fetches == [2]
    assert pt.stall_ms_per_step() == pytest.approx(1e3 * 150e-9 / 2)
    assert pt.fetches_per_step() == 2.0
    assert pt.scope_ms_per_step("dfxp_store", 1) == pytest.approx(
        1e3 * 250e-9 / 2)


def test_scopes_from_the_profilers_hlo(tmp_path):
    """A CPU capture of a scoped program: :func:`hlo_scopes` reads each
    instruction's scope from the HLO the profiler keeps, and the
    reduction joins it to the ops of a device plane built from the
    capture's op events (the CPU has no device plane of its own)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("fwd_bwd"):
            y = jnp.sin(x) @ x
        with jax.named_scope("dfxp_store"):
            return jnp.round(y * 3.0)

    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(str(tmp_path))
    scopes = program_trace.hlo_scopes(pathlib.Path(path).read_bytes())
    prog, = [p for p in scopes if p.startswith("jit_f(")]
    assert set(scopes[prog].values()) == {"fwd_bwd", "dfxp_store"}
    ops = []
    for _, lines in program_trace.planes_of(path):
        for _, events in lines:
            for e in events:
                st = dict(e.stats)
                if st.get("hlo_module") == "jit_f":
                    ops.append(ev(f"%{st['hlo_op']} = f32[] x()",
                                  e.start_ns, e.duration_ns))
    s0 = min(o.start_ns for o in ops)
    s1 = max(o.start_ns + o.duration_ns for o in ops)
    device = ("/device:TPU:0", [("XLA Ops", ops),
                                ("XLA Modules", [ev(prog, s0, s1 - s0)])])
    pt = program_trace.reduce_planes([device], scopes)
    assert set(pt.scope_seconds) == {"fwd_bwd", "dfxp_store"}


def test_trace_without_program_marks_reads_nothing():
    """A trace of a program without spans or scopes (the parent's)."""
    pt = program_trace.reduce_planes(recorded())
    assert pt.spans == {} and pt.idle_by_program_phase == {}
    assert pt.scope_seconds == {}
    assert pt.stall_ms_per_step() is None
    assert pt.fetches_per_step() is None
    assert pt.scope_ms_per_step("dfxp_store", 3) is None
    assert program_trace.reduce_planes([recorded()[0]]) is None


NAMES = ["supervisor.stall_ms_per_step", "supervisor.fetches_per_step",
         "dfxp_store.ms_per_step"]


def ctx_of_window():
    return {"trace_t0": 10.0, "trace_t1": 20.0, "step_ends": [9.0, 12.0],
            "config": {}, "mix": {}}


@pytest.mark.parametrize("planes,scopes,want", [
    (marked, SCOPES_OF, [1e3 * 150e-9 / 2, 2.0, 1e3 * 250e-9 / 2]),
    (recorded, {}, [None, None, None])])
def test_readers(monkeypatch, planes, scopes, want):
    pt = program_trace.reduce_planes(planes(), scopes)
    monkeypatch.setattr(program_trace, "for_run", lambda root, ctx: pt)
    got = [R.metric_reader(ROOT, n)(ctx_of_window(), None) for n in NAMES]
    assert got == [pytest.approx(w) if w else None for w in want]


def test_readers_without_a_trace(monkeypatch):
    monkeypatch.setattr(program_trace, "for_run", lambda root, ctx: None)
    for n in NAMES:
        assert R.metric_reader(ROOT, n)(ctx_of_window(), None) is None


def test_trace_dir_is_found_from_the_run(tmp_path):
    for rel in ("BENCHMARK.json", "bench/configs/mamba2_370m.json",
                "bench/traffic/train.b1s512.json"):
        (tmp_path / rel).parent.mkdir(parents=True, exist_ok=True)
        (tmp_path / rel).write_text((ROOT / rel).read_text())
    spec = R.load_spec(tmp_path, CELL)
    ctx = {"config": spec["config"], "mix": spec["mix"]}
    assert program_trace.trace_dir(tmp_path, ctx) is None   # not traced
    d = tmp_path / ".cache" / "bench" / "trace" / CELL
    d.mkdir(parents=True)
    assert program_trace.trace_dir(tmp_path, ctx) == d
    assert program_trace.for_run(tmp_path, ctx) is None     # no xplane
    assert program_trace.trace_dir(
        tmp_path, {"config": spec["config"], "mix": {}}) is None
