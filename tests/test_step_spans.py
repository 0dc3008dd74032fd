"""Step-phase spans on the profiler's clock (``repro.obs.span``), the
supervisor's host-fetch counter, and the train step's named scopes."""
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import PrecisionPolicy
from repro.data import SyntheticImages
from repro.models import maxout as MX
from repro.obs import (MetricsRegistry, NumericsLog, Tracer, span,
                       validate_trace)
from repro.obs import trace as obs_trace
from repro.optim.opt import OptConfig, sgd_init
from repro.train import (StepOutcome, TrainSupervisor, init_train_state,
                         make_train_step)
from repro.train.step import benign_injection

CFG = MX.MaxoutConfig(hidden=(16, 16), pieces=2)
GS = MX.group_shapes(CFG)
OPT = OptConfig(kind="sgd", lr=0.1, lr_decay_steps=2000)
DFXP = PrecisionPolicy("dfxp", comp_width=10, update_width=12,
                       update_interval=2)
DATA = SyntheticImages()
PHASES = ["train.batch", "train.launch", "train.wait", "train.record"]
SCOPES = ["fwd_bwd", "grad_quant", "optimizer", "dfxp_store", "controller",
          "sentinels"]


def _loss_fn(p, b, s, exps):
    return MX.loss_fn(CFG, DFXP, p, b, exps, s, rng=jax.random.PRNGKey(1))


def _batch_fn(cursor):
    b = DATA.batch(cursor, 16)
    return {"x": jnp.asarray(b["x"]), "y": jnp.asarray(b["y"])}


def _state():
    params = MX.init_params(CFG, jax.random.PRNGKey(3))
    return init_train_state(params, sgd_init(params), GS, DFXP,
                            init_exp=-8.0)


def _sup(**kw):
    return TrainSupervisor(_loss_fn, GS, DFXP, OPT, _state(),
                           batch_fn=_batch_fn, rng=jax.random.PRNGKey(0),
                           **kw)


# ---------------------------------------------------------------- the helper

class _FakeAnnotation:
    made = []

    def __init__(self, name, **args):
        self.name, self.args, self.log = name, dict(args), []
        _FakeAnnotation.made.append(self)

    def __enter__(self):
        self.log.append("enter")
        return self

    def __exit__(self, *exc):
        self.log.append("exit")

    def set_metadata(self, **args):
        self.args.update(args)


def test_span_records_into_tracer_and_opens_annotation(monkeypatch):
    monkeypatch.setattr(obs_trace, "_annotation", _FakeAnnotation)
    _FakeAnnotation.made = []
    tr = Tracer()
    with span("train.step", tr, "train", cursor=3) as outer:
        with span("train.wait", tr, "train", cursor=3):
            pass
        outer.note(fetches=2)
    outer_ann, inner_ann = _FakeAnnotation.made
    assert outer_ann.name == "repro:train.step"
    assert outer_ann.args == {"cursor": 3, "fetches": 2}
    assert outer_ann.log == ["enter", "exit"]
    assert inner_ann.name == "repro:train.wait"
    step, = tr.find("train.step", "X")
    assert step["tid"] == "train"
    assert step["args"] == {"cursor": 3, "fetches": 2}
    assert tr.span_names() == ["train.wait", "train.step"]
    assert outer.t0 <= outer.t1
    validate_trace(tr.to_chrome())


def test_span_without_tracer_calls_no_tracer_method(monkeypatch):
    for meth in ("begin", "end", "instant", "counter"):
        monkeypatch.setattr(
            Tracer, meth,
            lambda *a, _m=meth, **k: (_ for _ in ()).throw(
                AssertionError(f"Tracer.{_m} called without a tracer")))
    with span("decode_step", None, n_active=2) as sp:
        sp.note(extra=1)
    assert sp.t0 <= sp.t1


# ------------------------------------------------------------ the supervisor

def test_profiler_capture_nests_supervisor_phases(tmp_path):
    """A CPU ``jax.profiler`` capture of supervised steps holds each
    ``repro:train.step`` with its four phases nested, in order."""
    sup = _sup()
    sup.step_once()                       # compile outside the capture
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            sup.step_once()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    evs = sorted(((e.name[len("repro:"):], e.start_ns,
                   e.start_ns + e.duration_ns, dict(e.stats))
                  for p in pd.planes for line in p.lines for e in line.events
                  if e.name.startswith("repro:")), key=lambda e: e[1])
    steps = [e for e in evs if e[0] == "train.step"]
    assert [e[3]["cursor"] for e in steps] == [1, 2, 3]
    assert all(e[3]["fetches"] == 2 for e in steps)
    for name, s0, s1, stats in steps:
        inner = [e for e in evs if e[0] != "train.step"
                 and s0 <= e[1] and e[2] <= s1]
        assert [e[0] for e in inner] == PHASES
        assert all(e[3]["cursor"] == stats["cursor"] for e in inner)
        for a, b in zip(inner, inner[1:]):
            assert a[2] <= b[1]           # one after the other


def test_supervisor_phases_on_tracer_and_step_seconds():
    tr = Tracer()
    sup = _sup(tracer=tr)
    recs = [sup.step_once() for _ in range(2)]
    names = tr.span_names()
    assert "train_step" not in names
    assert names == (PHASES + ["train.step"]) * 2
    steps = tr.find("train.step", "X")
    assert [s["args"] for s in steps] == [{"cursor": 0, "fetches": 2},
                                          {"cursor": 1, "fetches": 2}]
    for rec, step in zip(recs, steps):
        assert 0 < rec.seconds <= step["dur"] / 1e6
    validate_trace(tr.to_chrome())


@pytest.mark.parametrize("numerics_every,per_step", [(0, 2), (1, 3)])
def test_host_fetches_counted_per_ok_step(numerics_every, per_step):
    """Flags and loss each step; the numerics tap adds its one fetch on
    its cadence."""
    reg = MetricsRegistry()
    kw = ({"numerics_log": NumericsLog(), "numerics_every": 1}
          if numerics_every else {})
    sup = _sup(metrics=reg, **kw)
    sup.run(4)
    ok = sum(r.outcome is StepOutcome.OK for r in sup.outcomes)
    assert ok == 4
    assert reg.snapshot()["train_host_fetches"]["value"] == per_step * ok


# --------------------------------------------------------- the step program

def test_lowered_step_carries_phase_scopes():
    step = jax.jit(make_train_step(_loss_fn, GS, DFXP, OPT, supervise=True))
    lowered = step.lower(_state(), _batch_fn(0), jax.random.PRNGKey(0), {},
                         benign_injection())
    text = lowered.as_text(debug_info=True)
    missing = [s for s in SCOPES if f"/{s}/" not in text]
    assert not missing, missing
