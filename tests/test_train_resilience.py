"""Fault-tolerant training: sentinels, rollback, bit-exact resume, chaos."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.core import PrecisionPolicy
from repro.data import SyntheticImages
from repro.models import maxout as MX
from repro.optim.opt import OptConfig, sgd_init
from repro.train import (FaultHarness, GradNaN, LossSpike, ParamBitFlip,
                         StepOutcome, TrainSupervisor, chaos_plan,
                         init_train_state)
from repro.train.faults import CkptTear

CFG = MX.MaxoutConfig(hidden=(48, 48), pieces=3)
GS = MX.group_shapes(CFG)
OPT = OptConfig(kind="sgd", lr=0.1, lr_decay_steps=2000, max_col_norm=1.9365)
DATA = SyntheticImages()

DFXP = PrecisionPolicy("dfxp", comp_width=10, update_width=12,
                       update_interval=4)


def _loss_fn(policy):
    def loss_fn(p, b, s, exps):
        return MX.loss_fn(CFG, policy, p, b, exps, s,
                          rng=jax.random.PRNGKey(1))
    return loss_fn


def _batch_fn(cursor):
    b = DATA.batch(cursor, 64)
    return {"x": jnp.asarray(b["x"]), "y": jnp.asarray(b["y"])}


def _state(policy, seed=7):
    params = MX.init_params(CFG, jax.random.PRNGKey(seed))
    return init_train_state(params, sgd_init(params), GS, policy,
                            init_exp=-8.0)


def _sup(policy=DFXP, **kw):
    kw.setdefault("batch_fn", _batch_fn)
    kw.setdefault("rng", jax.random.PRNGKey(0))
    return TrainSupervisor(_loss_fn(policy), GS, policy, OPT,
                           _state(policy), **kw)


# ---------------------------------------------------------------- sentinels


def test_sentinel_skips_and_preserves_state():
    """A poisoned step is SKIPPED on device: TrainState does not advance,
    the data cursor does, and the next clean step proceeds."""
    h = FaultHarness([GradNaN(step=2), LossSpike(step=5)])
    sup = _sup(faults=h, skip_budget=10)
    summary = sup.run(8)
    outs = [r.outcome for r in sup.outcomes]
    assert outs[2] is StepOutcome.SKIPPED
    assert outs[5] is StepOutcome.SKIPPED
    assert summary["outcomes"]["ok"] == 6
    assert summary["steps_committed"] == 6      # skips never hit the state
    assert summary["cursor"] == 8               # but the cursor moved on
    assert all(np.isfinite(loss) for loss in sup.losses)
    kinds = {e["kind"] for e in h.log}
    assert "grad_nan" in kinds and "loss_spike" in kinds


def test_skipped_step_is_identical_to_never_poisoned():
    """The in-jit discard is total: a run with a skipped step ends bit-
    identical to a run where that batch's update simply never happened."""
    h = FaultHarness([GradNaN(step=3)])
    a = _sup(faults=h, skip_budget=10)
    a.run(6)
    b = _sup(skip_budget=10)
    b.run(6)
    # b consumed batch 3 productively, a skipped it: align by replaying
    # b without cursor 3's update — easiest exact check: state after a's
    # 6 attempts == training only on batches [0,1,2,4,5].
    c = _sup(skip_budget=10,
             batch_fn=lambda i: _batch_fn(i if i < 3 else i + 1))
    c.run(5)
    for x, y in zip(jax.tree.leaves(a.state.params),
                    jax.tree.leaves(c.state.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_runaway_overflow_sentinel_fires():
    """An absurdly low runaway threshold trips the §5 overflow sentinel
    (quantizing anything overflows at some rate > 0 with exps at -8)."""
    sup = _sup(runaway_ovf=1e-12, skip_budget=1000)
    sup.run(3)
    skipped = [r for r in sup.outcomes if r.outcome is StepOutcome.SKIPPED]
    assert skipped, [r.outcome for r in sup.outcomes]
    assert any("runaway_ovf" in r.info.get("sentinels", ())
               for r in skipped)


# ---------------------------------------------------------------- rollback


def test_skip_budget_exhaustion_rolls_back(tmp_path):
    """A poison burst longer than the skip budget triggers a rollback to
    the last committed checkpoint; training continues past the burst."""
    mgr = CheckpointManager(str(tmp_path))
    h = FaultHarness([GradNaN(step=4, count=4)])
    sup = _sup(manager=mgr, ckpt_every=2, skip_budget=2, faults=h)
    summary = sup.run(12)
    outs = [r.outcome for r in sup.outcomes]
    assert StepOutcome.ROLLED_BACK in outs
    rb = outs.index(StepOutcome.ROLLED_BACK)
    assert sup.outcomes[rb].info["restored"] == 4   # ckpt at cursor 4
    # after the burst window, training resumed cleanly
    assert outs[-1] is StepOutcome.OK
    assert not summary["halted"]
    assert summary["outcomes"]["rolled_back"] >= 1
    # cursor kept its advanced value: the poisoned window is not replayed
    assert summary["cursor"] == 12


def test_double_rollback_failure_halts_with_bundle(tmp_path):
    """No restorable checkpoint: two failed rollbacks escalate to HALTED
    and the diagnostic bundle is written; run() resolves, never raises."""
    from repro.obs import NumericsLog, Tracer
    bundle = str(tmp_path / "bundle")
    h = FaultHarness([GradNaN(step=0, count=100)])
    sup = _sup(manager=None, skip_budget=1, faults=h, tracer=Tracer(),
               numerics_log=NumericsLog(), bundle_dir=bundle)
    summary = sup.run(50)
    assert summary["halted"]
    outs = [r.outcome for r in sup.outcomes]
    assert outs[-1] is StepOutcome.HALTED
    assert outs.count(StepOutcome.ROLLED_BACK) == 1   # first failure
    assert summary["attempts"] < 50                   # stopped early
    for fname in ("outcomes.json", "summary.json", "faults.json",
                  "trace.json"):
        assert os.path.exists(os.path.join(bundle, fname)), fname
    with open(os.path.join(bundle, "outcomes.json")) as f:
        recs = json.load(f)
    assert recs[-1]["outcome"] == "halted"
    with pytest.raises(RuntimeError):
        sup.step_once()                               # halted stays halted


# ---------------------------------------------------------- bit-exact resume


def _resume_pair(policy, *, tmp_path, n=10, k=6, compress_bits=None,
                 seed=0):
    """Train ``n`` straight vs train ``k``, 'crash', restore, train n-k.

    Returns (solo_losses, resumed_losses, solo_state, resumed_state).
    """
    solo = _sup(policy, compress_bits=compress_bits,
                rng=jax.random.PRNGKey(seed))
    solo.run(n)

    d = str(tmp_path / "ck")
    first = _sup(policy, compress_bits=compress_bits,
                 rng=jax.random.PRNGKey(seed),
                 manager=CheckpointManager(d))
    first.run(k)                     # run() commits synchronously at end
    del first                        # the "crash"

    second = _sup(policy, compress_bits=compress_bits,
                  rng=jax.random.PRNGKey(4242),   # wrong seed on purpose:
                  manager=CheckpointManager(d))   # ckpt must carry the key
    assert second.resume() == k
    second.run(n - k)
    return solo, second


def _assert_bit_identical(solo, resumed, k):
    assert solo.losses[k:] == resumed.losses
    for a, b in zip(jax.tree.leaves(solo.ckpt_tree()),
                    jax.tree.leaves(resumed.ckpt_tree())):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bit_exact_resume_deterministic(tmp_path):
    """K=6 lands mid-§5-window (interval 4): the pre-reset acc counters
    must be checkpointed for the cursor-8 controller move to agree."""
    solo, resumed = _resume_pair(DFXP, tmp_path=tmp_path, n=10, k=6)
    _assert_bit_identical(solo, resumed, 6)


def test_bit_exact_resume_stochastic_fused(tmp_path):
    """Stochastic rounding + fused matmul: the per-step key derives from
    the checkpointed base key and cursor, so the random stream continues
    exactly."""
    pol = dataclasses.replace(DFXP, stochastic_rounding=True,
                              fused_matmul=True)
    solo, resumed = _resume_pair(pol, tmp_path=tmp_path, n=9, k=5)
    _assert_bit_identical(solo, resumed, 5)


def test_bit_exact_resume_error_feedback_packed(tmp_path):
    """Error-feedback residuals + packed int16 storage survive the crash:
    forgetting either breaks bitwise equality immediately."""
    pol = dataclasses.replace(DFXP, storage="packed")
    solo, resumed = _resume_pair(pol, tmp_path=tmp_path, n=8, k=5,
                                 compress_bits=8)
    # the residuals themselves must be nonzero for this test to bite
    assert any(float(jnp.max(jnp.abs(leaf))) > 0
               for leaf in jax.tree.leaves(solo.ef))
    _assert_bit_identical(solo, resumed, 5)


# -------------------------------------------------------------- host faults


def test_param_bit_flip_packed_and_sim_skip(tmp_path):
    pol = dataclasses.replace(DFXP, storage="packed")
    h = FaultHarness([ParamBitFlip(step=2, bit=6)])
    sup = _sup(pol, faults=h, skip_budget=100)
    sup.run(5)
    assert any(e["kind"] == "bit_flip" for e in h.log)
    # sim storage has no mantissa: the injector skips with a reason
    h2 = FaultHarness([ParamBitFlip(step=2)])
    sup2 = _sup(DFXP, faults=h2, skip_budget=100)
    sup2.run(4)
    assert any(e["kind"] == "bit_flip_skipped" for e in h2.log)


@pytest.mark.parametrize("mode", ["strip", "corrupt"])
def test_ckpt_tear_falls_back_to_previous_commit(tmp_path, mode):
    """Tearing the newest checkpoint (strip _COMMITTED / corrupt a leaf
    against its CRC) makes restore fall back to the previous commit."""
    mgr = CheckpointManager(str(tmp_path))
    sup = _sup(manager=mgr, ckpt_every=2)
    sup.run(6)                       # commits at 2, 4, 6
    mgr.wait()
    h = FaultHarness([CkptTear(step=0, mode=mode)])
    h._tear(sup, h.faults[0], 0)
    assert any(e["kind"] == "ckpt_tear" for e in h.log)
    tree, step = mgr.restore_latest(sup.ckpt_template())
    assert step == 4                 # newest (6) torn -> previous commit
    assert int(np.asarray(tree["cursor"])) == 4


def test_ckpt_tear_writer_surfaces_on_wait(tmp_path):
    """Writer death mid-save: save_async captures the failure and the
    supervisor's next commit logs it instead of raising."""
    mgr = CheckpointManager(str(tmp_path), retries=0, backoff_s=0.0)
    h = FaultHarness([CkptTear(step=1, mode="writer")])
    sup = _sup(manager=mgr, ckpt_every=2, faults=h)
    summary = sup.run(6)
    assert not summary["halted"]
    assert summary["outcomes"]["ok"] == 6
    kinds = [e["kind"] for e in h.log]
    assert "ckpt_tear" in kinds
    assert any(k in ("sup:ckpt_async_error", "sup:ckpt_write_error")
               for k in kinds), kinds
    # the run still ended with a good committed checkpoint (final sync
    # save happens after the injected failure budget is exhausted)
    assert mgr.latest() is not None


# -------------------------------------------------------------------- chaos


@pytest.mark.parametrize("seed", [0, 1])
def test_chaos_sweep_every_step_resolves(tmp_path, seed):
    """A seeded fault mix (NaN bursts, spikes, tears, bit flips) always
    terminates with every attempt resolved to an outcome — no raw
    tracebacks, no unresolved steps."""
    from repro.obs import Tracer
    pol = dataclasses.replace(DFXP, storage="packed")
    faults = chaos_plan(seed, n_steps=14, burst=4)
    assert faults                     # both seeds draw a non-empty plan
    mgr = CheckpointManager(str(tmp_path), retries=0, backoff_s=0.0)
    h = FaultHarness(faults, seed=seed, tracer=Tracer())
    sup = _sup(pol, manager=mgr, ckpt_every=2, skip_budget=2, faults=h,
               bundle_dir=str(tmp_path / "bundle"))
    summary = sup.run(14)
    assert summary["attempts"] == len(sup.outcomes)
    assert all(isinstance(r.outcome, StepOutcome) for r in sup.outcomes)
    assert sum(summary["outcomes"].values()) == summary["attempts"]
    # same seed -> same plan (reproducibility of the sweep itself)
    again = chaos_plan(seed, n_steps=14, burst=4)
    assert [type(f).__name__ for f in again] == \
           [type(f).__name__ for f in faults]
    # fault log serializes (the CI artifact)
    json.dumps(h.summary())

