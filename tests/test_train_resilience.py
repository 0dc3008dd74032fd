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
from repro.train.step import FLAG_LOSS_NONFINITE, benign_injection

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
                 seed=0, lookahead=True):
    """Train ``n`` straight vs train ``k``, 'crash', restore, train n-k.

    ``lookahead=False`` attaches an empty fault harness, which keeps the
    supervisor's loop serial.  Returns (solo, resumed) supervisors.
    """
    def sup(**kw):
        if not lookahead:
            kw["faults"] = FaultHarness([])
        return _sup(policy, compress_bits=compress_bits, **kw)

    solo = sup(rng=jax.random.PRNGKey(seed))
    solo.run(n)

    d = str(tmp_path / "ck")
    first = sup(rng=jax.random.PRNGKey(seed), manager=CheckpointManager(d))
    first.run(k)                     # run() commits synchronously at end
    del first                        # the "crash"

    second = sup(rng=jax.random.PRNGKey(4242),    # wrong seed on purpose:
                 manager=CheckpointManager(d))    # ckpt must carry the key
    assert second.resume() == k
    second.run(n - k)
    return solo, second


def _assert_bit_identical(solo, resumed, k):
    assert solo.losses[k:] == resumed.losses
    for a, b in zip(jax.tree.leaves(solo.ckpt_tree()),
                    jax.tree.leaves(resumed.ckpt_tree())):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("lookahead", [True, False],
                         ids=["lookahead", "serial"])
def test_bit_exact_resume_deterministic(tmp_path, lookahead):
    """K=6 lands mid-§5-window (interval 4): the pre-reset acc counters
    must be checkpointed for the cursor-8 controller move to agree."""
    solo, resumed = _resume_pair(DFXP, tmp_path=tmp_path, n=10, k=6,
                                 lookahead=lookahead)
    _assert_bit_identical(solo, resumed, 6)
    assert resumed.summary()["launched_ahead"] == (3 if lookahead else 0)


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


# ------------------------------------------------------------ the lookahead
#
# Without a fault harness the supervisor dispatches attempt N+1 before it
# blocks on N.  Each case below must equal a serial loop over the same
# jitted step bit for bit: a plain dispatch-block-adopt loop where no
# rollback happens, else the supervisor kept serial by an empty harness.


def _cursor_batch_fn(cursor):
    return {**_batch_fn(cursor), "cursor": cursor}


def _skip_at(step_fn, cursors):
    """``step_fn`` (fed :func:`_cursor_batch_fn`'s batches) that reports
    the loss sentinel at ``cursors`` and, like the in-jit select, returns
    the state and residuals it was given there."""
    def wrapped(state, batch, rng, ef, inj):
        batch = dict(batch)
        cursor = batch.pop("cursor")
        new_state, metrics, new_ef = step_fn(state, batch, rng, ef, inj)
        if cursor in cursors:
            flags = jnp.int32(FLAG_LOSS_NONFINITE)
            return state, {**metrics, "flags": flags}, ef
        return new_state, metrics, new_ef
    return wrapped


def _plain_loop(step_fn, state, ef, rng, batch_fn, cursors):
    """Dispatch, block on flags and loss, adopt: the serial loop."""
    recs, inj = [], benign_injection()
    for c in cursors:
        state, m, ef = step_fn(state, batch_fn(c), jax.random.fold_in(rng, c),
                               ef, inj)
        flags = int(np.asarray(m["flags"]))
        recs.append((c, "ok" if flags == 0 else "skipped", flags,
                     float(np.asarray(m["loss"]))))
    return recs, state, ef


def _recs(sup):
    return [(r.cursor, r.outcome.value, r.flags, r.loss) for r in sup.outcomes]


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _last_ckpt(sup):
    sup.manager.wait()
    return sup.manager.restore_latest(sup.ckpt_template())


def test_lookahead_clean_run_equals_serial_loop(tmp_path):
    """A clean run with error feedback: every attempt after the first was
    launched ahead and none discarded; records, state, residuals and the
    final checkpoint are the plain loop's."""
    sup = _sup(compress_bits=8, manager=CheckpointManager(str(tmp_path)),
               ckpt_every=2)
    state0, ef0 = sup.state, sup.ef
    summary = sup.run(6)
    recs, state, ef = _plain_loop(sup._step_fn, state0, ef0, sup.rng,
                                  _batch_fn, range(6))
    assert _recs(sup) == recs
    _assert_trees_equal(sup.state, state)
    _assert_trees_equal(sup.ef, ef)
    tree, step = _last_ckpt(sup)
    assert step == 6
    _assert_trees_equal(tree, {"train": state, "ef": ef, "rng": sup.rng,
                               "cursor": jnp.int32(6)})
    assert summary["launched_ahead"] == 5           # none past the last
    assert summary["ahead_discarded"] == 0
    assert sup.metrics.snapshot()["train_ahead_discarded"]["value"] == 0


def test_lookahead_skipped_attempts_equal_serial_loop():
    sup = _sup(batch_fn=_cursor_batch_fn, skip_budget=10)
    sup._step_fn = _skip_at(sup._step_fn, {2, 3})
    state0, ef0 = sup.state, sup.ef
    summary = sup.run(6)
    recs, state, ef = _plain_loop(sup._step_fn, state0, ef0, sup.rng,
                                  _cursor_batch_fn, range(6))
    assert [r[1] for r in recs] == ["ok", "ok", "skipped", "skipped", "ok",
                                    "ok"]
    assert _recs(sup) == recs
    _assert_trees_equal(sup.state, state)
    assert summary["steps_committed"] == 4
    assert summary["ahead_discarded"] == 0


def _lookahead_and_serial(tmp_path, skip, **kw):
    """The same supervisor twice over one jitted step, skipping at
    ``skip``: with the lookahead, and kept serial by an empty harness."""
    pair = []
    for name, faults in (("ahead", None), ("serial", FaultHarness([]))):
        mgr = CheckpointManager(str(tmp_path / name), retries=0,
                                backoff_s=0.0)
        pair.append(_sup(batch_fn=_cursor_batch_fn, manager=mgr,
                         faults=faults, bundle_dir=str(tmp_path / name / "b"),
                         **kw))
    ahead, serial = pair
    ahead._step_fn = serial._step_fn = _skip_at(ahead._step_fn, skip)
    return ahead, serial


def test_lookahead_rollback_discards_and_reruns(tmp_path):
    """Three skips over a budget of two roll back to the cursor-2
    checkpoint: the attempt launched ahead on the frozen state is
    discarded and cursor 6 runs again on the restored state."""
    ahead, serial = _lookahead_and_serial(tmp_path, {3, 4, 5},
                                          ckpt_every=2, skip_budget=2)
    a, b = ahead.run(9), serial.run(9)
    assert [r.outcome for r in ahead.outcomes].count(
        StepOutcome.ROLLED_BACK) == 1
    assert ahead.outcomes[5].info["restored"] == 2
    assert _recs(ahead) == _recs(serial)
    _assert_trees_equal(ahead.ckpt_tree(), serial.ckpt_tree())
    (ta, sa), (tb, sb) = _last_ckpt(ahead), _last_ckpt(serial)
    assert sa == sb == 9
    _assert_trees_equal(ta, tb)
    assert a["ahead_discarded"] == 1 and b["ahead_discarded"] == 0
    assert ahead.metrics.snapshot()["train_ahead_discarded"]["value"] == 1
    assert {k: v for k, v in a.items() if "ahead" not in k and k != "faults"} \
        == {k: v for k, v in b.items() if "ahead" not in k and k != "faults"}


def test_lookahead_halted_leaves_nothing_in_flight(tmp_path):
    """No checkpoint to restore: a failed rollback keeps the inputs (the
    attempt launched ahead is used), the second halts and drops it."""
    ahead, serial = _lookahead_and_serial(tmp_path, set(range(50)),
                                          skip_budget=1)
    ahead.manager = serial.manager = None
    a, b = ahead.run(50), serial.run(50)
    assert a["halted"] and b["halted"]
    assert _recs(ahead) == _recs(serial)
    assert ahead.outcomes[-1].outcome is StepOutcome.HALTED
    _assert_trees_equal(ahead.ckpt_tree(), serial.ckpt_tree())
    assert ahead._ahead is None
    assert a["ahead_discarded"] == 1
    assert a["launched_ahead"] == a["attempts"]
    with pytest.raises(RuntimeError):
        ahead.step_once()


def test_lookahead_stop_commits_the_serial_cursor(tmp_path):
    """``run(stop=...)`` discards the attempt in flight: the final commit
    holds the cursor and state of the attempts resolved."""
    sup = _sup(manager=CheckpointManager(str(tmp_path)))
    state0, ef0 = sup.state, sup.ef
    summary = sup.run(10, stop=lambda: len(sup.outcomes) >= 4)
    recs, state, ef = _plain_loop(sup._step_fn, state0, ef0, sup.rng,
                                  _batch_fn, range(4))
    assert _recs(sup) == recs
    assert summary["cursor"] == 4 and sup._ahead is None
    tree, step = _last_ckpt(sup)
    assert step == 4
    _assert_trees_equal(tree, {"train": state, "ef": ef, "rng": sup.rng,
                               "cursor": jnp.int32(4)})
    assert summary["launched_ahead"] == 4
    assert summary["ahead_discarded"] == 1


def test_lookahead_replaced_step_fn_runs_from_next_attempt():
    """A step program swapped in between calls (as a harness plants a
    fault) runs from the next attempt on: the one launched ahead with
    the old program is discarded."""
    sup = _sup(batch_fn=_cursor_batch_fn, skip_budget=10)
    inner = sup._step_fn
    state0, ef0 = sup.state, sup.ef
    sup.step_once()                                  # cursor 1 in flight
    sup._step_fn = _skip_at(inner, {1, 2})
    sup.run(3)
    head, s1, e1 = _plain_loop(_skip_at(inner, set()), state0, ef0, sup.rng,
                               _cursor_batch_fn, range(1))
    tail, state, _ = _plain_loop(sup._step_fn, s1, e1, sup.rng,
                                 _cursor_batch_fn, range(1, 4))
    assert _recs(sup) == head + tail
    assert [r.outcome.value for r in sup.outcomes] == ["ok", "skipped",
                                                       "skipped", "ok"]
    _assert_trees_equal(sup.state, state)
    assert sup.summary()["ahead_discarded"] == 1


def test_lookahead_discarded_after_resume(tmp_path):
    """``resume()`` adopts the checkpoint's state and cursor: the attempt
    in flight is discarded and the restored cursor runs again, reading
    the loss it read before."""
    sup = _sup(manager=CheckpointManager(str(tmp_path)))
    sup.run(3)                                        # commits at 3
    first = sup.step_once()                           # cursor 3
    sup.step_once()                                   # cursor 5 in flight
    assert sup.resume() == 3
    again = sup.step_once()
    assert (again.cursor, again.loss) == (first.cursor, first.loss)
    assert sup.summary()["ahead_discarded"] == 1


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

