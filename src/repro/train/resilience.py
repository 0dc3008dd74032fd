"""Fault-tolerant training: the supervised step loop.

The serve engine (PR 7) resolves every request to a terminal status and
never raises for load or faults; this module gives training the same
contract.  :class:`TrainSupervisor` wraps :func:`repro.train.step.
make_train_step` (``supervise=True``) and resolves every step attempt to
a :class:`StepOutcome`:

* ``OK`` — sentinels clean, update committed on device;
* ``SKIPPED`` — a device-side sentinel tripped (non-finite loss/grad, or
  a §5 runaway-overflow rate per tensor class): the update was discarded
  *inside the jit* (branch-free select — the step still costs one extra
  scalar fetch), the data cursor advances past the batch;
* ``ROLLED_BACK`` — ``skip_budget`` consecutive skips exhausted: restore
  the last committed checkpoint (walking past corrupt ones) and continue
  with the *advanced* data cursor, so the poisoned batch window is never
  replayed against the restored state;
* ``HALTED`` — rollback failed twice: a diagnostic bundle (obs trace,
  numerics JSONL tail, outcome log, fault log) is written and the run
  stops resolving instead of raising.

Bit-exact resume is the checkpoint contract: the saved tree covers the
:class:`~repro.train.state.TrainState` (params/opt/scale — DFXP
exponents AND the pre-reset §5 ``acc`` windows), the stochastic-rounding
base PRNG key, the dist error-feedback residual buffers, and the data
cursor.  ``train N steps solo == train K, crash, restore, train N-K``
holds bit-for-bit, for deterministic and stochastic rounding (the
per-step key derives from ``fold_in(base, cursor)``, both checkpointed).

The loop keeps one attempt in flight.  Every outcome short of a rollback
adopts the step's output state (a SKIPPED step's in-jit select has
already frozen it) and advances the cursor by one, so attempt N+1's
inputs exist as soon as N is dispatched.  ``step_once`` therefore
dispatches N+1 before it blocks on N's flags and loss, and the host's
batch, launch and bookkeeping overlap the device's work on N.  The next
call uses that attempt only while its inputs are still the supervisor's
(the same ``state``, ``ef``, ``rng``, ``batch_fn`` and step program
objects, and the same cursor); a rollback, ``resume()`` or a replaced
step program discards it and the attempt is dispatched again, so the
records, state and checkpoints are the serial loop's bit for bit.  With
a :class:`~repro.train.faults.FaultHarness` attached, which reads and
mutates the supervisor between attempts, the loop stays serial.
"""
from __future__ import annotations

import dataclasses
import enum
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointError, CheckpointManager
from repro.core.policy import PrecisionPolicy
from repro.obs.trace import span as obs_span
from repro.optim.opt import OptConfig

from .state import TrainState
from .step import (FLAG_GRAD_NONFINITE, FLAG_LOSS_NONFINITE,
                   FLAG_RUNAWAY_OVF, benign_injection, make_train_step)

Array = jax.Array


class StepOutcome(enum.Enum):
    OK = "ok"
    SKIPPED = "skipped"
    ROLLED_BACK = "rolled_back"
    HALTED = "halted"


@dataclasses.dataclass
class StepRecord:
    cursor: int                 # data cursor of the attempt
    outcome: StepOutcome
    flags: int                  # sentinel bitmask (step.FLAG_*)
    loss: float
    info: Dict[str, Any] = dataclasses.field(default_factory=dict)
    seconds: float = 0.0        # host time of the call that resolved it

    def to_json(self) -> dict:
        return {"cursor": self.cursor, "outcome": self.outcome.value,
                "flags": self.flags, "loss": self.loss,
                "seconds": self.seconds, **self.info}


def flag_names(flags: int) -> List[str]:
    out = []
    if flags & FLAG_LOSS_NONFINITE:
        out.append("loss_nonfinite")
    if flags & FLAG_GRAD_NONFINITE:
        out.append("grad_nonfinite")
    if flags & FLAG_RUNAWAY_OVF:
        out.append("runaway_ovf")
    return out


@dataclasses.dataclass
class _Attempt:
    """A dispatched step attempt: what it was launched with, and its
    (possibly still computing) outputs."""
    cursor: int
    inputs: tuple               # state, ef, rng, batch_fn, step program
    new_state: TrainState
    metrics: Dict[str, Array]
    new_ef: Any


class TrainSupervisor:
    """Supervised train loop: sentinels, skip budget, rollback, resume.

    Parameters mirror :func:`make_train_step` plus:

    * ``batch_fn(cursor) -> batch`` — the deterministic data pipeline
      (cursor is the checkpointed data position; batches must be a pure
      function of it, as :class:`repro.data.SyntheticLM` is of its step).
    * ``rng`` — base PRNG key; the per-step stochastic-rounding key is
      ``fold_in(rng, cursor)``.  Saved in the checkpoint, so resume does
      not even need the original seed.
    * ``manager``/``ckpt_every`` — checkpoint cadence (async writes; the
      final :meth:`commit` is synchronous).  Checkpoints are keyed by the
      data cursor, which is monotonic even across skips.
    * ``skip_budget`` — consecutive SKIPPED attempts tolerated before a
      rollback.
    * ``compress_bits`` — run gradients through
      :func:`repro.dist.compress.compress_tree` error feedback; the
      residual buffers become part of the checkpointed state.
    * ``faults`` — a :class:`repro.train.faults.FaultHarness`.
    * ``tracer``/``metrics``/``numerics_log`` — repro.obs hooks; all
      optional and zero-cost when absent.  ``metrics`` holds the
      ``train_host_fetches`` and ``train_ahead_discarded`` counters.
    * ``bundle_dir`` — where the HALTED diagnostic bundle lands.
    """

    def __init__(self, loss_fn: Callable, group_shapes: Dict[str, tuple],
                 policy: PrecisionPolicy, opt_cfg: OptConfig,
                 state: TrainState, *,
                 batch_fn: Callable[[int], dict],
                 rng: Array,
                 manager: Optional[CheckpointManager] = None,
                 ckpt_every: int = 0,
                 skip_budget: int = 3,
                 runaway_ovf: Optional[float] = None,
                 compress_bits: Optional[int] = None,
                 microbatches: int = 1,
                 grad_transform: Optional[Callable] = None,
                 faults=None, tracer=None, metrics=None,
                 numerics_log=None, numerics_every: int = 0,
                 bundle_dir: Optional[str] = None):
        self.state = state
        self.batch_fn = batch_fn
        self.rng = jnp.asarray(rng)
        self.manager = manager
        self.ckpt_every = ckpt_every
        self.skip_budget = skip_budget
        self.policy = policy
        self.faults = faults
        self.tracer = tracer
        self.numerics_log = numerics_log
        self.numerics_every = numerics_every or policy.update_interval
        self.bundle_dir = bundle_dir

        ef_transform = None
        if compress_bits is not None:
            from repro.dist.compress import compress_tree, ef_init

            def ef_transform(grads, ef):
                return compress_tree(grads, ef, compress_bits)

            self.ef = ef_init(state.params)
        else:
            self.ef = {}
        self._step_fn = jax.jit(make_train_step(
            loss_fn, group_shapes, policy, opt_cfg,
            microbatches=microbatches, grad_transform=grad_transform,
            numerics_tap=numerics_log is not None,
            ef_transform=ef_transform, supervise=True,
            runaway_ovf=runaway_ovf))

        self.cursor = 0                     # next data position
        self.outcomes: List[StepRecord] = []
        self.losses: List[float] = []       # committed (OK) losses
        self.halted = False
        self._consec_skips = 0
        self._rollback_failures = 0
        self._last_commit: Optional[int] = None
        self._benign = benign_injection()
        self._ahead: Optional[_Attempt] = None   # the attempt in flight
        self._n_ahead = 0                       # attempts launched ahead
        self._n_discarded = 0                   # ... and discarded

        if metrics is None:
            from repro.obs import MetricsRegistry
            metrics = MetricsRegistry()
        self.metrics = metrics
        self._c_fetch = metrics.counter(
            "train_host_fetches", "device-to-host fetches of the supervisor")
        self._c_discard = metrics.counter(
            "train_ahead_discarded",
            "attempts launched ahead whose inputs changed before use")

    # -- checkpoint tree ---------------------------------------------------
    def ckpt_tree(self) -> dict:
        """Everything bit-exact resume needs, as one pytree."""
        return {"train": self.state, "ef": self.ef, "rng": self.rng,
                "cursor": jnp.int32(self.cursor)}

    def _adopt(self, tree: dict) -> None:
        self.state = tree["train"]
        self.ef = tree["ef"]
        self.rng = tree["rng"]

    def resume(self) -> Optional[int]:
        """Restore the newest clean committed checkpoint, if any.

        Returns the restored cursor (None when starting fresh).  Raises
        :class:`CheckpointError` only when checkpoints exist but every
        one fails verification — starting silently from step 0 in that
        situation would *look* like a resume.
        """
        if self.manager is None:
            return None
        try:
            tree, step = self.manager.restore_latest(self.ckpt_template())
        except FileNotFoundError:
            return None
        self._adopt(tree)
        self.cursor = int(np.asarray(tree["cursor"]))
        self._last_commit = step
        self._event("resumed", step=step, cursor=self.cursor)
        return self.cursor

    def ckpt_template(self) -> dict:
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x),
                                           jnp.result_type(x)),
            self.ckpt_tree())

    def commit(self, *, sync: bool = True) -> bool:
        """Write a checkpoint now.  Never raises: a failed write logs an
        event and returns False."""
        if self.manager is None:
            return False
        try:
            self.manager.wait()
        except Exception as e:               # surfaced background failure
            self._event("ckpt_async_error", error=str(e))
        try:
            self._c_fetch.inc()     # the manager fetches the tree to host
            if sync:
                self.manager.save(self.cursor, self.ckpt_tree())
            else:
                self.manager.save_async(self.cursor, self.ckpt_tree())
        except Exception as e:
            self._event("ckpt_write_error", cursor=self.cursor,
                        error=str(e))
            return False
        self._last_commit = self.cursor
        return True

    # -- the supervised step ----------------------------------------------
    def step_once(self) -> StepRecord:
        """One supervised step attempt; resolves to a StepRecord.

        Without a fault harness the call first dispatches the next
        attempt (cursor + 1, on this attempt's output state), then blocks
        on this attempt's flags and loss; the next call resolves that
        attempt if its inputs are still the supervisor's, and discards
        and re-dispatches it otherwise (``train_ahead_discarded``).  With
        a harness attached, nothing is launched ahead.

        Its phases are :class:`repro.obs.span`s, each with the cursor of
        the attempt it serves: ``train.step`` (this attempt's cursor)
        holds ``train.batch`` (host rows and their transfer) and
        ``train.launch`` (the step program dispatched) of each attempt it
        dispatches, ``train.wait`` (the flags and loss fetches, the host
        blocked on the device) and ``train.record`` (outcome bookkeeping,
        numerics, checkpoint).  ``train.step`` ends noting ``fetches``,
        this attempt's device-to-host fetches, and ``ahead``, 1 when the
        attempt it resolved had been launched by the previous call.
        """
        return self._step(ahead=self.faults is None)

    def _step(self, ahead: bool) -> StepRecord:
        """:meth:`step_once`, dispatching the next attempt if ``ahead``."""
        if self.halted:
            raise RuntimeError("supervisor is HALTED; inspect the bundle "
                               f"at {self.bundle_dir!r}")
        cursor, tr = self.cursor, self.tracer
        fetched = self._c_fetch.value
        with obs_span("train.step", tr, "train", cursor=cursor) as step:
            if self.faults is not None:
                self.faults.on_step(self)
            att = self._take_ahead()
            if self.faults is None:
                step.note(ahead=int(att is not None))
            if att is None:
                inj = (self.faults.injection(self)
                       if self.faults is not None else self._benign)
                att = self._launch(cursor, self.state, self.ef, inj)
            if ahead:
                self._ahead = self._launch(cursor + 1, att.new_state,
                                           att.new_ef, self._benign)
                self._n_ahead += 1
            with obs_span("train.wait", tr, "train", cursor=cursor) as wait:
                flags = int(np.asarray(att.metrics["flags"]))  # extra fetch
                loss = float(np.asarray(att.metrics["loss"]))
                self._c_fetch.inc(2)
            with obs_span("train.record", tr, "train", cursor=cursor):
                rec = self._record(cursor, flags, loss, att.metrics,
                                   att.new_state, att.new_ef)
            rec.seconds = wait.t1 - step.t0
            step.note(fetches=int(self._c_fetch.value - fetched))
        if rec.outcome is StepOutcome.HALTED:
            self._discard_ahead()
            # after the spans close: the bundle's trace holds this attempt
            bundle = self.write_bundle()
            self._event("halted", cursor=rec.cursor, bundle=bundle)
        return rec

    def _inputs(self, state, ef) -> tuple:
        """What an attempt on ``state``/``ef`` is launched with."""
        return (state, ef, self.rng, self.batch_fn, self._step_fn)

    def _launch(self, cursor: int, state, ef, inj) -> _Attempt:
        """Build the batch at ``cursor`` and dispatch its attempt."""
        tr = self.tracer
        with obs_span("train.batch", tr, "train", cursor=cursor):
            batch = self.batch_fn(cursor)
        with obs_span("train.launch", tr, "train", cursor=cursor):
            rng = jax.random.fold_in(self.rng, cursor)
            new_state, metrics, new_ef = self._step_fn(state, batch, rng,
                                                       ef, inj)
        return _Attempt(cursor, self._inputs(state, ef), new_state, metrics,
                        new_ef)

    def _take_ahead(self) -> Optional[_Attempt]:
        """The attempt launched ahead, if the serial loop would launch
        the same one now; discards it otherwise."""
        att = self._ahead
        if att is None:
            return None
        now = self._inputs(self.state, self.ef)
        if (self.faults is None and att.cursor == self.cursor
                and all(a is b for a, b in zip(att.inputs, now))):
            self._ahead = None
            return att
        self._discard_ahead()
        return None

    def _discard_ahead(self) -> None:
        if self._ahead is not None:
            self._ahead = None
            self._n_discarded += 1
            self._c_discard.inc()

    def _record(self, cursor: int, flags: int, loss: float, metrics,
                new_state, new_ef) -> StepRecord:
        """Resolve the attempt at ``cursor`` from its sentinel flags."""
        self.cursor += 1
        self.state, self.ef = new_state, new_ef     # select ran on device
        if flags == 0:
            self._consec_skips = 0
            self.losses.append(loss)
            rec = StepRecord(cursor, StepOutcome.OK, flags, loss)
            self._log_numerics(metrics)
            if (self.manager is not None and self.ckpt_every
                    and self.cursor % self.ckpt_every == 0):
                self.commit(sync=False)
        else:
            self._consec_skips += 1
            rec = StepRecord(cursor, StepOutcome.SKIPPED, flags, loss,
                             {"sentinels": flag_names(flags),
                              "consec": self._consec_skips})
            self._event("sentinel_skip", cursor=cursor, flags=flags,
                        sentinels=flag_names(flags))
            if self._consec_skips > self.skip_budget:
                rec = self._rollback(rec)
        self.outcomes.append(rec)
        if self.tracer is not None and rec.outcome is not StepOutcome.OK:
            self.tracer.instant(f"train:{rec.outcome.value}", tid="train",
                                cursor=cursor, flags=flags)
        return rec

    def _rollback(self, rec: StepRecord) -> StepRecord:
        """Skip budget exhausted: restore the last committed checkpoint.

        The data cursor keeps its *advanced* value — the restored state
        continues on fresh batches instead of replaying the window that
        tripped the sentinels (a deterministic poison would loop
        forever otherwise).  Two failed rollbacks escalate to HALTED +
        diagnostic bundle.
        """
        self._consec_skips = 0
        restored = None
        if self.manager is not None:
            try:
                self.manager.wait()
            except Exception as e:
                self._event("ckpt_async_error", error=str(e))
            try:
                restored = self.manager.restore_latest(self.ckpt_template())
            except (FileNotFoundError, CheckpointError) as e:
                self._event("rollback_restore_failed", error=str(e))
        if restored is None:
            self._rollback_failures += 1
            if self._rollback_failures >= 2:
                self.halted = True
                # bundle is written by step_once AFTER this record lands
                # in the outcome log, so the bundle includes it
                return StepRecord(rec.cursor, StepOutcome.HALTED, rec.flags,
                                  rec.loss,
                                  {**rec.info, "bundle": self.bundle_dir})
            self._event("rollback_failed", cursor=rec.cursor,
                        failures=self._rollback_failures)
            return StepRecord(rec.cursor, StepOutcome.ROLLED_BACK, rec.flags,
                              rec.loss, {**rec.info, "restored": None})
        tree, step = restored
        self._adopt(tree)
        # cursor stays advanced: do NOT replay the poisoned window
        self.cursor = max(self.cursor, int(np.asarray(tree["cursor"])))
        self._last_commit = step
        self._event("rolled_back", to_step=step, cursor=self.cursor)
        return StepRecord(rec.cursor, StepOutcome.ROLLED_BACK, rec.flags,
                          rec.loss, {**rec.info, "restored": step})

    def run(self, num_steps: int, *, stop: Optional[Callable[[], bool]] = None,
            log_every: int = 0) -> dict:
        """Drive ``num_steps`` attempts (or until HALTED / ``stop()``).

        Never raises for faults — every attempt lands in
        :attr:`outcomes`; returns :meth:`summary`.  The last attempt
        launches none ahead, and a stop discards the one in flight, so
        the final commit and cursor are the serial loop's.
        """
        for i in range(num_steps):
            if self.halted or (stop is not None and stop()):
                break
            rec = self._step(ahead=self.faults is None
                             and i < num_steps - 1)
            if log_every and rec.outcome is StepOutcome.OK and \
                    len(self.losses) % log_every == 0:
                print(f"step {int(self.state.step)}: loss={rec.loss:.4f}",
                      flush=True)
        self._discard_ahead()
        if not self.halted:
            self.commit(sync=True)
        return self.summary()

    # -- reporting ---------------------------------------------------------
    def outcome_counts(self) -> Dict[str, int]:
        counts = {o.value: 0 for o in StepOutcome}
        for r in self.outcomes:
            counts[r.outcome.value] += 1
        return counts

    def summary(self) -> dict:
        return {
            "attempts": len(self.outcomes),
            "outcomes": self.outcome_counts(),
            "steps_committed": int(self.state.step),
            "cursor": self.cursor,
            "final_loss": self.losses[-1] if self.losses else None,
            "halted": self.halted,
            "rollback_failures": self._rollback_failures,
            "last_checkpoint": self._last_commit,
            "launched_ahead": self._n_ahead,
            "ahead_discarded": self._n_discarded,
            "faults": (self.faults.summary()["event_counts"]
                       if self.faults is not None else {}),
        }

    def write_bundle(self, path: Optional[str] = None,
                     numerics_tail: int = 50) -> Optional[str]:
        """Write the diagnostic bundle: outcome log, summary, fault log,
        obs trace, numerics JSONL tail."""
        path = path or self.bundle_dir
        if path is None:
            return None
        os.makedirs(path, exist_ok=True)
        with open(os.path.join(path, "outcomes.json"), "w") as f:
            json.dump([r.to_json() for r in self.outcomes], f, indent=2)
        with open(os.path.join(path, "summary.json"), "w") as f:
            json.dump(self.summary(), f, indent=2)
        if self.faults is not None:
            with open(os.path.join(path, "faults.json"), "w") as f:
                json.dump(self.faults.summary(), f, indent=2)
        if self.tracer is not None:
            self.tracer.export(os.path.join(path, "trace.json"))
        if self.numerics_log is not None:
            with open(os.path.join(path, "numerics_tail.jsonl"), "w") as f:
                for r in self.numerics_log.tail(numerics_tail):
                    f.write(json.dumps(r) + "\n")
        return path

    # -- internals ---------------------------------------------------------
    def _event(self, kind: str, **kw) -> None:
        if self.faults is not None:
            self.faults.log_supervisor_event(kind, **kw)
        elif self.tracer is not None:
            self.tracer.instant(f"train:{kind}", tid="train", **kw)

    def _log_numerics(self, metrics) -> None:
        if self.numerics_log is None:
            return
        if len(self.losses) % self.numerics_every:
            return
        from repro.obs import train_records
        tap = jax.device_get(metrics["numerics"])
        self._c_fetch.inc()
        for rec in train_records(tap["prev_exps"], tap["exps"], tap["acc"],
                                 step=int(self.state.step),
                                 t=time.perf_counter()):
            self.numerics_log.record(rec)
