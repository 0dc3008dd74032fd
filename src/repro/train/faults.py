"""Deterministic fault injection for the training loop (chaos testing).

The training-side twin of :mod:`repro.serve.faults`: the resilience
layer in :mod:`repro.train.resilience` — device-side sentinels, the skip
budget, rollback to the last committed checkpoint — is only worth
trusting if its failure paths actually run.  Each injector fires at the
real boundary the matching production fault would cross:

* :class:`GradNaN` poisons the gradients **inside the train jit** (the
  step's ``inj`` input adds ``where(flag, nan, 0)`` to every leaf after
  the microbatch scan), so the non-finite-gradient sentinel genuinely
  detects it on device.
* :class:`LossSpike` scales the loss *before* autodiff, so the spike
  propagates through the backward pass like a real blowup (a large
  enough factor overflows grads to inf; a NaN-producing 0*inf is the
  loss sentinel's job).
* :class:`CkptTear` attacks the checkpoint pipeline in one of three
  modes — ``writer`` kills the background save mid-write (via
  :meth:`CheckpointManager.inject_failure`, surfacing on the next
  ``wait()``), ``strip`` deletes the newest ``_COMMITTED`` marker
  (power-cut-shaped tear), ``corrupt`` flips a byte in a committed leaf
  file against its manifest CRC32.  Restore must fall back to the
  previous committed step in all three.
* :class:`ParamBitFlip` XORs a mantissa bit of one packed param leaf on
  the host between steps, modeling a storage upset in DFXP weight
  memory.  Skips (with a logged reason) when params are in f32 compute
  storage — there is no mantissa to flip.
* :class:`Kill` SIGKILLs the process at a step — the CI ``train-resume``
  smoke's crash; nothing in-process can observe it, which is the point.

:class:`FaultHarness` fires each fault exactly once (or for its
``count`` window), keeps a JSON-able event log, and mirrors every event
onto a :class:`repro.obs.Tracer` when attached.  Injectors no-op
with a logged reason when their precondition fails, so a chaos sweep
never crashes the harness itself.  :func:`chaos_plan` draws a
reproducible fault mix from a seed.
"""
from __future__ import annotations

import dataclasses
import os
import random
import signal
from typing import List

import jax.numpy as jnp
import numpy as np

from .step import benign_injection

__all__ = ["GradNaN", "LossSpike", "CkptTear", "ParamBitFlip", "Kill",
           "FaultHarness", "chaos_plan"]


@dataclasses.dataclass
class GradNaN:
    """Poison the gradient tree at data cursor ``step`` (device-side),
    for ``count`` consecutive attempts — ``count > skip_budget`` forces
    a rollback instead of a lone skip."""

    step: int
    count: int = 1
    fired: bool = False


@dataclasses.dataclass
class LossSpike:
    """Multiply the loss by ``factor`` at cursor ``step`` for ``count``
    attempts.  ``factor=float('inf')`` (or ~1e30) trips the loss/grad
    sentinels; a merely-large factor tests that finite-but-ugly steps
    are NOT skipped (sentinels are for non-finites, §5 handles scale)."""

    step: int
    factor: float = float("inf")
    count: int = 1
    fired: bool = False


@dataclasses.dataclass
class CkptTear:
    """Tear the checkpoint pipeline at cursor ``step``.

    ``mode``: ``writer`` — the next ``retries+1`` save attempts die
    mid-leaf-write (async error surfaces at ``wait()``); ``strip`` —
    delete the newest checkpoint's ``_COMMITTED`` marker; ``corrupt`` —
    XOR one byte of a leaf file in the newest committed checkpoint, so
    its manifest CRC32 no longer matches.
    """

    step: int
    mode: str = "corrupt"
    fired: bool = False

    def __post_init__(self):
        if self.mode not in ("writer", "strip", "corrupt"):
            raise ValueError(f"unknown CkptTear mode {self.mode!r}")


@dataclasses.dataclass
class ParamBitFlip:
    """XOR bit ``bit`` of one packed-param mantissa at cursor ``step``."""

    step: int
    bit: int = 5
    fired: bool = False


@dataclasses.dataclass
class Kill:
    """SIGKILL the process at cursor ``step`` (the CI crash smoke)."""

    step: int
    fired: bool = False


class FaultHarness:
    """Drives a fault list against a :class:`TrainSupervisor`.

    The supervisor calls two hooks per step attempt: :meth:`on_step`
    (host-side surgery — checkpoint tears, param bit flips, kills)
    before building the batch, and :meth:`injection` for the device-side
    ``inj`` dict fed to the train jit.  Both are cheap no-ops with no
    pending faults.  ``log`` accumulates one JSON-able dict per event.
    """

    def __init__(self, faults, seed: int = 0, tracer=None):
        self.faults = list(faults)
        self.seed = seed
        self.log: List[dict] = []
        self.tracer = tracer

    def _event(self, kind: str, **kw) -> None:
        self.log.append({"kind": kind, **kw})
        if self.tracer is not None:
            self.tracer.instant(f"fault:{kind}", tid="faults", **kw)

    def log_supervisor_event(self, kind: str, **kw) -> None:
        """Supervisor outcomes land in the same log (rollbacks, halts),
        tagged so ``summary()`` separates them from injections."""
        self.log.append({"kind": f"sup:{kind}", **kw})
        if self.tracer is not None:
            self.tracer.instant(f"train:{kind}", tid="train", **kw)

    # -- supervisor hooks --------------------------------------------------
    def on_step(self, sup) -> None:
        cursor = sup.cursor
        for f in self.faults:
            if isinstance(f, CkptTear) and not f.fired and cursor >= f.step:
                f.fired = True
                self._tear(sup, f, cursor)
            elif isinstance(f, ParamBitFlip) and not f.fired and \
                    cursor >= f.step:
                f.fired = True
                self._flip(sup, f, cursor)
            elif isinstance(f, Kill) and not f.fired and cursor >= f.step:
                f.fired = True
                self._event("kill", cursor=cursor, pid=os.getpid())
                os.kill(os.getpid(), signal.SIGKILL)

    def injection(self, sup) -> dict:
        inj = benign_injection()
        cursor = sup.cursor
        for f in self.faults:
            if isinstance(f, GradNaN) and \
                    f.step <= cursor < f.step + f.count:
                inj["grad_nan"] = jnp.bool_(True)
                if not f.fired:
                    f.fired = True
                self._event("grad_nan", cursor=cursor,
                            window=[f.step, f.step + f.count])
            elif isinstance(f, LossSpike) and \
                    f.step <= cursor < f.step + f.count:
                inj["loss_scale"] = jnp.float32(f.factor)
                if not f.fired:
                    f.fired = True
                self._event("loss_spike", cursor=cursor, factor=f.factor)
        return inj

    # -- host-side surgery -------------------------------------------------
    def _tear(self, sup, f: CkptTear, cursor: int) -> None:
        mgr = sup.manager
        if mgr is None:
            self._event("ckpt_tear_skipped", cursor=cursor,
                        reason="no checkpoint manager attached")
            return
        if f.mode == "writer":
            mgr.inject_failure()
            self._event("ckpt_tear", mode="writer", cursor=cursor)
            return
        try:
            mgr.wait()
        except Exception:
            pass                            # surfaced later by supervisor
        steps = mgr.all_steps()
        committed = [s for s in steps if os.path.exists(
            os.path.join(mgr.dir, f"step_{s:08d}", "_COMMITTED"))]
        if not committed:
            self._event("ckpt_tear_skipped", cursor=cursor, mode=f.mode,
                        reason="no committed checkpoint to tear")
            return
        path = os.path.join(mgr.dir, f"step_{max(committed):08d}")
        if f.mode == "strip":
            os.remove(os.path.join(path, "_COMMITTED"))
            self._event("ckpt_tear", mode="strip", cursor=cursor,
                        victim=os.path.basename(path))
            return
        leaves = sorted(n for n in os.listdir(path) if n.endswith(".npy"))
        if not leaves:
            self._event("ckpt_tear_skipped", cursor=cursor, mode=f.mode,
                        reason="committed dir has no leaf files")
            return
        victim = os.path.join(path, leaves[len(leaves) // 2])
        with open(victim, "r+b") as fh:
            fh.seek(-1, os.SEEK_END)
            b = fh.read(1)
            fh.seek(-1, os.SEEK_END)
            fh.write(bytes([b[0] ^ 0xFF]))
        self._event("ckpt_tear", mode="corrupt", cursor=cursor,
                    victim=os.path.relpath(victim, mgr.dir))

    def _flip(self, sup, f: ParamBitFlip, cursor: int) -> None:
        from repro.core.packed import PackedArray

        import jax

        leaves = [x for x in jax.tree.leaves(
            sup.state.params,
            is_leaf=lambda x: isinstance(x, PackedArray))
            if isinstance(x, PackedArray)]
        if not leaves:
            self._event("bit_flip_skipped", cursor=cursor,
                        reason="params are not in packed storage")
            return
        target = leaves[len(leaves) // 2]
        m = np.asarray(target.mantissa)
        idx = tuple(d // 2 for d in m.shape)
        width = 8 * m.dtype.itemsize
        bit = min(f.bit, width - 2)         # keep off the sign bit
        old = int(m[idx])
        new_m = target.mantissa.at[idx].set(
            jnp.bitwise_xor(target.mantissa[idx],
                            jnp.asarray(1 << bit, target.mantissa.dtype)))
        sup.state = _replace_leaf(sup.state, target, new_m)
        self._event("bit_flip", cursor=cursor, bit=bit,
                    index=[int(i) for i in idx], old=old,
                    new=int(np.asarray(new_m[idx])))

    # -- reporting ---------------------------------------------------------
    def summary(self) -> dict:
        counts: dict = {}
        for ev in self.log:
            counts[ev["kind"]] = counts.get(ev["kind"], 0) + 1
        return {"seed": self.seed, "n_faults": len(self.faults),
                "events": list(self.log), "event_counts": counts}


def _replace_leaf(state, victim, new_mantissa):
    """Rebuild ``state`` with ``victim``'s mantissa swapped (PackedArray
    leaves are frozen dataclasses; the tree is host-side plumbing)."""
    import dataclasses as dc

    import jax

    from repro.core.packed import PackedArray

    def sub(x):
        if x is victim:
            return dc.replace(x, mantissa=new_mantissa)
        return x

    new_params = jax.tree.map(
        sub, state.params, is_leaf=lambda x: isinstance(x, PackedArray))
    return dc.replace(state, params=new_params)


def chaos_plan(seed: int, *, n_steps: int = 24, p_nan: float = 0.5,
               p_spike: float = 0.5, p_tear: float = 0.5,
               p_flip: float = 0.5, burst: int = 0) -> list:
    """Reproducible random fault mix for a train chaos sweep.

    Same seed → same plan (``random.Random(seed)``, no global state).
    Each class draws independently; ``burst > 0`` adds one GradNaN run
    of that length (longer than the default skip budget → exercises the
    rollback path, not just lone skips).
    """
    rng = random.Random(seed)
    faults: list = []
    hi = max(3, n_steps - 2)
    if rng.random() < p_nan:
        faults.append(GradNaN(step=rng.randint(2, hi)))
    if rng.random() < p_spike:
        faults.append(LossSpike(step=rng.randint(2, hi),
                                factor=float("inf")))
    if rng.random() < p_tear:
        faults.append(CkptTear(step=rng.randint(3, hi),
                               mode=rng.choice(["writer", "strip",
                                                "corrupt"])))
    if rng.random() < p_flip:
        faults.append(ParamBitFlip(step=rng.randint(2, hi),
                                   bit=rng.randint(0, 6)))
    if burst > 0:
        faults.append(GradNaN(step=rng.randint(2, hi), count=burst))
    return faults
