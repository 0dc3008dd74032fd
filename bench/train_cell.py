"""A training cell: the program's supervised DFXP step, driven closed
loop over seeded rows.

Set-up makes the weights from the seed, runs the launcher's calibration
(paper section 9.3, every DFXP job pays it once), builds one
:class:`repro.train.TrainSupervisor` and drives it through its first
three steps, reading what the check compares.  The window then keeps
calling the same supervisor's ``step_once``; afterwards the plain
reference follows the first three steps from the same weights.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops, loadgen, weights

CHECK_STEPS = 3


def leaf_norms(tree) -> dict:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        out[name] = float(jnp.sqrt(jnp.sum(jnp.square(
            leaf.astype(jnp.float32)))))
    return out


def diff_norms(a, b) -> dict:
    """Per-leaf norm of ``a - b`` (``b`` may be host arrays)."""
    return leaf_norms(jax.tree.map(lambda x, y: x - jnp.asarray(y), a, b))


class Cell:
    """``config``: a configuration file's content; ``mix``: the traffic
    file's.  ``policy_overrides`` runs the program at another precision
    (the control, or its own wider paths); ``fault`` plants a fault for the harness's own tests
    and readings (``"half_batch"``, ``"frozen"``)."""

    def __init__(self, config: dict, mix: dict, seed: int, *,
                 policy_overrides: dict | None = None, fault: str = ""):
        self.config, self.mix, self.seed = config, mix, seed
        self.model = config["model"]
        self.policy_overrides = policy_overrides or {}
        self.fault = fault
        self.rows = loadgen.BigramRows(mix["vocab_ids"], mix["batch"],
                                       mix["seq_len"], seed)
        self.tokens_per_step = mix["batch"] * mix["seq_len"]
        self.readings: dict = {}
        self.sup = None

    # -- program ----------------------------------------------------------
    def _program(self):
        from repro import configs
        from repro.core.policy import PrecisionPolicy
        from repro.models import transformer as T
        from repro.optim.opt import OptConfig
        smoke = self.config.get("preset") == "smoke"
        cfg = (configs.get_smoke if smoke else configs.get)(
            self.config["repro_config"])
        pol = PrecisionPolicy(**{**self.config["policy"],
                                 **self.policy_overrides})
        opt = OptConfig(**self.config["optimizer"])
        return cfg, pol, opt, T

    def batch(self, cursor: int) -> dict:
        b = self.rows.batch(cursor)
        if self.fault == "half_batch":
            # planted fault: half the rows (half the row's tokens, where
            # the batch is one row) never reach the step, the mean is
            # taken over the rest
            if self.mix["batch"] > 1:
                b = {k: v[: v.shape[0] // 2] for k, v in b.items()}
            else:
                b = {k: v[:, : v.shape[1] // 2] for k, v in b.items()}
        with jax.profiler.TraceAnnotation("bench:batch"):
            return {k: jnp.asarray(v) for k, v in b.items()}

    def setup(self) -> None:
        import dataclasses as dc

        from repro.train import TrainSupervisor, init_train_state
        from repro.train.calibrate import calibrate
        from repro.optim.opt import sgd_init
        cfg, pol, opt, T = self._program()
        self.cfg = cfg
        gs = T.group_shapes(cfg)
        kind = self.config["reference"]
        params = weights.make_params(kind, self.model, self.seed)
        weights.check_program_config(self.config, cfg)
        want = jax.eval_shape(lambda: T.init_params(cfg,
                                                    jax.random.PRNGKey(0)))
        got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
        if jax.tree.map(lambda x: (x.shape, x.dtype), want) != got:
            raise ValueError("configuration file and program disagree on "
                             "the parameter shapes")

        def loss_fn(p, b, s, exps):
            return T.loss_fn(cfg, pol, p, b, exps, s)

        init_exp = -8.0
        n_cal = self.config["calibration"]["steps"]
        if pol.dynamic and n_cal:
            obs = dc.replace(pol, arithmetic="observe", storage="sim")

            def obs_loss(p, b, s, exps):
                return T.loss_fn(cfg, obs, p, b, exps, s)

            init_exp = calibrate(obs_loss, params, gs, pol, opt,
                                 (self.batch(i) for i in range(n_cal)),
                                 steps=n_cal)
        state = init_train_state(params, sgd_init(params), gs, pol,
                                 init_exp=init_exp)
        del params
        p0 = jax.tree.map(jnp.copy, state.params)
        self.sup = TrainSupervisor(
            loss_fn, gs, pol, opt, state, batch_fn=self.batch,
            rng=weights.seed_key(self.seed))
        del state
        if self.fault == "frozen":
            self._plant_frozen_step()
        losses, outcomes = [], []
        for i in range(CHECK_STEPS):
            rec = self.sup.step_once()
            losses.append(rec.loss)
            outcomes.append(rec.outcome.value)
            if i == 0:
                # the first gradient as the optimizer took it: SGD's
                # momentum after one step from zero (kept on the host
                # until the reference has run)
                self.first_grad = jax.device_get(
                    self.sup.state.opt["momentum"])
        self.readings["loss"] = losses
        self.readings["outcomes"] = outcomes
        self.update = jax.device_get(jax.tree.map(
            lambda a, b: a - b, self.sup.state.params, p0))
        del p0

    def _plant_frozen_step(self):
        """Planted fault: the step returns the state it was given."""
        inner = self.sup._step_fn

        def frozen(state, batch, rng, ef, inj):
            _, metrics, ef_new = inner(state, batch, rng, ef, inj)
            return state, metrics, ef_new

        self.sup._step_fn = frozen

    # -- window -------------------------------------------------------------
    def window(self, seconds: float, poll) -> None:
        """Closed loop of ``step_once`` until ``seconds`` have passed;
        ``poll`` runs once an iteration (the harness's profiler)."""
        from repro.train import StepOutcome
        steps = ok = 0
        self.step_ends = []
        t0 = time.perf_counter()
        while True:
            poll()
            with jax.profiler.TraceAnnotation("bench:step_once"):
                rec = self.sup.step_once()
            steps += 1
            ok += rec.outcome is StepOutcome.OK
            t1 = time.perf_counter()
            self.step_ends.append(t1)
            if t1 - t0 >= seconds:
                break
        self._result = (steps, ok, t1 - t0)

    def drain(self) -> dict:
        steps, ok, span = self._result
        return {"attempted": steps, "failed": steps - ok,
                "metrics": {"train_tokens_per_s":
                            ok * self.tokens_per_step / span},
                "ctx": {"steps": steps, "window_s": span,
                        "step_ends": self.step_ends,
                        "tokens_per_step": self.tokens_per_step,
                        "tokens": ok * self.tokens_per_step,
                        "flops_per_token":
                            flops.mamba2_train_flops_per_token(self.model),
                        "qmatmul_step": flops.qmatmul_step_work(
                            self.model, self.tokens_per_step)}}

    def release(self) -> None:
        self.sup = None
        gc.collect()

    # -- check ----------------------------------------------------------------
    def reference(self) -> dict:
        """The plain reference's three steps from the same weights and
        rows: losses, first gradient and change of the parameters."""
        ref = weights.reference(self.config["reference"])
        params = weights.make_params(self.config["reference"], self.model,
                                     self.seed)
        batches = [{k: jnp.asarray(v) for k, v in
                    self.rows.batch(i).items()} for i in range(CHECK_STEPS)]
        pol = {**self.config["policy"], **self.policy_overrides}
        cal = self.config["calibration"]
        storage = ({"width": pol["update_width"],
                    "margin_bits": cal["margin_bits"]}
                   if pol["arithmetic"] == "dfxp" else None)
        losses, g1, p0, p3 = ref.train(
            params, batches, self.model, self.config["optimizer"],
            storage=storage, calibration_batches=batches[:cal["steps"]])
        del params
        dp = jax.tree.map(lambda a, b: a - b, p3, p0)
        del p0, p3
        out = {"loss": losses,
               "grad": leaf_norms(g1), "update": leaf_norms(dp),
               "grad_diff": diff_norms(g1, self.first_grad),
               "update_diff": diff_norms(dp, self.update)}
        del g1, dp
        gc.collect()
        return out

    def numbers(self, ref: dict, detail: bool = False) -> dict:
        prog = {"loss": self.readings["loss"],
                "grad": leaf_norms(self.first_grad),
                "update": leaf_norms(self.update)}
        return compare_training(prog, ref, detail)


def leaf_shares(diff: dict, ref: dict, keep=None) -> dict:
    """Per leaf, ``diff / max(|ref|, median |ref|)``, the median over the
    leaves the reference moves.  ``diff`` is either the norm of the
    program's leaf less the reference's (a difference norm) or the gap
    between the two norms."""
    names = [n for n in ref if keep is None or n in keep]
    med = float(np.median([ref[n] for n in names if ref[n] > 0]))
    return {n: diff[n] / max(ref[n], med) for n in names}


def norm_gaps(prog: dict, ref: dict) -> dict:
    return {n: abs(prog[n] - ref[n]) for n in ref}


def compare_training(prog: dict, ref: dict, detail: bool = False) -> dict:
    """The numbers a training cell reads of the first gradient and the
    parameters' change: the worst leaf's gap between norms, and the
    worst and the median leaf's difference norm, beside the worst step's
    loss gap.  ``bench/limits/<cell>.json`` names those compared
    (PERF.md, section 2).  ``detail`` adds every leaf's share and the
    worst leaves."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"],
                                                        ref["loss"]))
    med_g = float(np.median(list(ref["grad"].values())))
    # leaves whose reference gradient is nought to rounding move under
    # the optimizer by round-off alone: their change is not compared
    moving = {n for n, g in ref["grad"].items() if g >= 1e-3 * med_g}
    shares = {
        "grad_gap": leaf_shares(norm_gaps(prog["grad"], ref["grad"]),
                                ref["grad"]),
        "update_gap": leaf_shares(norm_gaps(prog["update"], ref["update"]),
                                  ref["update"], keep=moving),
        "grad_diff": leaf_shares(ref["grad_diff"], ref["grad"]),
        "update_diff": leaf_shares(ref["update_diff"], ref["update"],
                                   keep=moving)}
    out = {"loss_gap": loss_gap}
    for name, per_leaf in shares.items():
        out[name] = max(per_leaf.values())
    out["grad_diff_med"] = float(np.median(list(
        shares["grad_diff"].values())))
    out["update_diff_med"] = float(np.median(list(
        shares["update_diff"].values())))
    if detail:
        for name, per_leaf in shares.items():
            out[f"{name}_worst"] = max(per_leaf, key=per_leaf.get)
            out[f"{name}_leaves"] = {n: round(v, 5)
                                     for n, v in per_leaf.items()}
    return out
