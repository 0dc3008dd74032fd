"""The DFXP train step (paper §5-§7, end to end).

Order of operations per step (all inside one jit program):
  1. microbatch ``lax.scan``: forward/backward with quantized activations &
     backprop signals (model-side qbound sites); accumulate mean grads,
     forward overflow stats, and sink cotangents (gradient overflow stats);
  2. optional global-norm clip;
  3. quantize accumulated weight gradients at the computation width
     (``pg:`` groups — these are the paper's "gradient" groups);
  4. optimizer math in f32 (wide accumulator hypothesis);
  5. quantize new parameters (and momentum) at the update width
     (``p:``/``pm:`` groups — the paper's 12-bit parameter updates),
     optionally with stochastic rounding (beyond-paper);
  6. max-norm constraint (paper's maxout recipe);
  7. feed every group's statistics to the overflow-rate controller; apply
     the scale-update rule every ``policy.update_interval`` steps.

In ``packed`` storage mode, parameters/momentum live as int-mantissa
``PackedArray``s; step 4 unpacks per-leaf (elementwise, fuses) and step 5
re-packs, so wide master copies never persist in HBM.

The phases run under ``jax.named_scope``s, which reach each compiled
instruction's ``op_name`` metadata (and a profiler's view of it) and
rename no instruction: ``fwd_bwd`` (1), ``grad_quant`` (2-3),
``optimizer`` (4 and the max-norm of 6), ``dfxp_store`` (5),
``controller`` (7) and, supervised, ``sentinels``.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.packed import PackedArray, pack
from repro.core.policy import PrecisionPolicy
from repro.core.quant import exact_pow2
from repro.core.scale import accumulate, controller_step
from repro.optim.opt import (OptConfig, adamw_update, apply_max_norm,
                             clip_by_global_norm, global_norm, sgd_update)

from .state import TrainState, _bexp, _path_str, unpack_tree

Array = jax.Array


def quantize_param(x: Array, width: int, e: Array, *, stochastic_key=None):
    """Quantize a parameter/gradient leaf; per-layer stats if ``e`` is [L].

    Returns (y, stats) with stats shaped ``e.shape + (3,)``.
    """
    eb = _bexp(e, x)
    step = exact_pow2(eb)
    qmax = float(2 ** (width - 1) - 1)
    qmin = -float(2 ** (width - 1))
    m = x.astype(jnp.float32) / step
    if stochastic_key is not None:
        u = jax.random.uniform(stochastic_key, m.shape, jnp.float32)
        m_r = jnp.floor(m + u)
    else:
        m_r = jnp.round(m)
    over = (m_r > qmax) | (m_r < qmin)
    over_h = (m_r > qmax / 2) | (m_r < qmin / 2)
    axes = tuple(range(jnp.ndim(e), x.ndim))
    ovf = jnp.sum(over, axis=axes, dtype=jnp.float32)
    ovfh = jnp.sum(over_h, axis=axes, dtype=jnp.float32)
    total = jnp.broadcast_to(
        jnp.float32(x.size / max(1, int(jnp.size(e)))), ovf.shape)
    y = (jnp.clip(m_r, qmin, qmax) * step).astype(x.dtype)
    return y, jnp.stack([ovf, ovfh, total], axis=-1)


def _map_with_group(fn, tree, exps: Dict[str, Array], prefix: str,
                    is_packed=False):
    """tree_map with the leaf's scale group exponent. Returns (tree', stats)."""
    stats: Dict[str, Array] = {}

    def apply(path, leaf):
        name = _path_str(path)
        e = exps[f"{prefix}{name}"]
        out, st = fn(leaf, e, name)
        stats[f"{prefix}{name}"] = st
        return out

    leaf_fn = (lambda x: isinstance(x, PackedArray)) if is_packed else None
    out = jax.tree_util.tree_map_with_path(apply, tree, is_leaf=leaf_fn)
    return out, stats


# Sentinel flag bits (metrics["flags"] in supervised mode).
FLAG_LOSS_NONFINITE = 1
FLAG_GRAD_NONFINITE = 2
FLAG_RUNAWAY_OVF = 4


def benign_injection() -> Dict[str, Array]:
    """The no-fault injection input for a supervised step."""
    return {"grad_nan": jnp.bool_(False), "loss_scale": jnp.float32(1.0)}


def make_train_step(
    loss_fn: Callable,            # (params, batch, sinks, exps) -> (loss, stats)
    group_shapes: Dict[str, tuple],
    policy: PrecisionPolicy,
    opt_cfg: OptConfig,
    *,
    microbatches: int = 1,
    compute_dtype=jnp.float32,
    grad_transform: Optional[Callable] = None,   # e.g. DFXP compression
    numerics_tap: bool = False,
    ef_transform: Optional[Callable] = None,     # (grads, ef) -> (grads, ef)
    supervise: bool = False,
    runaway_ovf: Optional[float] = None,
):
    """Build ``step(state, batch, rng) -> (state, metrics)``.

    ``numerics_tap=True`` adds a ``metrics["numerics"]`` sub-dict carrying
    the §5 controller's inputs and outputs out of the jit — per-group
    exponents before/after the controller and the window accumulators the
    decision was made from (captured BEFORE the post-apply reset).  The
    host feeds it to :func:`repro.obs.numerics.train_records` on the
    logging cadence; off (the default) the metrics pytree is unchanged.

    ``ef_transform`` threads an error-feedback state (e.g. the residual
    buffers of :func:`repro.dist.compress.compress_tree`) through the
    step: it is applied to the mean gradients and its state rides the
    signature — required so crash recovery can checkpoint the residuals
    and resume bit-exactly.

    ``supervise=True`` is the fault-tolerant variant used by
    :class:`repro.train.resilience.TrainSupervisor`.  The signature
    becomes ``step(state, batch, rng, ef, inj) -> (state, metrics, ef)``:

    * ``inj`` is a device-side fault-injection input (see
      :func:`benign_injection`): ``loss_scale`` multiplies the loss
      inside the differentiated function (a LossSpike travels through
      real gradients) and ``grad_nan`` poisons the mean gradients with
      NaN — both reach the sentinels by the same path a genuine blowup
      would, mirroring the serve engine's ``nan_mask``.
    * ``metrics["flags"]`` is an int32 sentinel bitmask computed inside
      the jit — :data:`FLAG_LOSS_NONFINITE` | :data:`FLAG_GRAD_NONFINITE`
      | :data:`FLAG_RUNAWAY_OVF` (any tensor class whose §5 overflow
      rate this step exceeds ``runaway_ovf``) — and
      ``metrics["cls_rates"]`` carries the per-tensor-class rates.  One
      extra scalar fetch per step, like serve's ``guard_logits``.
    * On a tripped sentinel the state update is discarded *on device*
      (branch-free select): params/opt/step/ef keep their old values.
      The scale state is still adopted when only the runaway flag is set
      — the §5 controller must see the overflow window to escape it —
      but never on a NaN flag.
    """
    dyn = policy.dynamic
    quant_params = policy.enabled and policy.arithmetic in ("fixed", "dfxp")

    def _impl(state: TrainState, batch, rng: Array, ef, inj):
        sinks = {n: jnp.zeros(s + (3,), jnp.float32)
                 for n, s in group_shapes.items() if n.startswith("g:")}

        # ---- unpack storage (packed mode) --------------------------------
        if policy.storage == "packed":
            params_c = unpack_tree(state.params, compute_dtype)
            mom_c = unpack_tree(state.opt, jnp.float32)
        else:
            params_c = state.params
            mom_c = state.opt

        # ---- grads over microbatches --------------------------------------
        exps = state.scale.exps

        def loss_wrap(p, s, b):
            loss, st = loss_fn(p, b, s, exps)
            if inj is not None:
                # LossSpike rides through AD: scaled loss => scaled grads
                loss = loss * inj["loss_scale"]
            return loss, st

        # the forward and backward pass, traced inside the call
        grad_fn = jax.named_scope("fwd_bwd")(jax.value_and_grad(
            loss_wrap, argnums=(0, 1), has_aux=True))

        if microbatches > 1:
            for key in ("labels", "y", "tokens", "x"):
                if key in batch:
                    B = batch[key].shape[0]
                    break
            else:
                raise ValueError("cannot infer batch axis for microbatching")

            def to_micro(x):
                if x.shape[0] == B:
                    return x.reshape((microbatches, B // microbatches)
                                     + x.shape[1:])
                # leaves with batch on axis 1 (e.g. M-RoPE positions [3,B,S])
                assert x.ndim >= 2 and x.shape[1] == B, x.shape
                y = x.reshape((x.shape[0], microbatches, B // microbatches)
                              + x.shape[2:])
                return jnp.moveaxis(y, 1, 0)

            mb = jax.tree.map(to_micro, batch)

            def body(carry, b):
                (loss_a, g_a, s_a, st_a) = carry
                (loss, st), (g, gs) = grad_fn(params_c, sinks, b)
                st_new = {k: st_a[k] + st.get(k, 0.0) for k in st_a}
                return (loss_a + loss,
                        jax.tree.map(jnp.add, g_a, g),
                        jax.tree.map(jnp.add, s_a, gs),
                        st_new), None

            z_g = jax.tree.map(jnp.zeros_like, params_c)
            z_s = jax.tree.map(jnp.zeros_like, sinks)
            st0 = {n: jnp.zeros(s + (3,), jnp.float32)
                   for n, s in group_shapes.items()
                   if n.startswith(("a:", "w:"))}
            (loss, grads, sink_stats, fwd_stats), _ = jax.lax.scan(
                body, (jnp.float32(0), z_g, z_s, st0), mb)
            loss = loss / microbatches
            grads = jax.tree.map(lambda g: g / microbatches, grads)
        else:
            (loss, fwd_stats), (grads, sink_stats) = grad_fn(params_c, sinks,
                                                             batch)

        # ---- gradient processing ------------------------------------------
        with jax.named_scope("grad_quant"):
            if inj is not None:
                poison = jnp.where(inj["grad_nan"], jnp.float32(jnp.nan),
                                   jnp.float32(0.0))
                grads = jax.tree.map(lambda g: g + poison.astype(g.dtype),
                                     grads)

            if grad_transform is not None:
                grads = grad_transform(grads)

            new_ef = ef
            if ef_transform is not None:
                grads, new_ef = ef_transform(grads, ef)

            gnorm = global_norm(grads)
            if opt_cfg.grad_clip:
                grads, _ = clip_by_global_norm(grads, opt_cfg.grad_clip)

            all_stats: Dict[str, Array] = {}
            for d in (fwd_stats, sink_stats):
                for k, v in d.items():
                    key = k if not k.startswith("g:") else k
                    all_stats[key] = all_stats.get(key, 0) + v

            if quant_params:
                grads, gstats = _map_with_group(
                    lambda g, e, n: quantize_param(g, policy.comp_width, e),
                    grads, state.scale.exps, "pg:")
                all_stats.update(gstats)

        # ---- optimizer (wide math) ----------------------------------------
        with jax.named_scope("optimizer"):
            if opt_cfg.kind == "sgd":
                updates, new_opt = sgd_update(opt_cfg, grads, mom_c,
                                              state.step)
            else:
                updates, new_opt = adamw_update(opt_cfg, grads, mom_c,
                                                state.step, params=params_c)

            new_params = jax.tree.map(lambda p, u: (p.astype(jnp.float32)
                                                    + u).astype(jnp.float32),
                                      params_c, updates)
            if opt_cfg.max_col_norm:
                new_params = apply_max_norm(new_params, opt_cfg.max_col_norm)

        # ---- parameter/momentum storage quantization ----------------------
        with jax.named_scope("dfxp_store"):
            def q_store(x, e, name, key=None):
                sk = None
                if policy.stochastic_rounding:
                    sk = jax.random.fold_in(rng, hash(name) % (2 ** 31))
                return quantize_param(x, policy.update_width, e,
                                      stochastic_key=sk)

            if quant_params:
                if policy.storage == "packed":
                    def pk(x, e, name):
                        y, st = q_store(x, e, name)
                        return pack(y, policy.update_width, _bexp(e, y)), st
                    new_params, pstats = _map_with_group(
                        pk, new_params, state.scale.exps, "p:")
                    all_stats.update(pstats)
                    if policy.quantize_momentum and opt_cfg.kind == "sgd":
                        new_mom, mstats = _map_with_group(
                            pk, new_opt["momentum"], state.scale.exps, "pm:")
                        new_opt = {"momentum": new_mom}
                        all_stats.update(mstats)
                else:
                    new_params, pstats = _map_with_group(
                        q_store, new_params, state.scale.exps, "p:")
                    all_stats.update(pstats)
                    if policy.quantize_momentum and opt_cfg.kind == "sgd":
                        new_mom, mstats = _map_with_group(
                            q_store, new_opt["momentum"], state.scale.exps,
                            "pm:")
                        new_opt = {"momentum": new_mom}
                        all_stats.update(mstats)
            elif policy.enabled:
                # float emulation of the storage format (fp16/bf16/fp8 rows)
                from repro.core.quant import float_round
                fmt = policy.update_format()
                new_params = jax.tree.map(lambda x: float_round(x, fmt),
                                          new_params)

        # ---- scale controller ----------------------------------------------
        with jax.named_scope("controller"):
            new_scale = state.scale
            acc_window = None
            if dyn:
                new_scale = accumulate(new_scale, all_stats)
                acc_window = new_scale.acc    # pre-reset §5 window accums
                apply = (state.step + 1) % policy.update_interval == 0
                new_scale = controller_step(
                    new_scale, max_overflow_rate=policy.max_overflow_rate,
                    apply=apply)

        metrics = {"loss": loss, "grad_norm": gnorm,
                   "step": state.step.astype(jnp.float32)}
        if numerics_tap:
            metrics["numerics"] = {
                "prev_exps": state.scale.exps,
                "exps": new_scale.exps,
                "acc": acc_window if acc_window is not None else {},
            }

        new_state = TrainState(params=new_params, opt=new_opt,
                               scale=new_scale, step=state.step + 1)

        if supervise:
            with jax.named_scope("sentinels"):
                from repro.core.tape import tensor_class
                bad_loss = ~jnp.isfinite(loss)
                bad_grad = ~jnp.isfinite(gnorm)
                cls_ovf: Dict[str, Array] = {}
                cls_tot: Dict[str, Array] = {}
                for gname, st in all_stats.items():
                    c = tensor_class(gname)
                    cls_ovf[c] = cls_ovf.get(c, 0.0) + jnp.sum(st[..., 0])
                    cls_tot[c] = cls_tot.get(c, 0.0) + jnp.sum(st[..., 2])
                cls_rates = {c: cls_ovf[c] / jnp.maximum(cls_tot[c], 1.0)
                             for c in sorted(cls_ovf)}
                runaway = jnp.bool_(False)
                if runaway_ovf is not None and cls_rates:
                    runaway = (jnp.stack(list(cls_rates.values())).max()
                               > runaway_ovf)
                flags = (bad_loss.astype(jnp.int32) * FLAG_LOSS_NONFINITE
                         + bad_grad.astype(jnp.int32) * FLAG_GRAD_NONFINITE
                         + runaway.astype(jnp.int32) * FLAG_RUNAWAY_OVF)
                metrics["flags"] = flags
                metrics["cls_rates"] = cls_rates

                # Discard a tripped step's update on device: SKIPPED costs no
                # extra host round-trip before the next step can launch.
                nan_bad = bad_loss | bad_grad
                any_bad = nan_bad | runaway

                def sel(pred, old, new):
                    return jax.tree.map(lambda a, b: jnp.where(pred, a, b),
                                        old, new)

                new_state = TrainState(
                    params=sel(any_bad, state.params, new_state.params),
                    opt=sel(any_bad, state.opt, new_state.opt),
                    # runaway-only: keep the new scale so the §5 controller
                    # can move the exponent out of the overflow regime
                    scale=sel(nan_bad, state.scale, new_state.scale),
                    step=jnp.where(any_bad, state.step, new_state.step))
                new_ef = sel(any_bad, ef, new_ef)

        return new_state, metrics, new_ef

    if supervise:
        def step(state: TrainState, batch, rng: Array, ef, inj):
            return _impl(state, batch, rng, ef, inj)
    elif ef_transform is not None:
        def step(state: TrainState, batch, rng: Array, ef):
            return _impl(state, batch, rng, ef, None)
    else:
        def step(state: TrainState, batch, rng: Array):
            out_state, metrics, _ = _impl(state, batch, rng, {}, None)
            return out_state, metrics

    return step
