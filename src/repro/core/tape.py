"""QTape — the per-trace quantization context model code writes against.

A layer function receives a tape scoped to its own scale/sink slices and
calls ``tape.act(name, x)`` after every weighted sum / nonlinearity and
``tape.weight(name, w)`` when a stored parameter enters a multiplication.
The tape records forward overflow statistics; backward statistics arrive via
sink cotangents (see :mod:`repro.core.quant`). Layer functions return
``tape.stats`` explicitly so ``lax.scan`` stacks them per layer.

Group naming convention (mirrors the paper's per-layer groups):
  ``a:<site>`` activation scale, ``g:<site>`` gradient scale,
  ``w:<name>`` weight use-time scale, ``p:<name>`` parameter-storage scale.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from .policy import MATMUL_PRECISION, PrecisionPolicy
from .quant import q_stats, qbound, ste_quant

Array = jax.Array

# Group-prefix → tensor-class names, the paper's §3 breakdown plus the
# optimizer-side groups train/state.py adds ("pg:" gradient-of-parameter,
# "pm:" momentum).  repro.obs aggregates numeric-health series per class.
_TENSOR_CLASSES = {
    "a": "activation",
    "g": "gradient",
    "w": "weight",
    "p": "param",
    "pg": "param_grad",
    "pm": "momentum",
}


def tensor_class(group: str) -> str:
    """Tensor class of a tape group name (``"a:mlp_out"`` → ``"activation"``)."""
    prefix = group.split(":", 1)[0]
    return _TENSOR_CLASSES.get(prefix, prefix)


class QTape:
    def __init__(
        self,
        policy: PrecisionPolicy,
        scales: Dict[str, Array],
        sinks: Dict[str, Array],
    ):
        self.policy = policy
        self.scales = scales
        self.sinks = sinks
        self.stats: Dict[str, Array] = {}

    # -- helpers --------------------------------------------------------
    def _exp(self, group: str) -> Array:
        return self.scales.get(group, jnp.float32(0.0))

    def _record(self, group: str, stats: Array) -> None:
        if group in self.stats:
            self.stats[group] = self.stats[group] + stats
        else:
            self.stats[group] = stats

    # -- quantization sites ----------------------------------------------
    def act(self, name: str, x: Array) -> Array:
        """Activation site: fwd quant at comp width, bwd cotangent quant too."""
        pol = self.policy
        if not pol.enabled:
            return x
        fmt = pol.comp_format()
        a_e, g_e = self._exp(f"a:{name}"), self._exp(f"g:{name}")
        sink = self.sinks.get(f"g:{name}")
        if sink is None:
            sink = jnp.zeros((3,), jnp.float32)
        y = qbound(x, fmt, fmt, a_e, g_e, sink)
        if pol.dynamic or pol.observing:
            self._record(f"a:{name}", q_stats(x, fmt, a_e))
        return y

    def weight(self, name: str, w: Array) -> Array:
        """Weight use-time site: re-quantize storage-width param to comp width.

        Straight-through backward — the weight gradient is quantized once,
        in the train step, with its own ``p:`` group statistics.
        """
        pol = self.policy
        if not pol.enabled:
            return w
        fmt = pol.comp_format()
        e = self._exp(f"w:{name}")
        y = ste_quant(w, fmt, e)
        if pol.dynamic or pol.observing:
            self._record(f"w:{name}", q_stats(w, fmt, e))
        return y

    def state(self, name: str, x: Array, record: bool = True) -> Array:
        """Recurrent-state site: quantized at the *update* width (paper §6 —
        states, like parameters, accumulate many small contributions).

        Pass ``record=False`` when calling from inside an inner ``lax.scan``
        body (stats recorded there would leak tracers out of the scan); then
        record once afterwards with :meth:`record_state_stats` on the stacked
        values.
        """
        pol = self.policy
        if not pol.enabled:
            return x
        fmt = pol.update_format()
        a_e, g_e = self._exp(f"a:{name}"), self._exp(f"g:{name}")
        sink = self.sinks.get(f"g:{name}")
        if sink is None:
            sink = jnp.zeros((3,), jnp.float32)
        y = qbound(x, fmt, fmt, a_e, g_e, sink)
        if (pol.dynamic or pol.observing) and record:
            self._record(f"a:{name}", q_stats(x, fmt, a_e))
        return y

    def record_state_stats(self, name: str, x: Array) -> None:
        pol = self.policy
        if pol.enabled and (pol.dynamic or pol.observing):
            self._record(f"a:{name}",
                         q_stats(x, pol.update_format(), self._exp(f"a:{name}")))

    def dot(self, name: str, x: Array, w: Array, *,
            transpose_b: bool = False) -> Array:
        """Quantized matmul: weight re-quantized to comp width, wide accumulate.

        Operands are cast to ``x.dtype`` (the policy's compute container);
        accumulation is f32 — the MXU contract / paper §7.  ``transpose_b``
        contracts against ``w``'s last dim (the tied-lm-head layout).

        With ``policy.fused_matmul`` set under DFXP arithmetic, the whole
        site — weight rounding, matmul, dgrad, wgrad — runs as one fused
        Pallas kernel per pass (:mod:`repro.kernels.dispatch`), bit-identical
        to the composite below; stats recording is unchanged.
        """
        pol = self.policy
        if pol.dynamic and pol.fused_matmul:
            from repro.kernels.dispatch import tape_dot
            fmt = pol.comp_format()
            e = self._exp(f"w:{name}")
            y = tape_dot(x, w, e, width=fmt.width, transpose_b=transpose_b)
            self._record(f"w:{name}", q_stats(w, fmt, e))
            return y
        wq = self.weight(name, w).astype(x.dtype)
        if transpose_b:
            y = jnp.einsum("...d,vd->...v", x, wq,
                           precision=MATMUL_PRECISION,
                           preferred_element_type=jnp.float32)
        else:
            y = jnp.matmul(x, wq, precision=MATMUL_PRECISION,
                           preferred_element_type=jnp.float32)
        return y.astype(x.dtype)


def null_tape(policy: PrecisionPolicy) -> QTape:
    """Tape with default scales — for fp32/float-emulation paths."""
    return QTape(policy, {}, {})
