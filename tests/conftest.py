"""Fixtures shared by the serving tests."""
import jax.numpy as jnp
import numpy as np
import pytest

# How far below the float32 model's best logit an int8-pool greedy token
# may lie, as a share of max|logit|.  The int8 pool rounds each K/V entry
# to a power-of-two step at most 2**-8 of its slot's largest magnitude;
# that error, compounded through the layers, moves logits by a few times
# 2**-8 of their scale, so the int8 stream may take another token where
# the float32 top two nearly tie.  A wrong cache (stale rows, a bad mask,
# a mis-scaled step) picks tokens a sizable share of the logit range
# below the best.
PACKED_REGRET = 2.0 ** -4


@pytest.fixture
def assert_near_f32_greedy():
    """``check(cfg, params, prompt, tokens)``: every generated token is
    within :data:`PACKED_REGRET` of the float32 model's best logit,
    teacher-forced along ``tokens`` through an uncached forward pass."""
    from repro.core import ScaleState
    from repro.core.policy import PrecisionPolicy
    from repro.models import transformer as T

    pol = PrecisionPolicy("float32")

    def check(cfg, params, prompt, tokens):
        prompt, tokens = np.asarray(prompt), np.asarray(tokens)
        gs = T.group_shapes(cfg)
        sinks = {n: jnp.zeros(s + (3,), jnp.float32)
                 for n, s in gs.items() if n.startswith("g:")}
        seq = np.concatenate([prompt, tokens[:-1]])[None]
        logits, _, _ = T.forward(cfg, pol, params,
                                 {"tokens": jnp.asarray(seq)},
                                 ScaleState.create(gs, -6.0).exps, sinks,
                                 mode="train")
        lg = np.asarray(logits[0, len(prompt) - 1:])
        chosen = lg[np.arange(len(tokens)), tokens]
        regret = (lg.max(-1) - chosen) / np.abs(lg).max()
        assert np.all(regret <= PACKED_REGRET), (
            f"greedy tokens {tokens.tolist()} fall {regret.tolist()} of "
            f"max|logit| below the float32 best (limit {PACKED_REGRET})")

    return check


def _dot_precisions(jaxpr):
    """``precision`` of every ``dot_general`` in ``jaxpr`` and in the
    jaxprs its equations carry (jit, scan, cond, custom VJPs, Pallas
    kernel bodies)."""
    from jax.extend import core as jc

    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn.params["precision"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, jc.ClosedJaxpr):
                    out += _dot_precisions(sub.jaxpr)
                elif isinstance(sub, jc.Jaxpr):
                    out += _dot_precisions(sub)
    return out


@pytest.fixture
def assert_dots_full_precision():
    """``check(fn, *args)``: ``fn`` traces at least one dot, and every
    dot it traces runs at :data:`repro.core.policy.MATMUL_PRECISION`
    (one bf16 MXU pass would round 10-bit DFXP operands on a TPU)."""
    import jax

    from repro.core.policy import MATMUL_PRECISION

    def check(fn, *args):
        precs = _dot_precisions(jax.make_jaxpr(fn)(*args).jaxpr)
        assert precs, "no dot traced"
        bad = [p for p in precs
               if p != (MATMUL_PRECISION, MATMUL_PRECISION)]
        assert not bad, f"{len(bad)} of {len(precs)} dots at {bad[:3]}"

    return check
