"""Seeded model weights, made on the device in one jitted call.

The benchmark makes the weights itself, from ``--seed``, in the pytree
layout the program takes (``params["stages"][stage]["stacked"][block]``)
and at the scales of the program's own initialisation, so that the
program and the plain reference start from the same numbers without the
reference taking anything the program made.  Each leaf is drawn by a
rule on its name:

* norm gains and the SSM skip ``D``: ones; convolution bias: zeros;
* ``A_log``: log of 1..16 spread over the heads; ``dt_bias``: inverse
  softplus of a log-uniform step in [1e-3, 1e-1];
* embedding table and MoE router: N(0, 0.02²);
* every other matrix: N(0, 1/fan_in), fan-in the second-to-last axis.
"""
from __future__ import annotations

import importlib
import math

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any non-negative integer seed (wider than 32 bits)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def reference(kind: str):
    """The plain reference module of model family ``kind``
    (``bench/reference/<kind>.py``), found by name: it gives the
    family's parameter shapes (``shapes``) and its equations."""
    return importlib.import_module(f"bench.reference.{kind}")


def _leaf(name: str, shape, key):
    if name in ("norm", "final_norm", "norm_w", "D"):
        return jnp.ones(shape, jnp.float32)
    if name == "conv_b":
        return jnp.zeros(shape, jnp.float32)
    if name == "A_log":
        return jnp.broadcast_to(
            jnp.log(jnp.linspace(1.0, 16.0, shape[-1], dtype=jnp.float32)),
            shape)
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return jnp.log(jnp.expm1(dt))
    scale = 0.02 if name in ("embed", "router") else 1.0 / math.sqrt(shape[-2])
    return jax.random.normal(key, shape, jnp.float32) * scale


def _is_shape(x):
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def make_params(kind: str, model: dict, seed: int):
    """The weights of configuration ``model`` (a config file's ``model``
    block) for reference ``kind``, on the default device, from ``seed``."""
    shapes = reference(kind).shapes(model)
    paths = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_shape)[0]

    def build(key):
        leaves = []
        for i, (path, shape) in enumerate(paths):
            name = getattr(path[-1], "key", str(path[-1]))
            leaves.append(_leaf(name, shape, jax.random.fold_in(key, i)))
        treedef = jax.tree_util.tree_structure(shapes, is_leaf=_is_shape)
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(seed_key(seed))


def abstract_params(kind: str, model: dict):
    """``jax.ShapeDtypeStruct`` tree of :func:`make_params`, nothing made."""
    return jax.eval_shape(lambda: make_params(kind, model, 0))


def check_program_config(config: dict, program_cfg) -> None:
    """Raise where the program's model configuration disagrees with the
    configuration file on a size the file maps to a program field
    (``program_fields``: file key -> program field)."""
    bad = {k: (config["model"][k], getattr(program_cfg, f))
           for k, f in config["program_fields"].items()
           if config["model"][k] != getattr(program_cfg, f)}
    if bad:
        raise ValueError(f"configuration file and program disagree "
                         f"(file, program): {bad}")
