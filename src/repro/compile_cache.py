"""Where a checkout keeps what it generates at run time: ``<checkout>/.cache``.

JAX's persistent compilation cache is keyed by its directory among other
things, so a directory that moves between runs never hits.  The entry
points (``repro.launch.train``, ``repro.launch.serve``,
``benchmarks/run.py``, ``chip_smoke.py``) call
:func:`use_compile_cache` once at start-up; importing this module
changes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".cache"
_ENV = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed.  Otherwise the cache goes to the fixed path
    ``<checkout>/.cache/jax``.
    """
    env = os.environ.get(_ENV)
    if env:
        return env
    path = str(CACHE_DIR / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
