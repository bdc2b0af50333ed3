"""Persistent XLA compile cache for the entry points.

Every process that drives the dispatcher compiles the same device programs:
the fused PTS descent per slot bucket, the per-round applies, the training
step.  The entry points (``chip_smoke.py``, ``benchmarks/run.py``, the
examples) call :func:`use_compile_cache` before their first compile so a
second process reads those programs back instead of compiling them again.
Importing the library never calls it: the tests run with JAX's defaults.
"""

from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache: a fixed path (never a temp name, pid or time), so
# every process of one checkout reads the entries the previous one wrote.
# Only the src layout (<checkout>/src/repro/core) has a checkout to put it in.
_SRC = pathlib.Path(__file__).resolve().parents[2]
CHECKOUT_CACHE_DIR = _SRC.parent / ".jax_cache" if _SRC.name == "src" else None


def use_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    no other directory is set here; otherwise the cache goes to
    ``<checkout>/.jax_cache``, and outside a checkout the variable is
    required.  Every program is cached, however quickly it compiled: the
    per-bucket descents compile in about a second each."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        if CHECKOUT_CACHE_DIR is None:
            raise RuntimeError(
                "repro is not running from a source checkout: set "
                "JAX_COMPILATION_CACHE_DIR to place the compile cache"
            )
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
