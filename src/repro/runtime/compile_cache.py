"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, the benchmark
CLIs) call :func:`enable_compile_cache` before their first compile.
Library modules and tests never do: importing this module changes
nothing.
"""
from __future__ import annotations

import os
import pathlib

# src/repro/runtime/compile_cache.py -> the checkout root
_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def compile_cache_dir(environ=os.environ) -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/`` at the
    root of the checkout.  The path is fixed: it is part of the cache
    key, so a temporary or per-run directory would never hit."""
    return environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(_CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on at
    :func:`compile_cache_dir` and return that directory.  Every program
    is cached, however quick its compile: a serving start-up compiles
    hundreds of small bucket programs."""
    import jax

    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
