"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points (``chip_smoke.py``, ``repro.launch.*``, ``benchmarks/run.py``)
call :func:`use_compile_cache` from their ``main()``; importing a module
never touches the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def use_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing.  Otherwise the cache goes to ``.jax_cache/`` at the
    checkout root: a fixed path, so the next process finds its entries.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
