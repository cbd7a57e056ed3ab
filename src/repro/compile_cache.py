"""Where JAX's persistent compilation cache lives, for the entry points.

Scripts (``chip_smoke.py``, ``examples/*.py``, ``benchmarks/run.py``,
``benchmarks/paper_sweep.py``) call :func:`use_compile_cache` once before
their first compile; importing :mod:`repro` never touches the cache.

The directory is part of the cache's key, so it is fixed: the one
``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself and
nothing else is set), otherwise ``.jax_cache`` at the root of the
checkout (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the in-checkout default when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


__all__ = ["DEFAULT_CACHE_DIR", "use_compile_cache"]
