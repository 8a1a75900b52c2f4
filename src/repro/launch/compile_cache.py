"""JAX's persistent compilation cache, switched on by the entry points.

``chip_smoke.py``, ``python -m repro.launch.enumerate`` and
``python -m benchmarks.run`` call :func:`enable_compile_cache` before
their first compile; importing the library never touches the cache.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing here overrides it. Otherwise the cache lives at one fixed path in
the checkout, ``.jax_cache/`` (git-ignored) — never a temporary, per-pid
or per-time name, since a directory that moves never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the checkout root (this file is <root>/src/repro/launch/compile_cache.py)
ROOT = Path(__file__).resolve().parents[3]
CACHE_DIR = ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
