"""JAX's persistent compilation cache at one fixed path per checkout.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks.run``)
call :func:`use_persistent_cache` once at start-up; tests never do.  The
cache key includes the directory, so a path that moves between runs never
hits: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
nothing is set here; otherwise the cache lives in ``.jax_cache/`` at the
root of the checkout (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_persistent_cache() -> str:
    """Point JAX's compilation cache at its directory; return that path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
