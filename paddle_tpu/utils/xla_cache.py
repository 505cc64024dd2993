"""Where JAX's persistent compilation cache lives — one rule for
``bench.py``, ``chip_smoke.py``, every ``benchmarks/*`` / ``tools/*``
entry point and ``tests/conftest.py``.

The directory is part of the cache's key, so it must not move between
runs: if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
this module sets NO directory in code; otherwise the cache sits at one
fixed, git-ignored path inside the checkout (``<repo>/.jax_cache``).
"""
from __future__ import annotations

import os

__all__ = ["enable_compilation_cache"]

_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Failing to create the in-checkout directory raises — a run that was
    meant to share compiles must not silently pay them all again."""
    import jax

    from_env = os.environ.get(_ENV)
    if not from_env:
        os.makedirs(_REPO_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", _REPO_DIR)
    # 0.2s: the test tier's cost is a flat tail of mid-size CPU
    # compiles; caching them is where the repeat-run win lives
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    return from_env or _REPO_DIR
