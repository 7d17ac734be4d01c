"""Where the entry points (``chip_smoke.py``, ``benchmarks/run.py``) keep
JAX's persistent compilation cache.

With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable itself and
nothing here names another directory.  Without it the cache lives at one
fixed directory inside the checkout, ``<repo>/.jax_cache`` (listed in
``.gitignore``): a cache directory that moved between runs would never be
hit, so the path is neither temporary nor per-process.  Either way every
executable is cached, however quickly it compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first compile; returns the
    directory in use."""
    import jax
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
