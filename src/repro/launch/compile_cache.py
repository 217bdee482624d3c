"""JAX's persistent compilation cache, for entry points.

Call :func:`enable_compile_cache` first thing in a program's ``main``
(never at import): a fresh process then reuses the executables an
earlier run compiled.  ``JAX_COMPILATION_CACHE_DIR``, when set, is read
by JAX itself and wins; otherwise the cache lives at the fixed path
``<repo>/.jax_cache`` (ignored by git).  The path is part of what the
cache matches on, so it must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<repo>/.jax_cache``, next to ``src/``.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
