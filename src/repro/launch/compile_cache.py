"""JAX's persistent compilation cache, placed from outside the program.

Entry points that compile for the device (``chip_smoke.py``, the train and
serve launchers, the spec CLI's ``run``) call :func:`enable_compile_cache`
once, before their first compile.  Library code, tests and imports never
do: turning the cache on is a decision of whoever runs the process.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it at import and keeps its
  cache there; nothing here overrides it.
* Otherwise the cache lives at the fixed ``<checkout>/.jax_cache`` (listed
  in ``.gitignore``).  The path is part of what a cached entry is found
  by, so it must not move between runs.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

#: ``<checkout>/.jax_cache`` — this file sits at ``src/repro/launch/``.
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
