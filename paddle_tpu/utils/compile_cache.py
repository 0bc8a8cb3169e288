"""Where an entry-point script keeps jax's persistent compilation cache.

Called first thing by ``chip_smoke.py``, ``bench.py`` and
``benchmarks/serve_bench.py`` — never at library import. A chip run may
start with no compiled code at all, and the 1.3B programs take minutes to
compile cold, so every process of a run, and the next run on the same
machine, should find what an earlier one compiled.
"""
from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Returns the cache directory in use. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and nothing
    is set in code (whoever runs the program owns the placement).
    Otherwise the cache is ``<checkout>/.jax_cache``, derived from this
    file's own location: the path is part of the cache key, so a
    directory named after a pid, a time or a temp dir would never hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def cache_entries(path: str) -> int:
    """How many entries the cache directory holds (0 when absent)."""
    try:
        return len(os.listdir(path))
    except OSError:
        return 0
