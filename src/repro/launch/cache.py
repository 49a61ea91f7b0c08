"""JAX's persistent compilation cache, kept at one fixed place.

A cold run on a TPU host compiles every device shape again, which can be
most of a short run.  The cache directory is part of the cache's key, so
it must not move between runs: never a temp dir, a pid or a time in it.
Entry points call :func:`enable_compile_cache` before their first compile;
nothing calls it at import time, and the tests never call it.
"""

import os
from pathlib import Path

import jax


def checkout_cache_dir() -> Path:
    """``<checkout>/.jax_cache`` for the checkout this package is run from
    (``src/repro/launch/cache.py`` inside it).  An installed copy of the
    package has no checkout to keep a cache in: that raises."""
    src = Path(__file__).resolve().parents[2]
    root = src.parent
    if src.name != "src" or not (root / "pyproject.toml").is_file():
        raise RuntimeError(
            f"repro is not run from a checkout ({src}); set "
            "JAX_COMPILATION_CACHE_DIR to say where compiled programs go")
    return root / ".jax_cache"


def enable_compile_cache() -> str:
    """Where ``JAX_COMPILATION_CACHE_DIR`` is set, leave the cache to it
    (JAX reads that variable itself) and change no setting.  Else keep
    compiled programs in ``<checkout>/.jax_cache``.  Returns the directory
    in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = str(checkout_cache_dir())
    jax.config.update("jax_compilation_cache_dir", path)
    # keep every compile, not only those over a second: the served scorer's
    # shape buckets each compile in less, and a run compiles many of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
