"""Where JAX keeps its persistent compilation cache.

One rule for every entry point that compiles for the device
(``chip_smoke.py``; ``bench/run.py`` keeps its own copy): when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it into
``jax_compilation_cache_dir`` and that path stands; otherwise the cache
goes to ``<repo>/.jax_cache``.  The path is part of the cache key, so it
is fixed — never a temp name, pid or timestamp — and a later process in
the same checkout finds what an earlier one compiled.

Call :func:`use_compile_cache` before the first compilation: JAX opens
the cache once, on first use.
"""

from __future__ import annotations

import os

import jax

__all__ = ["REPO_CACHE_DIR", "use_compile_cache"]

#: <repo>/.jax_cache (this file is <repo>/src/repro/compile_cache.py)
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))),
    ".jax_cache",
)


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    it: the one ``JAX_COMPILATION_CACHE_DIR`` named, else
    :data:`REPO_CACHE_DIR`."""
    configured = jax.config.jax_compilation_cache_dir
    if configured:
        return configured
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
