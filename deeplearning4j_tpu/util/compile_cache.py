"""Where JAX's persistent compilation cache lives.

The cache's path is part of its key, so a directory that moves never
hits. Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it and this
sets nothing; where it is not, the cache goes to one fixed directory
inside the checkout (`<repo>/.jax_cache`, git-ignored) — never a path
built from tempfile, a pid or the time. Called by the processes that
compile for the chip: chip_smoke.py, bench.py's mode children and the
CLI.
"""

from __future__ import annotations

import os

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    placed = os.environ.get(ENV_CACHE_DIR)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
