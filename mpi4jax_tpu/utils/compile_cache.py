"""Where JAX keeps its persistent compilation cache for this checkout.

A path is part of the cache's key, so it has to stay put from one run to
the next: ``JAX_COMPILATION_CACHE_DIR`` where the environment sets it,
else ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
import sys

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def use_compile_cache() -> str:
    """Resolve the cache directory, export it, and return it.

    The path goes into ``os.environ`` so that child processes (the
    launcher's ranks inherit the environment) share the same cache, and
    into ``jax.config`` when jax is already imported.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _REPO_ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
