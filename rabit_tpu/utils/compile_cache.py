"""Where JAX keeps its persistent compile cache.

Called by the entry scripts (``chip_smoke.py``, ``bench.py``), never
when the library is imported: the cache is placed from outside. A
``JAX_COMPILATION_CACHE_DIR`` in the environment wins and JAX reads it
itself; otherwise the cache goes to one fixed directory in the checkout
(listed in ``.gitignore``). The path is part of the cache key, so it is
never built from a temporary name, a pid or the time.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"


def setup(repo_root: str) -> str:
    """Point JAX's persistent compile cache at its directory and return
    that directory."""
    path = os.environ.get(ENV)
    if path:
        return path
    import jax
    path = os.path.join(os.path.abspath(repo_root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
