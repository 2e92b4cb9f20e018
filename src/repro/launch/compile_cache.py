"""JAX's persistent compilation cache for the repository's entry points.

Called from the ``main`` of each runnable script, never at import, so a
library user's process keeps whatever cache policy it set itself.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["enable_compile_cache"]

#: Checkout root (``src/repro/launch`` -> three levels up).
_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: a directory that moved between runs (a
    temporary name, a pid, a time) would never be hit again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
