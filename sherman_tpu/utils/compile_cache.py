"""The one persistent-compilation-cache rule every entry point follows.

``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, so nothing else
is set.  Unset: ``<checkout>/.jax_cache`` (listed in ``.gitignore``) — a
fixed path, because the path is part of the cache's key.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def setup_compile_cache(min_compile_secs: float = 1.0) -> str:
    """Point JAX's persistent compilation cache at the one directory this
    rule picks (call before the first compile) and return it."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    return path
