"""Where JAX keeps its persistent compilation cache: one decision.

``JAX_COMPILATION_CACHE_DIR`` set in the environment wins — JAX reads it
itself, so nothing here overrides it.  Otherwise the cache lives at one
fixed absolute path inside the checkout, ``<repo>/.jax_cache`` (ignored
by git): the path is part of the cache key, so a directory that moves
with the working directory would never hit.

The minimum compile time to cache is kept low so the limb-crypto
programs (pairing stages, scalar-mul ladders — seconds to minutes each)
are all cached.
"""
from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")
MIN_COMPILE_SECS = 0.5


def configure() -> str:
    """Point JAX's persistent cache at its one directory; returns it."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_SECS)
    return path
