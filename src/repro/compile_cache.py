"""Where the entry points keep JAX's persistent compilation cache.

Call :func:`use_compile_cache` from an entry point's ``__main__`` block,
never at import: tests and library callers import the same modules and
must not have a cache directory chosen for them.
"""
from __future__ import annotations

import os

#: The fixed default: JAX keys cache entries by their directory, so a
#: path that moved between runs (a temp dir, a pid, a timestamp) would
#: never hit.  ``.gitignore`` lists it.
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already read it and
    nothing is changed.  Otherwise the cache goes to ``<repo>/.jax_cache``
    and keeps every compile: the kernels compile in under JAX's default
    one-second threshold, and a rerun would compile them again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return REPO_CACHE_DIR
