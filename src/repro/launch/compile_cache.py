"""Where JAX keeps its persistent compilation cache.

A compiled program is looked up by a key that includes the cache's path, so
the directory must not move between runs: it is never derived from a
temporary directory, a pid or the time.
"""
import os
import pathlib

import jax

# <checkout>/.jax_cache (listed in .gitignore)
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return it.  ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own
    setting and is left alone; otherwise the cache is ``REPO_CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
