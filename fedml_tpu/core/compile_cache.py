"""Where the persistent XLA compilation cache lives.

One rule for every entry point (``chip_smoke.py``,
``fedml_tpu.experiments.run``, ``tests/conftest.py``): where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing is set
in code; where it is not, the cache is one fixed directory inside the
checkout. The directory is part of the cache key, so it never carries
a temporary name, a pid or a time.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache (git-ignored): next to the package, not under it
CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)
# programs that compile faster than this are not worth a file: the
# directory rides along whenever the tree is copied
MIN_COMPILE_SECS = 1.0


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns the directory
    in use. Does not initialize a backend."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", MIN_COMPILE_SECS
    )
    return CACHE_DIR
