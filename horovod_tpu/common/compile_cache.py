"""Where the persistent XLA compile cache lives.

One rule for every entry point that compiles a model (chip_smoke.py,
the SPMD example, the serving load harness, multi-process world
formation): the caller places the cache with
``JAX_COMPILATION_CACHE_DIR``; when that is unset the cache is
``<checkout>/.jax_cache``.  The default is a fixed path derived from the
package's own location, never a temporary directory, a pid or a clock:
a cache that moves between runs never hits.
"""
from __future__ import annotations

import os

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    A set ``JAX_COMPILATION_CACHE_DIR`` is left alone (JAX reads it
    itself at import); otherwise the cache goes to ``DEFAULT_CACHE_DIR``.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
