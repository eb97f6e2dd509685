"""Where JAX's persistent compilation cache lives.

One rule, applied by the entry points that compile the big programs
(``tools/serve.py``, ``bench.py``, ``chip_smoke.py``'s children): when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this sets
nothing; otherwise the cache is ``<checkout>/.jax_cache`` (git-ignored).
Never a temp name, pid or time — the directory is part of the cache key,
so a path that moves never hits, and the processes of one run must find
each other's entries.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache() -> str:
    """Returns the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
