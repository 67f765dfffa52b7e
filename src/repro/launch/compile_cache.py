"""JAX's persistent compilation cache for the entry points.

Each entry point calls :func:`enable_compile_cache` first thing in its
``main()`` (never at import), so a program compiled once is found again
by the next process on the same machine.
"""
from __future__ import annotations

import os
from pathlib import Path

# A fixed path inside the checkout: the cache only hits when its
# directory stays put from one process to the next.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Turn on the persistent compilation cache.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache lives at :data:`CACHE_DIR`.
    Takes effect only before the process compiles its first program.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
