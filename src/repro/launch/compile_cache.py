"""JAX's persistent compilation cache, placed from outside the program.

Compiling the episode step and the serving scans takes a large share of a
cold run on a chip; the cache lets a later process of the same checkout
load what an earlier one compiled. Call :func:`enable_compile_cache` first
thing in an entry point (the launchers' ``main`` and ``chip_smoke.py`` do);
nothing enables it at import.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str | None:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (JAX reads it
    itself, and nothing here overrides it). Otherwise, on an accelerator,
    the cache lives at ``<checkout>/.jax_cache``: a fixed path, since the
    path is part of what a later run must find again. On the CPU backend
    (test-sized runs; XLA:CPU warns on every cached load) it stays off and
    this returns None.
    """
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    if jax.default_backend() == "cpu":
        return None
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
