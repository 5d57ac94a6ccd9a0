"""JAX's persistent compilation cache for the entry points.

Called from ``launch.train.main`` and ``chip_smoke.py``, never at import,
so tests and library callers keep the cache off.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing else
is set here.  Otherwise the cache lives at a fixed path in the checkout:
the directory is part of what a later run must find again, so it is never
derived from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses.

    A Pallas kernel's serialized body carries the MLIR locations of its
    lowering, which by default hold the whole Python call stack — so the
    same program reached from another caller got another cache key and
    compiled again (the round's kernels, ~130 s on a v5e).  Locations are
    cut to the innermost frame here, which keeps keys stable."""
    import jax
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
