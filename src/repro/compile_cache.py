"""JAX's persistent compilation cache, as the entry points configure it.

Called by entry points (`chip_smoke.py`, `python -m repro.experiments.run`),
never at import.  Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it
itself and nothing else is set here.  Otherwise the cache lives at the fixed
path `artifacts/jax_cache/` inside the checkout (gitignored): the directory
is part of what a later run must find again, so it never depends on a
temporary name, a pid or the time.
"""
from __future__ import annotations

import os

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "artifacts",
    "jax_cache",
)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
