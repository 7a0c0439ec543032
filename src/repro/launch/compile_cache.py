"""Where JAX keeps its persistent compilation cache.

Entry points call ``configure_compile_cache()`` before their first
compile (never at import).  A directory named by
``JAX_COMPILATION_CACHE_DIR`` wins, and nothing else is set: JAX reads
that variable itself.  Otherwise the cache lives at a fixed directory
inside the checkout — fixed because the path is part of the cache's key,
so a directory built from a temporary name, a pid or the time would
never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the in-checkout cache directory (listed in .gitignore)
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
