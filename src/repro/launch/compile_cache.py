"""Where JAX keeps its persistent compile cache.

A cold process compiles every program again; the persistent cache lets a
later process on the same machine read them back. Its directory is part
of every entry's key, so it must not move between runs: it is either the
operator's ``JAX_COMPILATION_CACHE_DIR`` (JAX reads that variable itself)
or a fixed directory inside the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The checkout-relative default (listed in ``.gitignore``).
CACHE_DIRNAME = ".jax_cache"


def enable_compile_cache(root: str | os.PathLike) -> str:
    """Turn on the persistent compile cache and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set — left to JAX, nothing is set
    here — else ``<root>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(root).resolve() / CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
