"""JAX's persistent compilation cache, placed from outside or at a fixed
path in the caller's checkout.

The path is part of what the cache is keyed on, so it never holds a temp
directory, a process id or a time: a cache that moves never hits. The
checkout is named by the caller (a script knows where it lives; an
installed package does not), so two checkouts never share a cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir(checkout: Path) -> Path:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``."""
    env = os.environ.get(ENV_VAR)
    return Path(env) if env else Path(checkout) / ".jax_cache"


def use_compile_cache(checkout: Path) -> Path:
    """Turn the persistent cache on before the first compile; returns its
    directory. JAX reads ``$JAX_COMPILATION_CACHE_DIR`` itself, so when it
    is set no other directory is configured here."""
    path = compile_cache_dir(checkout)
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
