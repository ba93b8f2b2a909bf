"""Where the entry points keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, is the cache, and JAX reads it
itself.  Otherwise the cache lives at one fixed path inside the
checkout, ``<checkout>/.jax_cache`` (listed in ``.gitignore``): the
path is part of the cache's key, so a directory that moves from run to
run never hits.  The entry points (``launch/serve.py``,
``launch/train.py``, ``chip_smoke.py``) call :func:`enable_compile_cache`
before their first compile; importing this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["compile_cache_dir", "enable_compile_cache"]

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> Path:
    """The cache directory the entry points use."""
    env = os.environ.get(ENV)
    return Path(env) if env else CHECKOUT / ".jax_cache"


def enable_compile_cache() -> Path:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return it."""
    path = compile_cache_dir()
    if ENV not in os.environ:
        import jax
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
