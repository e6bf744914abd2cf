"""Where compiled programs are kept between processes.

One root holds both caches the system has: JAX's persistent compilation
cache, and the AOT serve-executable cache (``serve/aot.py``) in its
``repro-aot`` subdirectory. The root is ``$JAX_COMPILATION_CACHE_DIR``
when that is set, and ``<repo>/.cache/jax`` otherwise. It is a fixed path
on purpose: the path is part of what a cache hit depends on, so a root
that moved between runs would never hit.

Entry points call :func:`enable` from their ``main``; importing this
module (or anything else in the package) changes no JAX setting."""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_root() -> str:
    """``$JAX_COMPILATION_CACHE_DIR``, else ``<repo>/.cache/jax``."""
    return os.environ.get(ENV) or os.path.join(REPO_ROOT, ".cache", "jax")


def enable() -> str:
    """Turn on JAX's persistent compilation cache at :func:`cache_root`
    and return that directory. With ``$JAX_COMPILATION_CACHE_DIR`` set,
    JAX already reads it and nothing is set here."""
    root = cache_root()
    if not os.environ.get(ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", root)
    return root
