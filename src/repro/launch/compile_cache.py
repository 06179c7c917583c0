"""JAX's persistent compilation cache, for the command-line entry points.

Scripts call :func:`enable_compile_cache` once, before their first
compile; importing the library never turns the cache on. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no directory
is set here. Otherwise the cache lives at one fixed path inside the
checkout, ``<repo>/.jax_cache`` (listed in ``.gitignore``): the path is
part of what a later process must find again, so it never depends on a
temporary name, a process id or the time.

JAX's one-second floor on what it writes stays: a size-capped cache scans
every entry on each write, and caching each small eager op (a Gaussian
feature map for one ragged request shape takes about twenty) made those
writes cost about a second apiece on a v5e host.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
