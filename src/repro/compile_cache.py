"""JAX's persistent compilation cache, placed the same way by every entry
point (``chip_smoke.py`` and the ``benchmarks/`` scripts).

The cache key includes the directory, so the directory must not move from
one run to the next: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and nothing is set here; otherwise the cache goes to one fixed
directory inside the checkout (listed in ``.gitignore``), never one built
from a temporary directory, a process id or the time.
"""
from __future__ import annotations

import os

import jax

__all__ = ["CACHE_DIR", "enable"]

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
