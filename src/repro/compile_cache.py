"""JAX's persistent compilation cache, kept at one fixed place.

The cache is keyed by the directory it lives in, so the directory must not
move between runs: a temporary name, a pid or a timestamp would never hit.
``JAX_COMPILATION_CACHE_DIR``, when set, wins and nothing is set in code
(JAX reads that variable itself); otherwise the cache lives at
``<checkout>/.jax_cache``, which ``.gitignore`` lists.

The command-line entry points (``chip_smoke.py``, the benchmarks) call
:func:`enable_compile_cache`; the library and the tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
