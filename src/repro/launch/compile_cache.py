"""One home for JAX's persistent compilation cache.

Every entry point (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``, ``benchmarks/run.py``) calls ``enable_compile_cache``
before its first compile, so a second process on the same machine loads
the executables the first one built instead of compiling them again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins:
    JAX reads it at import and nothing is set here.  Otherwise the cache is
    ``.jax_cache/`` in the checkout (gitignored).  The path is fixed — never
    built from a temp name, a pid or the time — because a later process
    only finds what an earlier one wrote at the same path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
