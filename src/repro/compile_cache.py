"""Where JAX keeps compiled programs between processes.

A compile of the lease-plane kernels and their dispatch programs takes
seconds to a minute, so the entry points (``chip_smoke.py``, the lease-array
bench and the falsify CLI) turn on JAX's persistent compilation cache. The
cache directory is part of each entry's key, so it must not move between
runs: it is ``JAX_COMPILATION_CACHE_DIR`` when that is set, else the fixed
``.jax_cache/`` at the repository root (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the default cache directory: ``<repo>/.jax_cache``
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX has already read it as its
    own ``jax_compilation_cache_dir`` setting, and nothing here overrides
    it. Otherwise the cache goes to :data:`DEFAULT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
