"""Shared set-up of the benchmark's own tests: the repository root on the
import path (the benchmark is the ``bench`` package there), and JAX's
global settings put back after each test that runs a cell."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

@pytest.fixture
def jax_settings(monkeypatch, tmp_path):
    """Run a cell without writing a persistent compilation cache (the
    variable set here stops the harness from naming one) and without
    leaving its settings or traced programs behind for later tests."""
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    keys = ("jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    jax.clear_caches()
