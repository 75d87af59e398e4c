"""Where ``repro.compile_cache.enable_compile_cache`` puts JAX's persistent
compilation cache: ``JAX_COMPILATION_CACHE_DIR`` when it is set, else the
fixed ``.jax_cache/`` at the repository root."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_default_cache_is_the_repo_root(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()


def test_env_cache_dir_is_jax_own_and_receives_entries(tmp_path):
    """With the variable set, the helper sets nothing and a compile lands
    in that directory."""
    code = """
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
before = jax.config.jax_compilation_cache_dir
print(enable_compile_cache() == before)
jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.arange(8.0)).block_until_ready()
"""
    env = dict(os.environ)
    env.update(
        JAX_COMPILATION_CACHE_DIR=str(tmp_path),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        JAX_PLATFORMS="cpu",
        PYTHONPATH=os.pathsep.join(
            p for p in (str(ROOT / "src"), env.get("PYTHONPATH", "")) if p
        ),
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=tmp_path, check=True,
        capture_output=True, text=True, timeout=300,
    )
    assert out.stdout.split() == ["True"]
    assert any(tmp_path.iterdir())
