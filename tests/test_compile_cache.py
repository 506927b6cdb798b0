"""The persistent compile cache goes where it is told, and nowhere else."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax

from repro.launch.compile_cache import DEFAULT_CACHE_DIR, configure_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_default_is_a_fixed_directory_at_the_repo_root(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert configure_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_the_environment_variable_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_entries_land_in_the_named_directory(tmp_path):
    # a fresh process, so JAX reads the variable the way an entry point's does
    program = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import configure_compile_cache
        configure_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((16, 16))).block_until_ready()
    """)
    cache = tmp_path / "cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               PYTHONPATH=str(ROOT / "src"))
    res = subprocess.run([sys.executable, "-c", program], capture_output=True,
                         text=True, env=env, timeout=120, cwd=tmp_path)
    assert res.returncode == 0, res.stderr
    assert any(p.name.endswith("-cache") for p in cache.iterdir())
    assert sorted(p.name for p in tmp_path.iterdir()) == ["cache"]
