"""The entry points' persistent compilation cache location
(``repro.launch.compile_cache``)."""

import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch.compile_cache import CACHE_DIR, use_compile_cache

REPO = Path(__file__).resolve().parents[1]


def _entries(d: Path) -> set:
    return set(os.listdir(d)) if d.is_dir() else set()


def test_default_cache_is_fixed_repo_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert use_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    ignored = subprocess.run(["git", "check-ignore", "-q", ".jax_cache/x"],
                             cwd=REPO)
    assert ignored.returncode == 0, ".jax_cache is not git-ignored"


def test_env_cache_dir_wins_and_receives_entries(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, compiles land there only."""
    env_dir = tmp_path / "cache"
    default_before = _entries(CACHE_DIR)
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import use_compile_cache\n"
        "print(use_compile_cache())\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(env_dir),
           "PYTHONPATH": str(REPO / "src")}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == str(env_dir)
    assert any(n.startswith("jit__lambda") for n in _entries(env_dir))
    assert _entries(CACHE_DIR) == default_before
