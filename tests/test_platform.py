"""anomod.utils.platform: the one compilation-cache placement rule."""

import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

from anomod.utils import platform

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture
def restore_cache_config():
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev_min)


def test_cache_dir_from_env_is_left_alone(monkeypatch, tmp_path,
                                          restore_cache_config):
    """JAX_COMPILATION_CACHE_DIR set: the helper reports it and sets no
    directory in code (JAX reads the variable itself)."""
    jax.config.update("jax_compilation_cache_dir", "sentinel-untouched")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
    assert platform.enable_compile_cache() == str(tmp_path / "c")
    assert jax.config.jax_compilation_cache_dir == "sentinel-untouched"


def test_cache_dir_defaults_to_fixed_checkout_path(monkeypatch,
                                                   restore_cache_config):
    """Unset: <checkout>/.jax_cache — fixed (the path is part of the cache
    key), git-ignored, and with the compile-time floor dropped so the
    serve grid's many sub-second executables are cached at all."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(ROOT / ".jax_cache")
    assert platform.enable_compile_cache() == want
    assert platform.enable_compile_cache() == want          # idempotent
    assert jax.config.jax_compilation_cache_dir == want
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


@pytest.mark.parametrize("env_set", [True, False])
def test_cache_entries_land_where_the_rule_says(tmp_path, env_set):
    """End to end in a fresh process: a compile leaves its entry under
    the env directory when set, else under <checkout>/.jax_cache."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    code = (
        "import jax, jax.numpy as jnp\n"
        "from anomod.utils.platform import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        # a constant no earlier run compiled: the entry must be NEW
        f"f = jax.jit(lambda x: (x * {time.time_ns()}.5).sum())\n"
        "f(jnp.arange(7.0)).block_until_ready()\n"
        "print(jax.config.jax_compilation_cache_dir)\n")
    want = tmp_path / "cc" if env_set else ROOT / ".jax_cache"
    before = set(want.iterdir()) if want.is_dir() else set()
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-500:]
    assert Path(r.stdout.splitlines()[0]) == want
    assert set(want.iterdir()) - before, f"no new cache entry under {want}"
    if env_set:
        # the config value is whatever JAX derived from the env itself
        assert r.stdout.splitlines()[1] == str(tmp_path / "cc")
