"""``repro.compile_cache``: where the entry points keep compiled programs."""
from __future__ import annotations

import os
import subprocess
import sys

import jax
import pytest

from repro import compile_cache

_KEYS = ("jax_compilation_cache_dir",
         "jax_persistent_cache_min_compile_time_secs",
         "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def saved_config():
    prev = {k: getattr(jax.config, k) for k in _KEYS}
    yield
    for k, v in prev.items():
        jax.config.update(k, v)


def test_env_dir_is_left_to_jax(monkeypatch, saved_config, tmp_path):
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_default_dir_is_fixed_in_checkout(monkeypatch, saved_config):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    path = compile_cache.use_compile_cache()
    assert path == str(compile_cache.CHECKOUT_CACHE)
    assert jax.config.jax_compilation_cache_dir == path
    assert (compile_cache.CHECKOUT_CACHE.parent / "pyproject.toml").exists()
    assert compile_cache.use_compile_cache() == path


def test_not_enabled_on_import():
    code = ("import repro, repro.api, jax; "
            "print(jax.config.jax_compilation_cache_dir)")
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.CACHE_ENV}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "None"
