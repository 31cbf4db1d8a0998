"""The entry points' compile-cache placement (repro.launch.cache).

``jax.config.update`` is replaced by a recorder, so these tests never turn
the persistent cache on for the rest of the suite.
"""
import os

import jax

from repro.launch import cache


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_honours_env_var(monkeypatch, tmp_path):
    calls = _record_updates(monkeypatch)
    monkeypatch.setenv(cache.ENV_VAR, str(tmp_path))
    assert cache.compile_cache_dir() == str(tmp_path)
    assert cache.enable_compile_cache() == str(tmp_path)
    assert calls == []          # JAX reads the variable itself


def test_compile_cache_fixed_repo_path_when_unset(monkeypatch):
    calls = _record_updates(monkeypatch)
    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    first = cache.enable_compile_cache()
    second = cache.enable_compile_cache()
    assert first == second == os.path.join(cache.REPO_ROOT, ".jax_cache")
    assert os.path.isfile(os.path.join(cache.REPO_ROOT, "pyproject.toml"))
    assert calls == [("jax_compilation_cache_dir", first)] * 2
