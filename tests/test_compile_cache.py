"""Where the entry points keep JAX's persistent compilation cache
(``repro.launch.compile_cache``): the environment's directory when
``JAX_COMPILATION_CACHE_DIR`` names one, else one fixed path inside the
checkout.  ``jax.config.update`` is recorded, not applied, so no test
moves the cache of the worker it runs in."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_environment_directory_is_left_to_jax(monkeypatch, updates, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() is None
    assert updates == []


@pytest.mark.parametrize("value", [None, ""])
def test_fixed_in_repo_path_otherwise(monkeypatch, updates, value):
    if value is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", value)
    repo = Path(__file__).resolve().parents[1]
    assert compile_cache.use_compile_cache() == repo / ".jax_cache"
    assert updates == [("jax_compilation_cache_dir", str(repo / ".jax_cache"))]
