"""The persistent compile cache is placed from outside or at one fixed path."""

import os

import jax
import pytest

from elasticsearch_tpu.common.compile_cache import configure_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_places_the_cache_and_code_sets_nothing(
        monkeypatch, tmp_path, restore_cache_dir):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None


def test_default_is_the_checkouts_jax_cache(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert configure_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


@pytest.mark.parametrize("path", ["__graft_entry__.py", "chip_smoke.py",
                                  "elasticsearch_tpu/__main__.py",
                                  "elasticsearch_tpu/node.py"])
def test_entry_points_set_no_cache_path_of_their_own(path):
    with open(os.path.join(REPO, path)) as f:
        assert "jax_compilation_cache_dir" not in f.read()
