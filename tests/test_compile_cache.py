"""Persistent-compile-cache contract (utils/compile_cache.py).

Where the cache lives is decided outside the program: with
``JAX_COMPILATION_CACHE_DIR`` set, jax reads it and the program sets NO
directory in code; unset, the cache is at one fixed git-ignored path
inside the checkout.  XLA:CPU stays excluded unless forced — its cached
AOT executables embed the compiling process's detected machine features,
and loading a mismatched entry segfaulted this container.
"""

import os

import jax
import pytest

from gordo_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _isolated(monkeypatch):
    """Every test starts un-enabled and leaves jax's cache config as it
    found it (never a disk cache pointed anywhere for later tests)."""
    monkeypatch.setattr(compile_cache, "_ENABLED", False)
    monkeypatch.delenv("GORDO_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = (
        jax.config.jax_compilation_cache_dir,
        jax.config.jax_enable_compilation_cache,
    )
    yield
    jax.config.update("jax_compilation_cache_dir", before[0])
    jax.config.update("jax_enable_compilation_cache", before[1])


@pytest.fixture
def dir_updates(monkeypatch):
    """Record every ``jax.config.update`` of the cache directory."""
    seen = []
    real = jax.config.update

    def recording(name, value):
        if name == "jax_compilation_cache_dir":
            seen.append(value)
        return real(name, value)

    monkeypatch.setattr(jax.config, "update", recording)
    return seen


def test_default_dir_is_fixed_inside_the_checkout():
    assert compile_cache.DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()


def test_cpu_backend_skips_cache(monkeypatch, tmp_path, dir_updates):
    monkeypatch.setattr(
        compile_cache, "DEFAULT_CACHE_DIR", str(tmp_path / "x")
    )
    # conftest pins the cpu backend for the whole suite
    assert compile_cache.enable_persistent_compile_cache() is False
    assert not (tmp_path / "x").exists()
    assert dir_updates == []


def test_cpu_exclusion_switches_off_a_cache_placed_from_outside(
    monkeypatch, tmp_path
):
    """With the variable set jax would cache on CPU by itself; the
    exclusion has to disable the cache, not just decline to configure it."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "ext"))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "ext"))
    assert compile_cache.enable_persistent_compile_cache() is False
    assert jax.config.jax_enable_compilation_cache is False


def test_env_set_means_no_directory_set_in_code(
    monkeypatch, tmp_path, dir_updates
):
    monkeypatch.setenv("GORDO_COMPILE_CACHE", "force")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "ext"))
    monkeypatch.setattr(
        compile_cache, "DEFAULT_CACHE_DIR", str(tmp_path / "default")
    )
    assert compile_cache.enable_persistent_compile_cache() is True
    assert dir_updates == []
    assert not (tmp_path / "default").exists()
    assert jax.config.jax_enable_compilation_cache is True


def test_env_unset_uses_the_in_checkout_path(
    monkeypatch, tmp_path, dir_updates
):
    monkeypatch.setenv("GORDO_COMPILE_CACHE", "force")
    monkeypatch.setattr(
        compile_cache, "DEFAULT_CACHE_DIR", str(tmp_path / "default")
    )
    assert compile_cache.enable_persistent_compile_cache() is True
    assert dir_updates == [str(tmp_path / "default")]
    assert (tmp_path / "default").is_dir()
    # idempotent: a second call neither re-creates nor re-sets anything
    assert compile_cache.enable_persistent_compile_cache() is True
    assert dir_updates == [str(tmp_path / "default")]


def test_uncreatable_cache_dir_raises(monkeypatch, tmp_path):
    """No quiet carry-on uncached: a cache that cannot be placed is an
    error at start-up."""
    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    monkeypatch.setenv("GORDO_COMPILE_CACHE", "force")
    monkeypatch.setattr(
        compile_cache, "DEFAULT_CACHE_DIR", str(blocker / "cache")
    )
    with pytest.raises(OSError):
        compile_cache.enable_persistent_compile_cache()
    assert compile_cache._ENABLED is False


def test_opt_out(monkeypatch, dir_updates):
    monkeypatch.setenv("GORDO_COMPILE_CACHE", "0")
    assert compile_cache.enable_persistent_compile_cache() is False
    assert dir_updates == []
