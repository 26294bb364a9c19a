"""Persistent XLA compilation cache rule (utils/compile_cache.py): the
directory is placed from OUTSIDE when ``JAX_COMPILATION_CACHE_DIR`` is
set (JAX has read it; the module sets none), and is otherwise ONE fixed
path inside the checkout — never derived from a pid, a clock or a
temporary name, because a cache directory that moves never hits."""

import inspect
import os
from pathlib import Path

import pytest

from ray_dynamic_batching_tpu.utils import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def fresh(monkeypatch):
    """``enable()`` as a new process would see it, with the directory
    setter replaced by a recorder so no test re-points the suite's own
    cache."""
    from jax.experimental.compilation_cache import (
        compilation_cache as jax_cache,
    )

    calls = []
    monkeypatch.setattr(jax_cache, "set_cache_dir", calls.append)
    monkeypatch.setattr(compile_cache, "_enabled", False)
    return calls


def test_env_set_means_no_directory_set_in_code(fresh, monkeypatch,
                                                tmp_path):
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert fresh == []


def test_env_unset_uses_the_fixed_path_in_the_checkout(fresh,
                                                       monkeypatch):
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    want = str(REPO / ".jax_cache")
    assert compile_cache.enable() == want
    assert fresh == [want]
    # Idempotent: a second call sets nothing again and answers the same.
    assert compile_cache.enable() == want
    assert fresh == [want]


def test_thresholds_cache_everything(fresh, monkeypatch):
    import jax

    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    compile_cache.enable()
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_path_is_not_derived_from_pid_time_or_tempfile():
    src = inspect.getsource(compile_cache)
    for banned in ("getpid", "tempfile", "mkdtemp", "time."):
        assert banned not in src, banned
    assert compile_cache.DEFAULT_CACHE_DIR == REPO / ".jax_cache"


def test_cache_dir_is_git_ignored():
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


@pytest.mark.slow  # compiles and writes to disk
def test_a_compile_lands_in_the_directory_in_effect():
    import jax
    import jax.numpy as jnp

    cache_dir = compile_cache.enable()
    # A unique shape forces a fresh compile that must land on disk.
    x = jnp.ones((3, 7, 11), jnp.float32)
    jax.jit(lambda a: (a * 2).sum())(x).block_until_ready()
    assert os.listdir(cache_dir), "compilation cache dir stayed empty"
