"""Persistent-compilation-cache rule of the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX uses it and the repo sets no
directory of its own; otherwise the cache lives at one fixed path inside the
checkout. Library code (``BatchServer``) never turns the cache on. The
heavy claim — a second process reloads compiled executables from disk — is
shown by a second ``chip_smoke.py`` run reporting fewer compile seconds.
"""
import os
from pathlib import Path

import jax
import pytest

from repro.common.compile_cache import (
    DEFAULT_DIR, ENV_VAR, enable_compilation_cache,
)


@pytest.fixture(autouse=True)
def _isolate(monkeypatch):
    """Each test sees no ambient env var; the jax config values are restored
    afterwards so other suites are unaffected."""
    monkeypatch.delenv(ENV_VAR, raising=False)
    names = ("jax_compilation_cache_dir",
             "jax_compilation_cache_max_size",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in before.items():
        jax.config.update(n, v)


def test_default_dir_is_fixed_inside_the_checkout():
    """The checkout's own cache is unbounded, so an LRU bound from the
    environment cannot evict a run's programs before the next run reads
    them."""
    repo = Path(__file__).resolve().parents[1]
    assert Path(DEFAULT_DIR) == repo / ".jax_cache"
    jax.config.update("jax_compilation_cache_max_size", 1 << 20)
    assert enable_compilation_cache() == DEFAULT_DIR
    assert os.path.isdir(DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == DEFAULT_DIR
    assert jax.config.jax_compilation_cache_max_size == -1


def test_env_var_activates(tmp_path, monkeypatch):
    """JAX reads $JAX_COMPILATION_CACHE_DIR itself: the repo reports it and
    sets no directory, and no size bound, in code."""
    target = str(tmp_path / "jcc-env")
    monkeypatch.setenv(ENV_VAR, target)
    jax.config.update("jax_compilation_cache_max_size", 1 << 20)
    before = jax.config.jax_compilation_cache_dir
    assert enable_compilation_cache() == target
    assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_compilation_cache_max_size == 1 << 20


def test_idempotent_and_caches_every_compile():
    got = enable_compilation_cache()
    assert enable_compilation_cache() == got
    # cache-everything thresholds: the serving bucket steps are small
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1


def test_batch_server_sets_no_cache_dir():
    """The server is library code: constructing one leaves JAX's cache
    configuration to the entry point."""
    from repro.configs.vq_opt_125m import smoke_config
    from repro.models import transformer as T
    from repro.serving.batch_server import BatchServer

    cfg = smoke_config(vqt=True)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    before = jax.config.jax_compilation_cache_dir
    BatchServer(params, cfg, edit_capacity=4, row_capacity=16, max_batch=2,
                min_doc_capacity=16)
    assert jax.config.jax_compilation_cache_dir == before
