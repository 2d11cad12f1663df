"""JAX's persistent compilation cache for the serving entry points.

The serving stack compiles one step per ``(B, n_cap, C, R)`` bucket shape,
and at full width each is a whole multi-layer program. Those compiles are
deterministic functions of the config, so the entry points (``chip_smoke.py``,
``python -m repro.launch.serve``, the fleet worker, the load benchmarks)
keep them on disk across process restarts.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no directory, and the environment's size bound
(``JAX_COMPILATION_CACHE_MAX_SIZE``) holds. Otherwise the cache lives at one
fixed path inside the checkout, ``<repo>/.jax_cache`` (git-ignored): the
directory is part of what lets a later process find an entry, so it must
not move between runs. That directory is unbounded: one full-width run
compiles over a hundred programs in a fixed order, and an LRU bound below
their total size evicts each entry before the next run asks for it.
Library code (``BatchServer`` and below) never turns the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn the persistent compilation cache on for this process and return
    its directory: ``$JAX_COMPILATION_CACHE_DIR`` when set, else
    ``DEFAULT_DIR``. Every compile is cached — serving bucket steps are
    small but hot, so JAX's default one-second floor would skip many.
    Idempotent."""
    import jax

    cache_dir = os.environ.get(ENV_VAR)
    if not cache_dir:
        cache_dir = DEFAULT_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
