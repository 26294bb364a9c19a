"""Persistent XLA compilation cache (SURVEY §7 hard-part (a)).

Every new (model, batch, seq) bucket pays an XLA compile of seconds to
tens of seconds on the TPU; the reference never faces this because any
CUDA batch size is instantly runnable
(``293-project/profiling/ModelProfiler.py:46``). JAX's persistent
compilation cache turns repeat compiles — across processes, restarts and
profile sweeps — into disk hits. This module is the single switch: every
compile-heavy entry point (model host, decode engine, profilers, the
chip smoke) calls :func:`enable` before its first jit.

Where the cache lives is decided OUTSIDE the program when the operator
wants it to be: if ``JAX_COMPILATION_CACHE_DIR`` is in the environment
JAX has already read it and this module sets no directory. Otherwise the
cache sits at one fixed path in the checkout, ``<repo>/.jax_cache``
(git-ignored) — fixed because the path is part of what makes a later
process find the entries: a directory that moves never hits.
"""

from __future__ import annotations

import os
import threading
from pathlib import Path

from ray_dynamic_batching_tpu.utils.logging import get_logger

logger = get_logger("compile_cache")

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

_lock = threading.Lock()
_enabled = False


def enable() -> str:
    """Idempotently turn the persistent cache on and return the
    directory in effect. Safe to call before or after backend
    initialization (JAX reads the knobs at compile time)."""
    global _enabled
    import jax
    from jax.experimental.compilation_cache import (
        compilation_cache as jax_cache,
    )

    with _lock:
        from_env = os.environ.get(CACHE_DIR_ENV)
        if not _enabled:
            if not from_env:
                jax_cache.set_cache_dir(str(DEFAULT_CACHE_DIR))
            # Cache every program: the default min-entry-size and
            # compile-time gates would skip exactly the small
            # decode-step programs the serving path dispatches hottest.
            jax.config.update(
                "jax_persistent_cache_min_entry_size_bytes", -1
            )
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0
            )
            _enabled = True
            logger.info(
                "persistent compilation cache at %s",
                from_env or DEFAULT_CACHE_DIR,
            )
        return from_env or str(DEFAULT_CACHE_DIR)
