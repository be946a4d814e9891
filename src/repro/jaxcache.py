"""Persistent JAX/XLA compilation cache, shared by tests, CI and the bench
CLI.

Most of the tier-1 suite's wall time is XLA compiling the same model
graphs over and over; with a persistent cache a warm run skips nearly all
of it.  Enabling is semantics-free — only compile time changes — and
opt-out via ``REPRO_NO_JAX_CACHE=1``.  The default cache directory is
repo-local (``.cache/jax`` next to this package's repo root, overridable
with ``JAX_COMPILATION_CACHE_DIR``) so nothing outside the workspace is
touched and a container rebuild starts cold.
"""

from __future__ import annotations

import os


def workspace_cache_dir() -> str:
    """Repo-local root for all persistent accelerator caches."""
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(repo_root, ".cache")


def default_dir() -> str:
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    return os.path.join(workspace_cache_dir(), "jax")


def enable_env(cache_dir: str | None = None) -> str | None:
    """Arrange the cache via ``JAX_*`` environment variables only.

    Unlike :func:`enable` this never imports jax itself — callers on paths
    where jax may not be needed at all (the bench CLI, pool workers) use
    this so the cache is active if and when jax loads lazily.
    """
    if os.environ.get("REPRO_NO_JAX_CACHE"):
        return None
    cache_dir = cache_dir or default_dir()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", cache_dir)
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_ENABLE_XLA_CACHES", "all")
    return cache_dir


def enable(cache_dir: str | None = None) -> str | None:
    """Point JAX's persistent compilation cache at ``cache_dir``.

    Returns the directory in use, or None when disabled.
    """
    if os.environ.get("REPRO_NO_JAX_CACHE"):
        return None
    import jax
    cache_dir = cache_dir or default_dir()
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache every computation: on CPU even small compiles add up across
    # a 140-test suite, and the cache is size-bounded by the workspace
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")
    return cache_dir
