"""Persistent XLA compile-cache setup, shared by every entry point.

ResNet-sized round programs take minutes to compile; the CLI, bench, tests,
the multichip dryrun and the probes all enable the same persistent cache so a
shape compiles once per cache directory. The directory is
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads it
itself — nothing is set in code), otherwise one fixed, git-ignored directory
inside the checkout: the path is part of the cache key, so it never moves."""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return path
