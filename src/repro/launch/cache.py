"""Where the persistent XLA compilation cache lives.

JAX keys a cache entry partly on the cache path, so the directory must not
move between runs: a fixed path inside the checkout lets a second run of
the same program load the first run's executables instead of compiling.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When JAX_COMPILATION_CACHE_DIR is set, JAX already reads it and nothing
    is set here (so nothing is written under the checkout). Otherwise the
    cache goes to ``<checkout>/.jax_cache``."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
