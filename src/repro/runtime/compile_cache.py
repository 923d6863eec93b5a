"""Persistent XLA compilation cache, kept at one fixed path.

Compiling the full-width decode step takes ~10 s on a TPU v5e (and the
parameter init ~30 s); the persistent cache lets a second process on the
same machine load both instead. A later run finds the entries only where
an earlier one left them, so the path never depends on a temp name, a
pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (git-ignored): src/repro/runtime/ -> checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    When JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and nothing
    is set here; otherwise the cache lives at `<checkout>/.jax_cache`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
