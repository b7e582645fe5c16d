"""Where JAX keeps its persistent compilation cache.

A cold chip run compiles every serving shape again unless compiled
programs persist, and a cache only hits when its directory stays put
(the path is part of what makes an entry findable). So the directory is
either the one the environment names in `JAX_COMPILATION_CACHE_DIR`, or
one fixed path inside the checkout, `<repo>/.jax_cache` (gitignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the path. Call before the first compilation."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
