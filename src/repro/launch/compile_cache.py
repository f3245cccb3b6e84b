"""Where the entry points keep JAX's persistent compilation cache.

One rule, applied by :func:`use_compile_cache` at the start of every entry
point (the launch CLIs, ``benchmarks.run``, ``chip_smoke.py``) and never at
library import:

- ``$JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing else is set.
- otherwise: ``<checkout>/.jax_cache``, a fixed path (the path is part of
  the cache key, so a moving directory would never hit), ignored by git.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the in-checkout cache used when the environment names none
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's compilation cache at its directory; returns the path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
