"""On-disk caches, kept inside the checkout.

Two caches can change what a run compiles or how long it takes to start:

* the tuning cache (``repro.tune``) decides which candidate an ``"auto"``
  strategy field resolves to; it lives at ``TUNE_CACHE`` unless
  ``$REPRO_TUNE_CACHE`` names another file;
* JAX's persistent compilation cache holds compiled programs;
  ``enable_compile_cache`` places it.

Both default to fixed, git-ignored paths under the checkout, so nothing
outside the repository decides what is compiled, and a second run on the
same machine finds what the first one stored.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[2]
TUNE_CACHE = REPO_ROOT / ".repro_tune" / "tune_cache.json"
JAX_CACHE = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone; otherwise the cache goes to ``<repo>/.jax_cache``. Call it
    once at start-up, before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(JAX_CACHE))
    return str(JAX_CACHE)
