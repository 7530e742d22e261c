"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``launch/serve.py``,
``examples/protein_clustering.py``) call ``enable_compile_cache`` once, before
their first compile. A compile of the batched SUMMA step takes minutes on the
TPU compiler, so a second process with the same shapes should find it on
disk.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: JAX reads it at
import and this module sets no other directory. Otherwise the cache goes to
the fixed path ``<repo>/.jax_cache`` (listed in ``.gitignore``) — never a
temporary name, since a cache directory that moves is never hit again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
