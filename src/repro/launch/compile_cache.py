"""Persistent XLA compilation cache at a fixed place.

JAX's persistent cache only hits when the directory does not move
between runs.  ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX
(it reads the variable itself); otherwise the cache lives in
``<checkout>/.jax_cache``.  Entry points (``chip_smoke.py``,
``launch/serve.py``, ``chipbench/run.py``) call
``enable_compile_cache()`` before their first compile; importing a
library module or running the tests never turns the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: Repository checkout root (``src/repro/launch/`` is three levels down).
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory
    and return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
