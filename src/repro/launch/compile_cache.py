"""Where JAX keeps its persistent compilation cache.

Called by the entry points (`repro.launch.serve.main`,
`benchmarks/run.py`, `chip_smoke.py`), never at import time.  An
explicitly exported `JAX_COMPILATION_CACHE_DIR` wins and JAX reads it
itself; otherwise the cache lives at one fixed directory inside the
checkout.  That path is never derived from a temp name, a pid or the
clock, so every run of one checkout finds the entries of the last.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_compile_cache"


def use_compile_cache() -> str:
    """Enable the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
