"""Where JAX keeps its persistent compilation cache.

Entry points (`chip_smoke.py`, `python -m repro.api.cli`,
`benchmarks/run.py`) call `use_compile_cache()` once at start-up, before
anything compiles; importing this module sets nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path: the cache directory is part of an entry's key, so a
# directory that moved between runs would never hit.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Return the persistent compilation cache directory in use.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that variable itself
    and nothing is changed here; otherwise the cache goes to ``.jax_cache/``
    at the repository root."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
