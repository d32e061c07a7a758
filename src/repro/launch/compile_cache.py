"""Where JAX keeps its persistent compilation cache.

Every entry point (`chip_smoke.py`, `launch/train.py`, the serving examples,
`benchmarks/run.py`) calls `configure_compile_cache()` before it compiles.
The cache directory is part of what a later run must find again, so it is
a fixed path: `JAX_COMPILATION_CACHE_DIR` when the environment sets it (JAX
reads that variable itself, and nothing is set here), else `.jax_cache` at
the root of the checkout (listed in `.gitignore`).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
