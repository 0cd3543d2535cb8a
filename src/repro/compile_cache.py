"""Where JAX keeps its persistent compilation cache.

Every entry point calls `configure_compile_cache()` before its first
compile: `python -m repro.explore`, the train and serve launchers,
`benchmarks/run.py`, `chip_smoke.py` and each fleet worker. When
`JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing here
sets another directory. Otherwise the cache lives at a fixed path inside
the checkout, `<repo>/.jax_cache`: the cache is keyed by program, and a
directory that moves from run to run never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
