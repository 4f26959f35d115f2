"""JAX's persistent compilation cache, placed for the entry points.

Entry points (`chip_smoke.py`, `repro.launch.serve`, `repro.launch.train`,
`benchmarks/bench_sim.py`) call `enable_compile_cache()` once, before
their first compile. Importing `repro` never touches the cache.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
sets nothing. Otherwise the cache lives at `<checkout>/.jax_cache`, a
fixed path (the path is part of the cache key, so a directory that moves
between runs never hits).
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
