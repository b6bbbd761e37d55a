"""JAX's persistent compilation cache for the repo's entry points.

`enable()` is called from the `main()` of each entry point
(`repro.launch.serve`, `benchmarks/perf_engine.py`, `chip_smoke.py`),
never at import, so importing the library changes no JAX setting.
"""
from __future__ import annotations

import os
import pathlib

import jax

# A fixed directory inside the checkout (listed in .gitignore). The path
# is part of what the cache is keyed on, so it must not move between
# runs: never a temporary name, a pid or the time.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already keeps its cache
    there and this sets no other. Otherwise the cache goes to
    `REPO_CACHE_DIR`. Every compiled program is cached, however quick
    its compile: a serving run compiles many small ones.
    """
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
