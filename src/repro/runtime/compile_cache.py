"""Where JAX's persistent compilation cache lives.

Entry points (`chip_smoke.py`, `benchmarks/run.py`, the `launch/`
scripts) call `enable_compile_cache()` first thing; importing a library
module never turns the cache on.  A directory named by
`JAX_COMPILATION_CACHE_DIR` wins, and JAX already reads it on its own,
so nothing else is set then.  Otherwise the cache lives at one fixed
path inside the checkout, `<repo>/.jax_cache` (listed in .gitignore):
the path is part of each entry's key, so it must not move between runs.
"""
from __future__ import annotations

import os
import pathlib

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache for this process and
    return its directory."""
    import jax

    env_dir = os.environ.get(CACHE_ENV)
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
