"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points (chip_smoke.py, examples, launch/train.py, benchmarks/run.py)
call `use_compile_cache()` once before their first compile; importing this
module changes nothing.  A run finds what earlier runs compiled only if the
directory stays where they left it: a temporary directory, or a pid or a
time in the path, would never be found again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (listed in .gitignore): this file is
# <checkout>/src/repro/launch/compile_cache.py.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    If JAX_COMPILATION_CACHE_DIR is set, JAX already reads it and nothing
    is set here; otherwise the cache goes to <checkout>/.jax_cache."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
