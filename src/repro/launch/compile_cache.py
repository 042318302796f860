"""Where JAX keeps its persistent compilation cache.

Every entry point (launch/serve.py, launch/train.py, launch/dryrun.py,
benchmarks/run.py, chip_smoke.py) calls `use_compile_cache()` once at
start-up, so their processes share compiled programs across runs.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <repo>/.jax_cache: a fixed path, because the path is part of the
# cache's key — a directory that moves never hits. Listed in .gitignore.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Leave the cache to JAX where JAX_COMPILATION_CACHE_DIR is set;
    otherwise keep it in the repo's fixed `.jax_cache` directory."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
