"""Where JAX keeps its persistent compile cache.

The entry points (`chip_smoke.py`, `launch/train.py`, `launch/serve.py`,
`benchmarks/run.py`) call `enable_compile_cache()` first thing in their
main; nothing calls it at import.  A cache's path is part of its key, so
the directory is fixed: `JAX_COMPILATION_CACHE_DIR` when it is set (JAX
reads it itself), otherwise `<repo>/.jax_cache`.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
