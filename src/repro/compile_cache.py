"""Where JAX keeps its persistent compilation cache.

Entry points call `use_compile_cache()` at the start of `main`, before
anything compiles; importing this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[2]


def use_compile_cache() -> Path:
    """Point JAX's persistent compilation cache at `JAX_COMPILATION_CACHE_DIR`
    when it is set, and otherwise at the fixed `.jax_cache/` at the repo
    root.  The path is part of each entry's key, so it never depends on a
    temp name, a PID or the time.  Returns the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return Path(path)
