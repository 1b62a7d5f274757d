"""Persistent XLA compilation cache for the launchers and the benchmark.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and
nothing here sets another path.  Otherwise the cache lives at a fixed
``<checkout>/.jax_cache`` (listed in ``.gitignore``): the directory is part
of what a later run looks up, so it is never derived from a temporary
name, a process id or the time.  An installed copy of the package (no
``pyproject.toml`` beside ``src/``) leaves the cache off.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on; returns its directory
    (None when it stays off).  Call before the first compilation."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if not (CHECKOUT / "pyproject.toml").is_file():
        return None
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
