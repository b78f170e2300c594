"""Persistent compilation cache location for the launchers.

JAX keys its persistent cache on the directory path, so the directory
must not move between runs: it is derived from the checkout, never from
a temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> Optional[Path]:
    """Keep compiled programs in ``<checkout>/.jax_cache``.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed (returns None).  Call before the first compile.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    path = CHECKOUT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path
