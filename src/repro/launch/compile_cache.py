"""JAX's persistent compilation cache for the entry points.

``enable_compile_cache()`` is called from an entry point's ``main()`` (serve,
train, the benchmark driver, ``chip_smoke.py``), never on import. Where
``JAX_COMPILATION_CACHE_DIR`` is set the cache lives there and nowhere
else; otherwise it lives at one fixed, git-ignored directory of the
checkout, so a later run of the same checkout finds what an earlier one
compiled (the directory is part of the cache key: a path that moves never
hits).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
