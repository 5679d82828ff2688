"""Placement of JAX's persistent compilation cache.

Entry points (`chip_smoke.py`, `benchmarks/run.py`, the `examples/`
CLIs) call `enable_compile_cache()` before their first compile. When
`JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing is
set in code. Otherwise the cache lives at `<checkout>/.jax_cache`: a
fixed path, because the directory is part of what a later run must find
again.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: The repository checkout this package was imported from.
CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def enable_compile_cache() -> pathlib.Path:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return pathlib.Path(env)
    path = CHECKOUT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path
