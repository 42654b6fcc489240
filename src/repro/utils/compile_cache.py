"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.run_graph``, the
benchmark scripts) call :func:`enable_compile_cache` before their first
compile; library modules never do.  The cache directory is part of each
entry's key, so it must not move between runs: it is
``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself), else the fixed ``<checkout>/.jax_cache``.

An entry's key includes the program's metadata (``op_name``, source
locations), which JAX leaves out by default: a program whose
``jax.named_scope``s changed, and nothing else, would otherwise load an
executable compiled under the old names, and a profiler trace of it
would show those.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
