"""JAX's persistent compilation cache, placed from outside the program.

The entry points — `python -m repro.launch.serve_cnn`, `chip_smoke.py` and
`benchmarks/run.py` — call `enable_compile_cache()` once, before they
compile anything. No library module turns the cache on when it is imported,
and neither does the test suite.

Where `JAX_COMPILATION_CACHE_DIR` is set, the cache lives there and nowhere
else. Otherwise it lives at one fixed path inside the checkout,
`<checkout>/.jax_cache` (git-ignored): the path is part of what a cache
entry is found by, so it is never built from a temporary name, a process id
or the time.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """`$JAX_COMPILATION_CACHE_DIR` when set, else the checkout's cache."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at `compile_cache_dir()` and
    return that directory."""
    import jax

    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
