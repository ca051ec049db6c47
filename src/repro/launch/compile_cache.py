"""Placement of JAX's persistent compilation cache for command-line entry points.

Every plan is one ``(template, bucket)`` pair and compiles its fixpoint from
nothing in a fresh process, so the scripts that drive the system
(``chip_smoke.py``, ``repro.launch.serve`` and the benchmark ``main``
functions) call :func:`enable_compile_cache` first thing.  It is never
called at import, so importing the library — and the test suite — leaves
JAX's cache settings alone.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is set here.  Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` (listed in ``.gitignore``): the directory is part
of the cache key, so a path that moves between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns the directory in use."""
    configured = os.environ.get(ENV_VAR)
    if configured:
        return configured
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
