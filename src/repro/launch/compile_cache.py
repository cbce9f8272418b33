"""The one place that turns on JAX's persistent compilation cache.

Called from the entry points' ``main`` (``chip_smoke.py``,
``repro.launch.explore``, ``perfbench/run.py``), never at import, so
tests and library users keep JAX's defaults.
"""
from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache — fixed, because the directory is part of what a
# later run looks the cache up by; .gitignore lists it
REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing here overrides it. Otherwise the cache goes to
    ``REPO_CACHE_DIR`` inside the checkout.

    The cache key keeps the programs' metadata (op names, named scopes,
    source locations): JAX's default key leaves it out, and a program whose
    scopes changed would then load an executable carrying the old op names,
    which a profiler trace would show."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
