"""JAX's persistent compilation cache, at one fixed place.

Entry points that compile for the chip (``chip_smoke.py``, ``python -m
repro.bench``, ``repro.launch.serve``, ``repro.launch.train``) call
``enable_compile_cache()`` before their first compile.  Importing the
library never does: a test process keeps JAX's defaults.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and that
directory is the cache.  Otherwise the cache lives at ``<repo>/.jax_cache``
(listed in ``.gitignore``): a fixed path, because the path is part of the
cache key and a directory that moves never hits.  Enabling the cache also
starts ``repro.obs``'s compile counter, so that every compile or cache
load after it is counted.
"""
from __future__ import annotations

import os

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    from repro.obs.telemetry import compile_counter

    compile_counter()
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or REPO_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
