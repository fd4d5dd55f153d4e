"""Persistent XLA compilation cache for the entry points (launchers,
benchmarks, ``chip_smoke.py``); the tests never turn it on.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
module sets nothing.  Otherwise the cache lives at ``<repo>/.jax_cache``:
a fixed path (the directory is part of each entry's key, so a temporary
or per-process directory would never hit), listed in ``.gitignore``."""
from __future__ import annotations

import os
from typing import Optional

ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<repo>/.jax_cache`` — next to ``src/``, whatever the cwd."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.normpath(os.path.join(here, "..", "..", "..",
                                         ".jax_cache"))


def enable_compilation_cache() -> Optional[str]:
    """Turn the persistent cache on for this process.  Returns the
    directory in use; with ``JAX_COMPILATION_CACHE_DIR`` set it is left
    to JAX and only reported."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
