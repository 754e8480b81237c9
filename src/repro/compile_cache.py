"""JAX's persistent compilation cache for the repo's entry points.

``chip_smoke.py`` and the benchmark entry points call
:func:`enable_compile_cache` before their first compile, so a later run of
the same programs on the same machine loads them instead of compiling them
again. Library code never calls it: importing ``repro`` changes no JAX
setting.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# A fixed path inside the checkout (listed in .gitignore): the cache key
# includes nothing of the path, but a directory that moves is never found.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed here; otherwise the cache goes to ``DEFAULT_DIR``."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
