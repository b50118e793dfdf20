"""Where JAX's persistent compilation cache lives.

Entry points (``launch/serve.py``, ``benchmarks/run.py``, ``chip_smoke.py``)
call :func:`use_compile_cache` once from ``main``; importing this module
changes nothing. The cache key includes the directory, so the directory is
fixed: a path built from a temp name, a pid or the time would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the checkout's own cache directory (listed in ``.gitignore``)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    sets nothing. Otherwise the cache goes to ``<checkout>/.jax_cache``.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
