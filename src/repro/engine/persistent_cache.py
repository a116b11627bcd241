"""JAX's persistent compilation cache for the repo's entry points.

Compiled executables (XLA programs and Mosaic kernels) are written to disk
so a second process on the same machine skips the compiles. Entry points
(``chip_smoke.py``, ``examples/serve_chordality.py``, ``bench/run.py``)
call :func:`enable_persistent_cache` before their first compile; library
imports and the tests never do.
"""
from __future__ import annotations

import os
import pathlib

#: The variable JAX reads its cache directory from.
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

#: Fixed fallback location: the cache key includes the directory, so a
#: path built from a temp name, pid or time would never hit.
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_persistent_cache() -> str:
    """Turn on the persistent compile cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX's own setting stands and
    no other directory is set. Otherwise the cache lives at
    ``<checkout>/.jax_cache``. Every compile is cached, however short: the
    serving path compiles many small bucket programs.
    """
    import jax

    path = os.environ.get(CACHE_DIR_ENV)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
