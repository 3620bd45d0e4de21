"""JAX's persistent compilation cache for the repo's entry points.

A measured profile jits one program per (layer, candidate, batch), so
one planning pass compiles hundreds of programs.  The entry points
(``chip_smoke.py``, ``benchmarks/run.py``, the ``examples/`` mains) turn
the persistent cache on so a later run finds them compiled.  Library
import and the tests never do.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: The checkout's own cache directory.  It is fixed, not derived from
#: the working directory, a temp name, a pid or the time: JAX keys its
#: entries by path, so a directory that moves never hits.
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it already
    and it stands.  Otherwise the cache goes to ``<repo>/.jax_cache``.
    Compiles of any duration are cached.  Call before the first compile.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
