"""JAX's persistent compilation cache, switched on by the entry points.

A process that compiles the programs an earlier one compiled (the same
design, shapes and device) reads them back instead of compiling again.
``chip_smoke.py``, ``python -m repro`` and the benchmarks call
:func:`enable` once, before they compile; library code and tests never
do.
"""
from __future__ import annotations

import os

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset: a
#: fixed path inside the checkout (git ignores it).  It never depends on
#: a temp name, pid or time — a directory that moves never hits.
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set here; otherwise the cache goes to
    :data:`REPO_CACHE_DIR`.  Executables are cached whatever their
    compile time: a design's group executables each compile in less
    than JAX's default one-second floor."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
