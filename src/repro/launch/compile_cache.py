"""JAX's persistent compilation cache, placed for the entry points.

Each entry point (``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``, ``chip_smoke.py``) calls
:func:`enable_compile_cache` once before its first compile. Nothing
happens at import.
"""
from __future__ import annotations

import os
from pathlib import Path

# The checkout's own cache directory (git-ignored). A fixed path: the
# directory is part of every cache key, so a moving one never hits.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set here; otherwise the cache goes to
    ``<checkout>/.jax_cache``.

    The cache key keeps the programs' op names, ``jax.named_scope``
    scopes included: by default JAX strips that metadata from the key, so
    a program whose scopes changed would load the executable of its
    unscoped twin, and a profiler trace, which reads op names from the
    loaded executable, would show the stale ones. The programs carry no
    Python frames in their op locations (so no source file or line in
    their metadata or a profile's op details), which keeps the key the
    same across checkout directories and edits that move source lines."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
