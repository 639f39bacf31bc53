"""Where the entry points keep JAX's persistent compilation cache.

Called from the ``main()`` of each entry point (``chip_smoke.py``,
``repro.launch.serve``, ``repro.serve.workload``), never at import. When
``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the cache and no
other is set. Otherwise the cache is ``<checkout>/.jax_cache`` — a fixed
path (git-ignored), because the path is part of what a later run must find
again: a name derived from a temp directory, a pid or the time never hits.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
