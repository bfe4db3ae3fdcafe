"""Where JAX keeps its persistent compilation cache.

Every entry point (the training and serving CLIs, the benchmark harness,
``chip_smoke.py``) calls :func:`enable_compile_cache` before it compiles
anything, so a second run of the same program on the same chip reads its
kernels back instead of compiling them again.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; the helper
  leaves it alone and sets no other path.
* Otherwise the cache goes to ``<repo>/.jax_cache`` (git-ignored). The
  path is fixed on purpose: a temporary, per-process or per-run
  directory starts empty every time, so nothing would ever be read back.
"""
from __future__ import annotations

import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO, ".jax_cache")
ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory."""
    import jax

    path = os.environ.get(ENV)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
