"""Where JAX keeps its persistent compilation cache.

Every entry point calls ``enable_compile_cache()`` from its ``main()``,
never at import.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has
already read it and this sets nothing.  Otherwise the cache lives at a
fixed path inside the checkout, ``<repo>/.jax_cache`` (ignored by git):
the directory is part of what a cached program is found by, so it never
holds a temp name, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Points JAX's persistent compilation cache at the environment's
    directory, else the checkout's fixed one, and returns it."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
