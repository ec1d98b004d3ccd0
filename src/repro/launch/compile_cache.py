"""JAX's persistent compilation cache for the repo's entry points.

``enable_compile_cache()`` is called at the top of the ``main()`` of
``chip_smoke.py`` and ``repro.launch.serve`` — never at import, so
importing the library changes no JAX setting.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
this helper sets no path.  Otherwise the cache lives at the fixed path
``<repo>/.jax_cache`` (listed in ``.gitignore``): the cache directory is
part of what a later process must find again, so it never moves.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_ENV", "REPO_ROOT", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache(root=None) -> str:
    """Turn the persistent cache on and return its directory.

    ``root`` replaces the repo root (tests point it at a scratch tree).
    Every compiled program is cached, however short its compile: the
    kernels and decode programs of one run are few and each is reused
    by the next run.
    """
    import jax

    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(Path(root or REPO_ROOT) / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
