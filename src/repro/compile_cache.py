"""The persistent XLA compilation cache of the program's entry points.

A run that starts with no compiled code spends much of its time compiling,
and JAX keys its persistent cache by directory, so the directory must not
move between runs.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
keeps its cache there; otherwise the entry points use one fixed directory in
the checkout (listed in ``.gitignore``).  Only entry points call
:func:`enable_compile_cache`, and only when run as programs: importing a
module, or a test calling ``main()``, never turns the cache on.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else the checkout's own."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(CHECKOUT_CACHE_DIR))


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`cache_dir`."""
    import jax
    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
