"""Where JAX's persistent compilation cache lives.

Entry points call :func:`use_compile_cache` before they compile anything,
so that every process started from one checkout finds the executables an
earlier one compiled: ``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train`` and ``benchmarks.serve_bench``. A full-width
serving engine compiles a dozen executables, and the cache is also hit
within one process, by a second engine or replica with the same shapes.

Placement can be set from outside: with ``JAX_COMPILATION_CACHE_DIR`` in
the environment JAX reads it itself and nothing is set here. Otherwise
the cache goes to ``.jax_cache`` at the root of the checkout (git-ignored)
— a fixed path, because the directory is part of what makes an entry
found again. Nothing here runs at import time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def checkout_cache_dir() -> str:
    """The in-checkout cache directory: ``<checkout>/.jax_cache``."""
    return str(Path(__file__).resolve().parents[3] / ".jax_cache")


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Honours ``JAX_COMPILATION_CACHE_DIR`` (JAX has already read it; no
    other directory is set in code), else points JAX at
    :func:`checkout_cache_dir`."""
    import jax
    outside = os.environ.get(ENV_VAR)
    if outside:
        return outside
    path = checkout_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
