"""The one place that decides how compiled code meets the device.

A TPU compiles every ``pl.pallas_call`` through Mosaic; any other backend
(the CPU the unit tests use) runs them in the Pallas interpreter. Every
``interpret`` default in the compiler, the Library-Node expansions, the
kernels and the serving path is ``None``, meaning "ask
:func:`default_interpret`"; an explicit ``True``/``False`` still wins.

:func:`enable_compile_cache` places JAX's persistent compilation cache.
Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/*``)
call it; nothing calls it at import.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

#: the checkout's own cache directory (git-ignored); a fixed path, since
#: the directory is part of every cache key
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def default_interpret() -> bool:
    """``False`` on a TPU (compiled kernels), ``True`` everywhere else."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """An explicit choice, or the device's default when ``None``."""
    return default_interpret() if interpret is None else bool(interpret)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set; otherwise the cache goes to
    ``<checkout>/.jax_cache``."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
