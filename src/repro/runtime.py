"""Runtime execution settings.

``compute_dtype`` is bf16 on TPU (MXU-native) but f32 on CPU, where XLA's
DotThunk cannot *execute* bf16×bf16→f32 (lowering works — the dry-run forces
bf16 via :func:`set_compute_dtype` so the compiled HLO matches the TPU target's
byte counts, but never runs the executable).

``enable_compile_cache`` turns on JAX's persistent compilation cache; entry
points call it, importing this module never does.
"""
from __future__ import annotations

import contextlib
import os
from pathlib import Path

import jax
import jax.numpy as jnp

__all__ = ["compute_dtype", "set_compute_dtype", "use_compute_dtype",
           "enable_compile_cache"]

# <repo root>/.jax_cache — fixed, because the directory is part of what a
# later process must find again
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside when it is
    set; otherwise it lives at the fixed, git-ignored ``<repo
    root>/.jax_cache``. Call it from entry points (``main()``), never at
    import.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path

_OVERRIDE = None


def compute_dtype():
    if _OVERRIDE is not None:
        return _OVERRIDE
    return jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32


def set_compute_dtype(dt) -> None:
    global _OVERRIDE
    _OVERRIDE = dt


@contextlib.contextmanager
def use_compute_dtype(dt):
    global _OVERRIDE
    prev = _OVERRIDE
    _OVERRIDE = dt
    try:
        yield
    finally:
        _OVERRIDE = prev
