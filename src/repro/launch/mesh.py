"""Device set-up: production meshes and the persistent compilation cache.

Importing this module never touches jax device state; meshes are built
only inside the functions.  The dry-run (and only the dry-run) forces 512
host platform devices via XLA_FLAGS before any jax import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> None:
    """Keep JAX's persistent compilation cache at a fixed path.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it already and
    it is left alone.  Otherwise the cache goes to ``<checkout>/.jax_cache``:
    never a temporary name, a process id or the time, since a cache that
    moves is never found again.  Call before the first compile.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT / ".jax_cache"))


def make_production_mesh(*, multi_pod: bool = False):
    """Single-pod (16, 16) = 256 chips, or 2-pod (2, 16, 16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes))


def single_device_mesh():
    """1x1 mesh for smoke tests / CPU engine runs."""
    return jax.make_mesh((1, 1), ("data", "model"))
