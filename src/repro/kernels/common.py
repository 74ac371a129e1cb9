"""Shared kernel helpers."""
from __future__ import annotations

import jax


def use_interpret() -> bool:
    """Pallas TPU kernels execute via the interpreter off-TPU (CPU CI)."""
    return jax.default_backend() != "tpu"


def pad_to(x, multiple: int, axis: int):
    """Zero-pad ``axis`` of x up to a multiple; returns (padded, orig_len)."""
    import jax.numpy as jnp

    n = x.shape[axis]
    rem = (-n) % multiple
    if rem == 0:
        return x, n
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads), n
