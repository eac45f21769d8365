"""Shared kernel utilities."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def interpret_default() -> bool:
    """Pallas TPU kernels execute for real only on TPU; everywhere else
    (this CPU container included) they run in interpret mode, which executes
    the kernel body with jnp semantics — bit-accurate for correctness
    validation against the ref oracles."""
    return jax.default_backend() != "tpu"


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def pad_dim(x: jax.Array, axis: int, to: int, value=0.0) -> jax.Array:
    pad = to - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)
