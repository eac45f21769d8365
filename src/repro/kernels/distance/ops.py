"""Public distance-matrix op: pads to tile alignment, dispatches kernel or
interpret mode, slices back."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import interpret_default, pad_dim, round_up
from repro.kernels.distance.distance import distance as _distance_kernel
from repro.kernels.distance.ref import distance_ref


@functools.partial(jax.jit,
                   static_argnames=("metric", "precision", "use_kernel"))
def pairwise_distance(
    q: jax.Array,
    x: jax.Array,
    *,
    metric: str = "l2",
    precision: jax.lax.Precision | None = None,
    use_kernel: bool | None = None,
) -> jax.Array:
    """(nq, d) x (nx, d) -> (nq, nx) fp32; smaller = closer for both metrics.

    ``precision`` is the kernel's MXU contract precision: ``None`` keeps
    Mosaic's default, ``Precision.HIGHEST`` contracts in float32."""
    if use_kernel is None:
        use_kernel = True
    if not use_kernel:
        return distance_ref(q, x, metric)

    nq, d = q.shape
    nx, _ = x.shape
    bq = 128 if nq >= 128 else max(8, round_up(nq, 8))
    bx = 128 if nx >= 128 else max(128, round_up(nx, 128))
    bd = 128 if d >= 128 else round_up(d, 128)
    qp = pad_dim(q, 0, round_up(nq, bq))
    qp = pad_dim(qp, 1, round_up(d, bd))
    xp = pad_dim(x, 0, round_up(nx, bx))
    xp = pad_dim(xp, 1, round_up(d, bd))
    out = _distance_kernel(qp, xp, metric=metric, bq=bq, bx=bx, bd=bd,
                           precision=precision, interpret=interpret_default())
    return out[:nq, :nx]
