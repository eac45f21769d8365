"""Pure-jnp oracle for the batched distance-matrix kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def distance_ref(q: jnp.ndarray, x: jnp.ndarray, metric: str = "l2") -> jnp.ndarray:
    """q: (nq, d), x: (nx, d) -> (nq, nx) fp32 distances.

    l2: squared euclidean.  ip: negative inner product (smaller = closer),
    which is angular distance when inputs are unit-normalised.  The dot
    runs at ``Precision.HIGHEST`` so the oracle (and the ground truth built
    on it) is float32 on a TPU too, not a single bf16 pass.
    """
    qf = q.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dots = jnp.matmul(qf, xf.T, precision=jax.lax.Precision.HIGHEST)
    if metric == "ip":
        return -dots
    qn = jnp.sum(qf * qf, axis=1, keepdims=True)
    xn = jnp.sum(xf * xf, axis=1, keepdims=True)
    return qn + xn.T - 2.0 * dots
