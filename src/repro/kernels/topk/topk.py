"""Pallas TPU kernel: k-smallest selection over distance rows.

Grid over query-row tiles; the full candidate row (nx) lives in VMEM per
tile.  Selection is iterative min-extraction (k rounds of row-min + one-hot
mask-out) — k is small in the ANNS setting (beam width / result size), so
k * nx VPU work beats a full sort, and everything stays rank-2 for the VPU
(8x128 vregs).  Ties resolve to the lowest index (matches jax.lax.top_k).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 3.0e38  # python float: jnp scalars would be captured consts in the kernel


def _kernel(d_ref, vals_ref, idx_ref, *, k: int):
    d = d_ref[...].astype(jnp.float32)              # (BQ, NX)
    bq, nx = d.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, nx), 1)
    # output slot j is written by a select against a lane iota: Mosaic has
    # no lowering for a dynamic_update_slice along the lane axis
    slot = jax.lax.broadcasted_iota(jnp.int32, (bq, k), 1)

    def body(j, carry):
        d_cur, vals, idxs = carry
        m = jnp.min(d_cur, axis=1)                   # (BQ,)
        # lowest index attaining the min (tie-break like lax.top_k)
        is_min = d_cur <= m[:, None]
        a = jnp.min(jnp.where(is_min, col, nx), axis=1).astype(jnp.int32)
        vals = jnp.where(slot == j, m[:, None], vals)
        idxs = jnp.where(slot == j, a[:, None], idxs)
        d_cur = jnp.where(col == a[:, None], BIG, d_cur)
        return d_cur, vals, idxs

    vals0 = jnp.zeros((bq, k), jnp.float32)
    idx0 = jnp.zeros((bq, k), jnp.int32)
    _, vals, idxs = jax.lax.fori_loop(0, k, body, (d, vals0, idx0))
    vals_ref[...] = vals
    idx_ref[...] = idxs


@functools.partial(jax.jit, static_argnames=("k", "bq", "interpret"))
def topk_smallest(
    d: jax.Array,             # (nq, nx)
    k: int,
    *,
    bq: int = 8,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    nq, nx = d.shape
    assert nq % bq == 0, (nq, bq)
    grid = (nq // bq,)
    return pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=grid,
        in_specs=[pl.BlockSpec((bq, nx), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i: (i, 0)),
            pl.BlockSpec((bq, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq, k), jnp.float32),
            jax.ShapeDtypeStruct((nq, k), jnp.int32),
        ],
        interpret=interpret,
    )(d)
