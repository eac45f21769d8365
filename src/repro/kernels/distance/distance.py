"""Pallas TPU kernel: batched distance matrix (the ANNS beam-scoring loop).

Matmul-form: ``||q - x||^2 = ||q||^2 + ||x||^2 - 2 q.x`` so the inner loop is
an MXU matmul over 128-aligned (BQ, BD) x (BD, BX) tiles with an fp32 VMEM
accumulator; norms are folded in on the final reduction step.  Grid is
(nq/BQ, nx/BX, d/BD) with the d axis innermost (``arbitrary`` semantics —
sequential accumulation), so each (i, j) output tile stays resident in VMEM
across the whole reduction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(q_ref, x_ref, qn_ref, xn_ref, o_ref, acc_ref, *, nd: int,
            metric: str, precision):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        q_ref[...], x_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(2) == nd - 1)
    def _finish():
        dots = acc_ref[...]
        if metric == "ip":
            o_ref[...] = -dots
        else:
            qn = qn_ref[0, :]          # (BQ,)
            xn = xn_ref[0, :]          # (BX,)
            o_ref[...] = qn[:, None] + xn[None, :] - 2.0 * dots


@functools.partial(
    jax.jit,
    static_argnames=("metric", "bq", "bx", "bd", "precision", "interpret"))
def distance(
    q: jax.Array,              # (nq, d)
    x: jax.Array,              # (nx, d)
    *,
    metric: str = "l2",
    bq: int = 128,
    bx: int = 128,
    bd: int = 128,
    precision: jax.lax.Precision | None = None,
    interpret: bool = False,
) -> jax.Array:
    nq, d = q.shape
    nx, _ = x.shape
    assert nq % bq == 0 and nx % bx == 0 and d % bd == 0, (q.shape, x.shape)
    nd = d // bd

    qn = jnp.sum(q.astype(jnp.float32) ** 2, axis=1)[None, :]   # (1, nq)
    xn = jnp.sum(x.astype(jnp.float32) ** 2, axis=1)[None, :]   # (1, nx)

    grid = (nq // bq, nx // bx, nd)
    return pl.pallas_call(
        functools.partial(_kernel, nd=nd, metric=metric,
                          precision=precision),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, bd), lambda i, j, k: (i, k)),
            pl.BlockSpec((bx, bd), lambda i, j, k: (j, k)),
            pl.BlockSpec((1, bq), lambda i, j, k: (0, i)),
            pl.BlockSpec((1, bx), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bq, bx), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nq, nx), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, bx), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, x, qn, xn)
