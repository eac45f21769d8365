"""Pallas TPU kernel: int8 asymmetric quantized distance (refinement module).

The int8 base tile (BX, BD) is dequantised in-register against the per-vector
scale and hits the MXU in bf16-ish fp32 accumulation.  HBM traffic for the
base vectors is 4x lower than fp32 — on the real part this kernel is
bandwidth-bound, which is exactly the regime the paper's quantized
preliminary search targets (§2.3).  Norms of the *quantized* vectors are
precomputed by the wrapper so l2 distances are exact w.r.t. the quantized
representation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(q_ref, x_ref, s_ref, qn_ref, xn_ref, o_ref, acc_ref, *,
            nd: int, metric: str):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xf = x_ref[...].astype(jnp.float32) * s_ref[0, :][:, None]   # dequant (BX, BD)
    acc_ref[...] += jax.lax.dot_general(
        q_ref[...].astype(jnp.float32), xf,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(2) == nd - 1)
    def _finish():
        dots = acc_ref[...]
        if metric == "ip":
            o_ref[...] = -dots
        else:
            o_ref[...] = qn_ref[0, :][:, None] + xn_ref[0, :][None, :] - 2.0 * dots


@functools.partial(
    jax.jit, static_argnames=("metric", "bq", "bx", "bd", "interpret"))
def qdist(
    q: jax.Array,               # (nq, d) fp
    xq: jax.Array,              # (nx, d) int8
    scale: jax.Array,           # (nx,) fp32
    *,
    metric: str = "l2",
    bq: int = 128,
    bx: int = 128,
    bd: int = 128,
    interpret: bool = False,
) -> jax.Array:
    nq, d = q.shape
    nx, _ = xq.shape
    assert nq % bq == 0 and nx % bx == 0 and d % bd == 0
    nd = d // bd

    qf = q.astype(jnp.float32)
    qn = jnp.sum(qf * qf, axis=1)[None, :]
    # norms of the dequantised base vectors (exact w.r.t. quantised rep)
    xn = (jnp.sum(xq.astype(jnp.float32) ** 2, axis=1) * scale ** 2)[None, :]
    s2 = scale[None, :]

    grid = (nq // bq, nx // bx, nd)
    return pl.pallas_call(
        functools.partial(_kernel, nd=nd, metric=metric),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, bd), lambda i, j, k: (i, k)),
            pl.BlockSpec((bx, bd), lambda i, j, k: (j, k)),
            pl.BlockSpec((1, bx), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bq), lambda i, j, k: (0, i)),
            pl.BlockSpec((1, bx), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bq, bx), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nq, nx), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, bx), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, xq, s2, qn, xn)
