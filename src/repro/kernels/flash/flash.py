"""Pallas TPU kernel: causal flash-attention forward.

Grid = (batch*kv_heads, q_blocks, kv_blocks), kv innermost with
``arbitrary`` semantics; running (m, l, acc) live in VMEM scratch across the
kv sweep and the normalised output is emitted on the last kv step.  Blocks
fully above the causal diagonal (or outside the sliding window band) are
skipped with ``pl.when`` — the MXU sees only the valid triangle/band, which
is the FLOP-level equivalent of the "triangle" jnp path in
``repro.models.attention``.

GQA is handled by loading one kv head per grid row and the matching group of
``G`` query heads folded into the q-block rows (``BQ * G`` MXU rows), so kv
tiles are read once per group, not once per query head — the bandwidth win
that makes GQA decode fast on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            q_scale: float, window: int, softcap: float,
            bq: int, bk: int, nk: int, g: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # block band check is static per (qi, kj) would need dynamic grid; use
    # pl.when on the dynamic ids — Mosaic turns this into a cheap predicate.
    q_start = qi * bq
    k_start = kj * bk
    in_band = k_start <= q_start + bq - 1
    if window > 0:
        in_band &= (k_start + bk - 1) > (q_start - window)

    @pl.when(in_band)
    def _compute():
        q = q_ref[0, 0]                               # (BQ*G, D)
        k = k_ref[0]                                  # (BK, D)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * q_scale   # (BQ*G, BK)
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g + q_start
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + k_start
        mask = cols <= rows
        if window > 0:
            mask &= cols > rows - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _emit():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "q_scale", "window", "softcap", "bq", "bk", "interpret"))
def flash_attention(
    q: jax.Array,     # (BH, S, G, D) — one kv head per leading row
    k: jax.Array,     # (BH, S, D)
    v: jax.Array,     # (BH, S, D)
    *,
    q_scale: float,
    window: int = 0,
    softcap: float = 0.0,
    bq: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    BH, S, G, D = q.shape
    assert S % bq == 0 and S % bk == 0
    nq, nk = S // bq, S // bk
    qf = q.reshape(BH, nq, bq * G, D)  # fold group into rows per q block

    grid = (BH, nq, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, q_scale=q_scale, window=window,
                          softcap=softcap, bq=bq, bk=bk, nk=nk, g=G),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq * G, D), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq * G, D), lambda b, i, j: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, nq, bq * G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq * G, 1), jnp.float32),
            pltpu.VMEM((bq * G, 1), jnp.float32),
            pltpu.VMEM((bq * G, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, k, v)
    return out.reshape(BH, S, G, D)
