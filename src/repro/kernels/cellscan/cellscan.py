"""Pallas TPU kernel: the IVF cell scan, one probed cell read as blocks.

Each grid step ``(b, j, t)`` scores chunk ``t`` of the cell query ``b``
probes ``j``-th: one contiguous ``(dp, chunk)`` int8 block (the codes of
``chunk`` rows, stored transposed so the rows run along the lanes), read
by one DMA chosen through the scalar-prefetched ``first``/``live``
tables.  Chunks past the cell's end (``t >= live``) map to the previous
block index, so Pallas issues no DMA for them, and score BIG.

The dot runs on the VPU in float32: each 32-row slab of the block is
widened to f32, multiplied by the query's matching slab (broadcast along
the lanes by the wrapper) and summed down the sublanes, so the result
row comes out lane-dense.  Then ``d = |q|² + norm − 2·scale·dot`` (l2)
or ``−scale·dot`` (ip), per row.

:func:`align_blocks` lays the blocks out once per layout: it copies each
cell's rows from a chunked copy of the cell-major codes (which starts a
chunk at every ``chunk``-th row, whatever the cell) to a chunk boundary
of its own, by shifting each 128-lane tile of two source chunks.  The
per-layout program is then one small kernel, which compiles in a
fraction of the time an XLA gather of the rows takes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 3.0e38
#: rows of an int8 tile: the codes' dims are read 32 at a time
SLAB = 32
LANES = 128


def _kernel(first_ref, live_ref, q_ref, qn_ref, codes_ref, rows_ref, o_ref,
            *, nprobe: int, tpp: int, metric: str):
    b, j, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    r = j * tpp + t                       # this step's row of the output
    dp, chunk = codes_ref.shape[1], codes_ref.shape[2]
    tiles = chunk // LANES

    def slab(s, acc):
        lo = pl.multiple_of(s * SLAB, SLAB)
        qs = q_ref[0, pl.ds(lo, SLAB), :]                    # (32, 128)
        out = []
        for c in range(tiles):
            x = codes_ref[0, pl.ds(lo, SLAB), c * LANES:(c + 1) * LANES]
            p = x.astype(jnp.float32) * qs
            out.append(acc[c] + ((p[0:8] + p[8:16]) + (p[16:24] + p[24:32])))
        return tuple(out)

    def score():
        zero = jnp.zeros((8, LANES), jnp.float32)
        acc = jax.lax.fori_loop(0, dp // SLAB, slab, (zero,) * tiles)
        dot = jnp.concatenate([a.sum(axis=0, keepdims=True) for a in acc],
                              axis=1)                        # (1, chunk)
        sdot = rows_ref[0, 0:1, :] * dot
        if metric == "ip":
            return -sdot
        return qn_ref[0, 0:1, 0:1] + rows_ref[0, 1:2, :] - 2.0 * sdot

    live = live_ref[b * nprobe + j]
    row = jax.lax.cond(t < live, score,
                       lambda: jnp.full((1, chunk), BIG, jnp.float32))
    # write sublane r % 8 of the resident (8, chunk) group r // 8
    grp = o_ref[0, r // 8]
    sub = jax.lax.broadcasted_iota(jnp.int32, grp.shape, 0)
    o_ref[0, r // 8] = jnp.where(sub == r % 8,
                                 jnp.broadcast_to(row, grp.shape), grp)


@functools.partial(jax.jit, static_argnames=("tpp", "metric", "interpret"))
def cell_scan_blocks(first, live, qb, qn, codes, rows, *, tpp: int,
                     metric: str, interpret: bool = False) -> jax.Array:
    """Score ``tpp`` chunks of every probed cell of every query.

    ``first``/``live``: (B·nprobe,) int32, the first chunk and the live
    chunk count of the cell each (query, probe) reads; ``qb``: (B, dp,
    128) f32 queries broadcast along the lanes; ``qn``: (B, 1, 128) f32
    squared query norms (broadcast); ``codes``: (n_chunks, dp, chunk)
    int8; ``rows``: (n_chunks, 2, chunk) f32, each row's scale and the
    squared norm of its dequantised vector.  Returns (B, G, 8, chunk)
    f32, ``G = ceil(nprobe·tpp / 8)``: row ``j·tpp + t`` of query ``b``
    (counted across the groups) holds chunk ``t`` of its ``j``-th probe.
    """
    B, dp, _ = qb.shape
    chunk = codes.shape[2]
    nprobe = first.shape[0] // B
    groups = pl.cdiv(nprobe * tpp, 8)

    def code_block(b, j, t, first, live):
        i = b * nprobe + j
        return (first[i] + jnp.minimum(t, jnp.maximum(live[i] - 1, 0)), 0, 0)

    return pl.pallas_call(
        functools.partial(_kernel, nprobe=nprobe, tpp=tpp, metric=metric),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nprobe, tpp),
            in_specs=[
                pl.BlockSpec((1, dp, LANES), lambda b, j, t, *_: (b, 0, 0)),
                pl.BlockSpec((1, 1, LANES), lambda b, j, t, *_: (b, 0, 0)),
                pl.BlockSpec((1, dp, chunk), code_block),
                pl.BlockSpec((1, 2, chunk), code_block),
            ],
            out_specs=pl.BlockSpec((1, groups, 8, chunk),
                                   lambda b, j, t, *_: (b, 0, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, groups, 8, chunk), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(first, live, qb, qn, codes, rows)


def _align_kernel(src_ref, rem_ref, ca_ref, cb_ref, ra_ref, rb_ref,
                  co_ref, ro_ref):
    """Block ``k`` = source rows ``src[k]`` onward, ``rem[k]`` of them
    live: virtual lanes ``src % chunk`` onward of source chunks ``a`` and
    ``a + 1``; lanes past ``rem`` are zero."""
    k = pl.program_id(0)
    chunk = ca_ref.shape[2]
    tiles = chunk // LANES
    r = src_ref[k] % chunk
    q, b = r // LANES, r % LANES
    rem = rem_ref[k]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    shift = (LANES - b) % LANES            # roll(x, shift)[l] = x[l + b]

    def tile(i, carry):
        def at(a_ref, b_ref, v):           # virtual tile v of [a | b]
            lo = pl.multiple_of((v % tiles) * LANES, LANES)
            xa = a_ref[0, :, pl.ds(lo, LANES)]
            xb = b_ref[0, :, pl.ds(lo, LANES)]
            return jnp.where(v < tiles, xa, xb)

        def shifted(a_ref, b_ref, cast):
            x0 = pltpu.roll(cast(at(a_ref, b_ref, q + i)), shift, 1)
            x1 = pltpu.roll(cast(at(a_ref, b_ref, q + i + 1)), shift, 1)
            y = jnp.where(lane < LANES - b, x0, x1)
            return jnp.where(i * LANES + lane < rem, y, 0)

        out = pl.ds(pl.multiple_of(i * LANES, LANES), LANES)
        co_ref[0, :, out] = shifted(
            ca_ref, cb_ref, lambda x: x.astype(jnp.int32)).astype(jnp.int8)
        ro_ref[0, :, out] = shifted(ra_ref, rb_ref, lambda x: x)
        return carry

    jax.lax.fori_loop(0, tiles, tile, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def align_blocks(src, rem, codes, rows, *, interpret: bool = False):
    """``src``/``rem``: (n_blocks,) int32, each block's first source row
    and live rows; ``codes``: (m, dp, chunk) int8 and ``rows``: (m, 2,
    chunk) f32, the chunked source, with a chunk past every ``src``.
    Returns the blocks: (n_blocks, dp, chunk) int8, (n_blocks, 2, chunk)
    f32."""
    n_blocks = src.shape[0]
    _, dp, chunk = codes.shape

    def first(k, src, rem):
        return (src[k] // chunk, 0, 0)

    def second(k, src, rem):
        return (src[k] // chunk + 1, 0, 0)

    def out(k, src, rem):
        return (k, 0, 0)

    return pl.pallas_call(
        _align_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_blocks,),
            in_specs=[pl.BlockSpec((1, dp, chunk), first),
                      pl.BlockSpec((1, dp, chunk), second),
                      pl.BlockSpec((1, 2, chunk), first),
                      pl.BlockSpec((1, 2, chunk), second)],
            out_specs=[pl.BlockSpec((1, dp, chunk), out),
                       pl.BlockSpec((1, 2, chunk), out)],
        ),
        out_shape=[jax.ShapeDtypeStruct((n_blocks, dp, chunk), jnp.int8),
                   jax.ShapeDtypeStruct((n_blocks, 2, chunk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(src, rem, codes, codes, rows, rows)
