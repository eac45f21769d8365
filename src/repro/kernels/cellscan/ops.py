"""The IVF cell scan over a chunk-aligned copy of the int8 codes.

:func:`cell_blocks` lays the codes out once per layout, as the kernel
reads them: cell-major, every cell starting on a chunk boundary, each
chunk's rows along the lanes.  :func:`cell_scan` then scores the probed
cells of a query batch into the same ``(B, nprobe·pad)`` matrix the
per-row gather gave, slot ``j·pad + r`` holding row ``r`` of the
``j``-th probed cell (BIG past the cell's live chunks; the caller masks
pad slots, as it did before).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.cellscan.cellscan import (LANES, SLAB, align_blocks,
                                             cell_scan_blocks)
from repro.kernels.common import interpret_default, pad_dim, round_up

#: bytes of int8 codes one grid step reads (one chunk)
STEP_BYTES = 256 * 1024


class CellBlocks(NamedTuple):
    """The int8 codes as :func:`cell_scan` reads them."""
    codes: jax.Array    # (n_chunks, dp, chunk) int8, dims padded to dp
    rows: jax.Array     # (n_chunks, 2, chunk) f32: scale, squared norm
    first: jax.Array    # (C,) int32 each cell's first chunk
    live: jax.Array     # (C,) int32 chunks holding the cell's rows


def chunk_rows(d: int, pad: int) -> int:
    """Rows per chunk: the largest power of two whose codes fit
    :data:`STEP_BYTES`, at least one lane tile, and at most the padded
    cell rounded up to a lane tile (2,048 at d=128, 256 at d=960)."""
    fit = max(STEP_BYTES // round_up(d, SLAB), LANES)
    return min(1 << (fit.bit_length() - 1), round_up(pad, LANES))


def live_chunks(offsets: np.ndarray, chunk: int) -> np.ndarray:
    """Chunks that hold each cell's rows (0 for an empty cell)."""
    return -(-np.diff(offsets) // chunk)


def cell_blocks(base_q: jax.Array, scales: jax.Array, offsets: np.ndarray,
                pad: int) -> CellBlocks:
    """Copy cell-major codes (``offsets``: CSR cell boundaries) into
    chunk-aligned blocks.  An empty cell keeps one zero chunk, so every
    cell's ``first`` names a chunk; rows past a cell's end are zero."""
    n, d = base_q.shape
    chunk = chunk_rows(d, pad)
    live = live_chunks(offsets, chunk)
    alloc = np.maximum(live, 1)
    first = np.concatenate([[0], np.cumsum(alloc)[:-1]])
    cell = np.repeat(np.arange(len(live)), alloc)
    t = np.arange(int(alloc.sum())) - first[cell]      # chunk of its cell
    src = offsets[cell] + t * chunk
    rem = np.diff(offsets)[cell] - t * chunk
    codes, rows = _chunked(base_q, scales, chunk=chunk, dp=round_up(d, SLAB))
    codes, rows = align_blocks(jnp.asarray(src, jnp.int32),
                               jnp.asarray(rem, jnp.int32), codes, rows,
                               interpret=interpret_default())
    return CellBlocks(codes, rows, jnp.asarray(first, jnp.int32),
                      jnp.asarray(live, jnp.int32))


@functools.partial(jax.jit, static_argnames=("chunk", "dp"))
def _chunked(base_q, scales, *, chunk: int, dp: int):
    """The codes cut every ``chunk`` rows, rows along the lanes, plus a
    zero chunk past the last row: a shape that follows ``base_q`` alone,
    not the layout's cells, so its compile is shared by every build.
    (The transpose runs on dims padded to a lane multiple: at d=960 that
    compiles in 1% of the time.)"""
    n, d = base_q.shape
    m = n // chunk + 2
    codes = jnp.pad(base_q, ((0, m * chunk - n), (0, round_up(d, LANES) - d)))
    codes = codes.reshape(m, chunk, -1).transpose(0, 2, 1)[:, :dp]
    norm = scales * scales * jnp.sum(jnp.square(base_q.astype(jnp.float32)),
                                     axis=1)

    def lanes(v):
        return jnp.pad(v, (0, m * chunk - n)).reshape(m, 1, chunk)

    return codes, jnp.concatenate([lanes(scales), lanes(norm)], axis=1)


@functools.partial(jax.jit, static_argnames=("pad", "metric"))
def cell_scan(q: jax.Array, blocks: CellBlocks, probe: jax.Array, *,
              pad: int, metric: str) -> jax.Array:
    """(B, d) f32 queries, (B, nprobe) probed cells -> (B, nprobe·pad)
    f32 int8-scan distances, smaller = closer."""
    B = q.shape[0]
    nprobe = probe.shape[1]
    _, dp, chunk = blocks.codes.shape
    tpp = -(-pad // chunk)
    qb = jnp.broadcast_to(pad_dim(q, 1, dp)[:, :, None], (B, dp, LANES))
    qn = jnp.broadcast_to(jnp.sum(q * q, axis=-1)[:, None, None],
                          (B, 1, LANES))
    out = cell_scan_blocks(blocks.first[probe].reshape(-1),
                           blocks.live[probe].reshape(-1), qb, qn,
                           blocks.codes, blocks.rows, tpp=tpp, metric=metric,
                           interpret=interpret_default())
    out = out.reshape(B, -1, chunk)[:, :nprobe * tpp]
    return out.reshape(B, nprobe, tpp * chunk)[:, :, :pad].reshape(B, -1)
