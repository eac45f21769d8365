"""Pure-jnp oracles for the cell-scan kernels: the blocks built by a row
gather, and the scan read by index arithmetic and scored with one
float32 einsum."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.cellscan.cellscan import BIG, SLAB
from repro.kernels.cellscan.ops import CellBlocks, chunk_rows, live_chunks
from repro.kernels.common import pad_dim, round_up


def cell_blocks_ref(base_q, scales, offsets, pad: int) -> CellBlocks:
    """What :func:`ops.cell_blocks` builds, gathered row by row."""
    n, d = base_q.shape
    chunk = chunk_rows(d, pad)
    live = live_chunks(offsets, chunk)
    alloc = np.maximum(live, 1)
    first = np.concatenate([[0], np.cumsum(alloc)[:-1]])
    cell = np.repeat(np.arange(len(live)), np.diff(offsets))
    src = np.full(int(alloc.sum()) * chunk, -1, np.int32)
    src[first[cell] * chunk + np.arange(n) - offsets[cell]] = np.arange(n)
    src = jnp.asarray(src.reshape(-1, chunk))
    ok = src >= 0
    pos = jnp.where(ok, src, 0)
    codes = jnp.where(ok[..., None], base_q[pos], 0)   # (n_chunks, chunk, d)
    codes = pad_dim(codes, 2, round_up(d, SLAB)).transpose(0, 2, 1)
    norm = scales * scales * jnp.sum(jnp.square(base_q.astype(jnp.float32)),
                                     axis=1)
    rows = jnp.stack([jnp.where(ok, scales[pos], 0.0),
                      jnp.where(ok, norm[pos], 0.0)], axis=1)
    return CellBlocks(codes, rows, jnp.asarray(first, jnp.int32),
                      jnp.asarray(live, jnp.int32))


def cell_scan_ref(q, blocks, probe, *, pad: int, metric: str):
    """(B, d) queries, (B, nprobe) probes -> (B, nprobe·pad) f32."""
    n_chunks, dp, chunk = blocks.codes.shape
    rows = blocks.codes.transpose(0, 2, 1).reshape(-1, dp)
    scale = blocks.rows[:, 0].reshape(-1)
    norm = blocks.rows[:, 1].reshape(-1)
    r = jnp.arange(pad)
    ok = r < (blocks.live[probe] * chunk)[..., None]       # (B, nprobe, pad)
    at = jnp.where(ok, blocks.first[probe][..., None] * chunk + r, 0)
    qp = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, dp - q.shape[1])))
    dot = jnp.einsum("bd,bjrd->bjr", qp, rows[at].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    sdot = scale[at] * dot
    if metric == "ip":
        d = -sdot
    else:
        d = jnp.sum(qp * qp, axis=-1)[:, None, None] + norm[at] - 2.0 * sdot
    return jnp.where(ok, d, BIG).reshape(q.shape[0], -1)
