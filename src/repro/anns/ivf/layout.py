"""Cell-major IVF layout: contiguous per-cell vector blocks.

The built state is CSR-style — vectors are permuted so each cell's members
occupy one contiguous block (``offsets[c]:offsets[c+1]``), with ``ids``
mapping a cell-major *position* back to the caller's original vector id.
Contiguity is the point: a per-cell scan is a dense block read, and the
padded ``cells`` view (one row of cell-major positions per cell, -1
padded to a common width) turns an ``nprobe``-cell probe into a single
rectangular gather + one dense distance call per query batch.

Each block also carries int8 codes (symmetric per-vector quantization via
the qdist kernel package's ``quantize_int8``) so the probe scan can run
in int8 with the standalone fp32 rerank on top — the same
prefilter/rerank split as ``backends/quantized.py``.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.anns.ivf.kmeans import assign, kmeans_fit, split_oversized
from repro.kernels.cellscan.ops import CellBlocks, chunk_rows, live_chunks
from repro.kernels.common import round_up
from repro.kernels.qdist.ops import quantize_int8


def probe_floor(index, k: int) -> int:
    """Worst-case nprobe floor: the smallest j such that *any* j cells
    jointly hold >= k vectors (the j smallest cells are the worst case).

    The ONE implementation shared by :class:`IvfIndex` and
    ``ShardedIvfIndex`` — both keep the same CSR ``offsets``, and the
    sharded==ivf exactness guarantee depends on both computing the
    identical floor.  The sorted cumulative cell sizes are immutable
    after build, so they are cached on the index off the serving hot
    path."""
    cum = getattr(index, "_sizes_cum", None)
    if cum is None:
        cum = np.cumsum(np.sort(np.diff(index.offsets)))
        index._sizes_cum = cum
    return int(np.searchsorted(cum, min(k, index.n)) + 1)


@dataclass
class IvfIndex:
    centroids: jax.Array       # (C, d) f32 coarse quantizer
    cells: jax.Array           # (C, pad) int32 cell-major positions, -1 pad
    ids: jax.Array             # (N,) int32 cell-major position -> original id
    base: jax.Array            # (N, d) f32, cell-major order
    base_q: jax.Array          # (N, d) int8 codes, cell-major order
    scales: jax.Array          # (N,) f32 dequant scales
    offsets: np.ndarray        # (C+1,) int64 CSR cell boundaries (host)
    metric: str                # "l2" | "ip"
    #: the int8 codes copied chunk-aligned for the cell-scan kernel
    #: (``kernels.cellscan.ops.cell_blocks``); held by the ``ivf``
    #: backend's layouts, None where the search gathers ``base_q``
    blocks: CellBlocks | None = None

    @property
    def n(self) -> int:
        return int(self.base.shape[0])

    @property
    def nlist(self) -> int:
        return int(self.centroids.shape[0])

    @property
    def cell_pad(self) -> int:
        return int(self.cells.shape[1])

    def min_cells_for(self, k: int) -> int:
        """Worst-case probe floor — see :func:`probe_floor`."""
        return probe_floor(self, k)


def _padded_cells(offsets: np.ndarray, nlist: int) -> np.ndarray:
    """(C, pad) rows of cell-major positions, -1 beyond each cell's size.
    ``pad`` is the max cell size rounded up to a sublane multiple so the
    probe gather stays tile-friendly."""
    counts = np.diff(offsets)
    pad = round_up(max(int(counts.max(initial=1)), 1), 8)
    cells = np.full((nlist, pad), -1, np.int32)
    for c in range(nlist):
        lo, hi = int(offsets[c]), int(offsets[c + 1])
        cells[c, : hi - lo] = np.arange(lo, hi, dtype=np.int32)
    return cells


def layout_from_assignments(base: np.ndarray, a: np.ndarray,
                            centroids: np.ndarray, *,
                            metric: str) -> IvfIndex:
    """Lay (n, d) vectors out cell-major given their cell assignments.

    The deterministic second half of :func:`build_ivf` (stable argsort of
    the assignments, CSR offsets, padded cell table, int8 codes), shared
    with the streaming subsystem's ``compact()`` — which assigns against
    the *existing* centroids instead of retraining, then rebuilds the
    layout through exactly this code path, so a compacted index and a
    fresh build differ only in their coarse quantizer.

    The returned index's ``ids`` map cell-major positions back to *row
    indices of ``base``* — callers carrying original ids compose them on
    top.
    """
    base = np.ascontiguousarray(np.asarray(base, np.float32))
    nlist = len(centroids)
    order = np.argsort(a, kind="stable").astype(np.int32)   # position -> row
    counts = np.bincount(a, minlength=nlist) if len(a) \
        else np.zeros(nlist, np.int64)
    offsets = np.zeros(nlist + 1, np.int64)
    np.cumsum(counts, out=offsets[1:])
    base_cm = base[order]
    base_q, scales = quantize_int8(jnp.asarray(base_cm))
    return IvfIndex(
        centroids=jnp.asarray(np.asarray(centroids, np.float32)),
        cells=jnp.asarray(_padded_cells(offsets, nlist)),
        ids=jnp.asarray(order),
        base=jnp.asarray(base_cm),
        base_q=base_q,
        scales=scales,
        offsets=offsets,
        metric=metric)


def build_ivf(base: np.ndarray, *, nlist: int, kmeans_iters: int = 8,
              metric: str = "l2", seed: int = 0,
              use_kernel: bool = True,
              max_cell: int | None = None) -> IvfIndex:
    """Train the coarse quantizer, then lay the base out cell-major.

    ``max_cell`` (optional) enforces the balanced-assignment constraint:
    cells larger than the cap are recursively split
    (:func:`repro.anns.ivf.kmeans.split_oversized`), growing ``nlist`` but
    bounding ``cell_pad`` — the knob that keeps one skewed cell from
    inflating every shard's probe gather at mesh scale.  Balanced cells
    trade the "nearest centroid == own cell" property for the bound.
    """
    base = np.ascontiguousarray(np.asarray(base, np.float32))
    n = len(base)
    nlist = max(1, min(nlist, n))
    centroids = kmeans_fit(base, nlist, iters=kmeans_iters, metric=metric,
                           seed=seed, use_kernel=use_kernel)
    a, _ = assign(base, centroids, metric=metric, use_kernel=use_kernel)
    if max_cell:
        centroids, a = split_oversized(base, centroids, a, cap=max_cell)
    return layout_from_assignments(base, a, centroids, metric=metric)


def ivf_stats(index: IvfIndex) -> dict:
    counts = np.diff(index.offsets)
    # degenerate layouts are legal states for a *mutable* index (a
    # fully-compacted-empty index keeps a single dummy cell; a fresh one
    # may hold one vector in one cell) — every ratio below must define
    # itself instead of dividing by zero
    mean = float(counts.mean()) if counts.size else 0.0
    biggest = int(counts.max(initial=0))
    return {
        "n": index.n,
        "nlist": index.nlist,
        "cell_pad": index.cell_pad,
        "mean_cell": mean,
        "max_cell": biggest,
        "empty_cells": int((counts == 0).sum()),
        # padding overhead of the dense probe view vs the CSR blocks
        "pad_overhead": float(index.nlist * index.cell_pad / max(index.n, 1)),
        # skew: how far the worst cell sits above the mean — the quantity
        # the balanced-assignment cap (build_ivf max_cell) bounds; an
        # empty index has no skew, a single non-empty cell has skew 1
        "cell_skew": float(biggest / mean) if mean > 0 else 0.0,
        # rows the cell-scan kernel reads per probed cell (its live
        # chunks) over the pad it used to gather: what skipping the dead
        # chunks saves; 1 where a chunk spans the whole pad
        "scan_read_share": _scan_read_share(index, counts),
    }


def _scan_read_share(index: IvfIndex, counts: np.ndarray) -> float:
    if not counts.size:
        return 0.0
    chunk = chunk_rows(int(index.base.shape[1]), index.cell_pad)
    live = live_chunks(index.offsets, chunk)
    return float(np.mean(live * chunk) / index.cell_pad)
