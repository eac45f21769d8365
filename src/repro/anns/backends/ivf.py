"""``"ivf"`` backend: k-means cells + per-cell dense scans.

Coarse stage routes through the Pallas ``pairwise_distance`` + ``topk``
kernels (query x centroids), the probed cells are scanned by the Pallas
cell-scan kernel, which reads each probed cell's int8 codes as
contiguous blocks of a chunk-aligned copy (fp32 rows gathered from the
cell-major base when ``SearchParams.quantized`` is explicitly
``False``), and the final answer comes from the standalone fp32 rerank
stage shared with ``backends/quantized.py``.

Jit hygiene: ``SearchParams.ef`` maps onto ``nprobe`` through a static
ladder (:data:`NPROBE_LADDER`), mirroring the graph family's EF_LADDER
bucketing — an (ef, target_recall) sweep reuses a handful of compiled
traces.  ``ef=64`` (the SearchParams default) probes exactly the
variant's ``nprobe``; other efs scale it proportionally before snapping.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.anns import search as search_lib
from repro.anns.api import (SearchParams, SearchResult, effective_ef,
                            snap_to_ladder)
from repro.anns.backends.quantized import fp32_rerank
from repro.anns.filters import AttributeColumns
from repro.anns.ivf.layout import IvfIndex, build_ivf
from repro.anns.registry import register
from repro.kernels.cellscan.ops import cell_blocks, cell_scan
from repro.kernels.distance.ops import pairwise_distance
from repro.kernels.topk.ops import topk_smallest

BIG = search_lib.BIG

# Geometric ~1.5x nprobe ladder (same trick as api.EF_LADDER): derived
# nprobes snap up to a rung so sweeps hit O(ladder) jit traces.
NPROBE_LADDER = (1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64, 96, 128, 192, 256)


def round_nprobe(nprobe: int) -> int:
    """Smallest ladder rung >= nprobe (multiples of 128 past the ladder)."""
    return snap_to_ladder(nprobe, NPROBE_LADDER, 128)


def nprobe_for(variant, params: SearchParams, nlist: int) -> int:
    """Map the universal ``ef`` effort knob onto nprobe: the variant's
    ``nprobe`` at the default ef=64, scaled proportionally elsewhere,
    snapped to the static ladder, clamped to the cell count.  Shared by
    the ``ivf`` and ``sharded`` backends so a given (variant, params)
    probes the *same* cells in both — the basis of their equivalence."""
    ef = effective_ef(params.ef, params.target_recall,
                      variant.adaptive_ef_coef)
    raw = max(1, round(variant.nprobe * ef / 64))
    return min(round_nprobe(raw), nlist)


def ef_ladder_for_nprobe(variant, nlist: int) -> tuple:
    """The ef values whose :func:`nprobe_for` mapping lands on each
    reachable ``NPROBE_LADDER`` rung (plus the all-cells probe when
    ``nlist`` is off-ladder) — the IVF family's answer to
    :func:`repro.anns.api.search_ef_ladder`.  Sweeping exactly these efs
    walks the whole nprobe ladder once, with no two efs landing on the
    same rung's trace."""
    base = max(1, int(variant.nprobe))
    rungs = [r for r in NPROBE_LADDER if r < nlist] + [int(nlist)]
    return tuple(sorted({max(1, round(64 * r / base)) for r in rungs}))


def shortlist_width(params: SearchParams, k: int, n: int, nprobe: int,
                    cell_pad: int) -> int:
    """Rerank shortlist width m: ``rerank_factor * k`` capped by the base
    size and by the probed block's width.  Shared with the sharded
    backend (identical m keeps merged results identical)."""
    m = max(k, min(max(params.rerank_factor, 1) * k, n))
    return min(m, nprobe * cell_pad)


@functools.partial(jax.jit, static_argnames=(
    "nprobe", "k", "m", "metric", "quantized"))
def _ivf_search(centroids, cells, ids, base, blocks, queries, fmask=None, *,
                nprobe: int, k: int, m: int, metric: str, quantized: bool):
    """(B, d) queries -> (ids (B, k) original ids, dists (B, k) fp32).

    Stage 1 (coarse, Pallas kernels): distance matrix to centroids +
    top-nprobe cells.  Stage 2 (scan): gather the probed cells' padded
    position rows — one (B, nprobe*pad) rectangular candidate block —
    and score it: the cell-scan kernel reads each probed cell's int8
    codes from ``blocks`` (a :class:`CellBlocks`), or the fp32 rows are
    gathered from ``base``.  Stage 3: shortlist the best m by scan
    distance, fp32-rerank, remap positions to original ids.
    Each stage runs under a named scope (``ivf.coarse``, ``ivf.scan``,
    ``ivf.cut``, ``ivf.rerank``), which every op it lowers to carries in
    its ``op_name`` metadata, so a device trace can time the stages apart.

    Pad slots (position -1) score BIG in the scan AND stay masked through
    the rerank (the validity mask travels with the shortlist), so they can
    never displace a real neighbor; duplicate ids appear only if the
    probed cells genuinely hold fewer than k vectors, which the caller's
    nprobe floor rules out.

    ``fmask`` ((n,) bool in cell-major position space, or None) is the
    filter predicate's bitmask: it ANDs into the same validity mask the
    pad slots ride, cutting non-matching vectors out of both the scan cut
    and the rerank.  ``None`` is an empty pytree, so the unfiltered trace
    is byte-identical to the pre-filter program.  Slots left without a
    matching vector surface as id -1 (dist BIG).
    """
    B = queries.shape[0]
    with jax.named_scope("ivf.coarse"):
        q32 = queries.astype(jnp.float32)
        dc = pairwise_distance(q32, centroids, metric=metric)  # (B, C)
        _, probe = topk_smallest(dc, nprobe)                   # (B, nprobe)

    with jax.named_scope("ivf.scan"):
        cand = cells[probe].reshape(B, -1)                     # (B, nprobe*pad)
        valid = cand >= 0
        pos = jnp.where(valid, cand, 0)
        if fmask is not None:
            valid = valid & fmask[pos]
        if quantized:
            d = cell_scan(q32, blocks, probe, pad=cells.shape[1],
                          metric=metric)
        else:
            d = search_lib._qdist(q32, base[pos], metric)
        d = jnp.where(valid, d, BIG)
        scanned = jnp.sum(valid)

    with jax.named_scope("ivf.cut"):
        _, keep = jax.lax.top_k(-d, m)
        short = jnp.take_along_axis(pos, keep, axis=1)         # (B, m)
        short_valid = jnp.take_along_axis(valid, keep, axis=1)

    with jax.named_scope("ivf.rerank"):
        out_pos, out_d = fp32_rerank(base, q32, short, k=k, metric=metric,
                                     valid=short_valid)
        out_ids = jnp.where(out_d < BIG, ids[out_pos], -1)
    return out_ids, out_d, scanned


@register("ivf")
class IvfBackend(AttributeColumns):
    name = "ivf"

    #: state_format 2: optional per-vector attribute columns (attr/<col>,
    #: stored in cell-major position order to match the saved layout)
    STATE_FORMAT = 2

    def __init__(self, variant=None, *, metric: str = "l2", seed: int = 0):
        if variant is None:
            from repro.anns.engine import VariantConfig
            variant = VariantConfig(backend="ivf")
        self.variant = variant
        self.metric = metric
        self.seed = seed
        self.index: IvfIndex | None = None

    def _with_blocks(self, index: IvfIndex) -> IvfIndex:
        """``index`` holding its int8 codes as the cell-scan kernel reads
        them, copied once per layout (a subclass whose search scans
        ``base_q`` itself returns ``index`` as it is)."""
        return dataclasses.replace(index, blocks=cell_blocks(
            index.base_q, index.scales, index.offsets, index.cell_pad))

    # -- AnnsIndex protocol ------------------------------------------------
    def build(self, base: np.ndarray) -> IvfIndex:
        v = self.variant
        self.index = self._with_blocks(build_ivf(
            base, nlist=v.nlist, kmeans_iters=v.kmeans_iters,
            metric=self.metric, seed=self.seed,
            max_cell=getattr(v, "max_cell", 0) or None))
        self.attributes = None       # columns describe one base layout
        self._clear_filter_caches()
        return self.index

    def _attr_order(self):
        # attribute columns live in cell-major position space — the same
        # permutation `ids` encodes — so fmask[pos] indexes directly
        return np.asarray(self.index.ids)

    def _nprobe_for(self, params: SearchParams) -> int:
        return nprobe_for(self.variant, params, self.index.nlist)

    def search_ef_ladder(self) -> tuple:
        """Effort ladder for the autotuner: efs covering every nprobe
        rung (built ``nlist`` when available — ``max_cell`` splits can
        grow it past the variant's)."""
        nlist = self.index.nlist if self.index is not None \
            else self.variant.nlist
        return ef_ladder_for_nprobe(self.variant, nlist)

    def _invocation(self, queries, params: SearchParams):
        """Resolve one search call to (positional arrays, static knobs) —
        shared by :meth:`search` and :meth:`lower_search`, so a compile
        check inspects exactly the program that serves."""
        idx = self.index
        p = params.resolved(self.variant)
        k = min(p.k, idx.n)
        nprobe = self._nprobe_for(p)
        # the probed cells must hold at least k real vectors, or the
        # answer can't contain k distinct ids (nprobe=1 over small cells
        # undershoots); min_cells_for gives the worst-case floor and is
        # <= nlist always, since the cells jointly hold all n >= k.
        min_probe = idx.min_cells_for(k)
        if nprobe < min_probe:
            nprobe = min(round_nprobe(min_probe), idx.nlist)
        # shortlist for the fp32 rerank; never wider than the probed block
        m = shortlist_width(p, k, idx.n, nprobe, idx.cell_pad)
        # int8 scan is this backend's default; explicit quantized=False
        # falls back to fp32 cell scans (params win over backend defaults)
        quantized = True if params.quantized is None else bool(params.quantized)
        fmask = (self._row_mask_dev(p.filter)
                 if p.filter is not None else None)
        args = (idx.centroids, idx.cells, idx.ids, idx.base, idx.blocks,
                jnp.asarray(queries, jnp.float32), fmask)
        statics = dict(nprobe=nprobe, k=k, m=m, metric=self.metric,
                       quantized=quantized)
        return args, statics

    def search(self, queries, params: SearchParams) -> SearchResult:
        assert self.index is not None, "build() first"
        args, statics = self._invocation(queries, params)
        out_ids, out_d, scanned = _ivf_search(*args, **statics)
        return SearchResult(ids=out_ids, dists=out_d,
                            steps=statics["nprobe"],
                            expansions=scanned, backend=self.name)

    def lower_search(self, queries, params: SearchParams):
        """AOT-lower the jitted search for inspection of the compiled
        program (e.g. that its kernels lowered to ``tpu_custom_call``)."""
        assert self.index is not None, "build() first"
        args, statics = self._invocation(queries, params)
        return _ivf_search.lower(*args, **statics)

    def memory_bytes(self) -> int:
        idx = self.index
        if idx is None:
            return 0
        arrays = (idx.centroids, idx.cells, idx.ids, idx.base, idx.base_q,
                  idx.scales, *(idx.blocks or ()))
        return (sum(a.size * a.dtype.itemsize for a in arrays)
                + idx.offsets.nbytes)

    def to_state_dict(self) -> dict:
        idx = self.index
        assert idx is not None, "build() first"
        return {
            "backend": self.name,
            "metric": idx.metric,
            "state_format": self.STATE_FORMAT,
            "centroids": np.asarray(idx.centroids),
            "cells": np.asarray(idx.cells),
            "ids": np.asarray(idx.ids),
            "base": np.asarray(idx.base),
            "base_q": np.asarray(idx.base_q),
            "scales": np.asarray(idx.scales),
            "offsets": np.asarray(idx.offsets),
            **self._attr_state_leaves(),
        }

    def from_state_dict(self, state: dict) -> None:
        self.metric = state["metric"]
        self.index = self._with_blocks(IvfIndex(
            centroids=jnp.asarray(state["centroids"]),
            cells=jnp.asarray(state["cells"]),
            ids=jnp.asarray(state["ids"]),
            base=jnp.asarray(state["base"]),
            base_q=jnp.asarray(state["base_q"]),
            scales=jnp.asarray(state["scales"]),
            offsets=np.asarray(state["offsets"]),
            metric=state["metric"]))
        self._restore_attr_leaves(state)
