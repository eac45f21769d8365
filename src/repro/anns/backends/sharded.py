"""``"sharded"`` backend: cell-routed IVF over a device mesh.

The scale-out path of the ROADMAP: the cell-major IVF layout is sliced
into whole-cell shards (:mod:`repro.anns.ivf.sharding`), and one query
batch runs as

1. **coarse = routing** — the replicated centroids produce the top-nprobe
   cells *and* with them the owning shards (``cell_shard`` is a static
   map): a probed cell contributes candidates only on the shard that owns
   it, every other shard sees a masked (pad) row.
2. **per-shard scan + local fp32 rerank** — each shard gathers its probed
   cells' padded rows from its local table, scores them densely (int8
   dequant by default, fp32 via its own ``base_f`` slice when
   ``quantized=False``), keeps its top-``m`` shortlist, and immediately
   re-scores that shortlist in fp32 against its *own* ``base_f`` slice
   (:func:`~repro.anns.backends.quantized.fp32_rescore`).  There is no
   replicated rerank store: the rerank distance of a vector is computed
   on the one shard that holds it.
3. **score merge** — per-shard shortlists (ids + scan scores + reranked
   scores + validity, (S, B, m) total) meet, are cut to the global
   top-``m`` by scan distance, and the final top-``k`` is read off the
   already-reranked scores.  Because a rerank distance is the same
   wherever it is computed, this is provably identical to reranking
   after the concat — with O(S*B*m) merge traffic instead of an (N, d)
   fp32 store on every device.

On one device stage 2 is a ``vmap`` over the leading shard axis; placed
on a ``("shard",)`` mesh (:meth:`ShardedBackend.place_on_mesh`) it runs
as an explicit ``shard_map`` whose only collectives are the shortlist
``all_gather`` and a scalar ``psum`` — the merge traffic is bounded by
construction, not by partitioner luck (pinned by the
``repro.dist.hlo.collective_bytes`` test).

Because the shard slices are byte-identical views of the unsharded
arrays and every stage-width (nprobe, m) comes from the helpers shared
with ``backends/ivf.py``, the merged results at any ``n_shards`` match
the unsharded ``ivf`` backend — ``n_shards=1`` is bit-identical, and at
max nprobe any shard count returns the same ids (the property tests pin
both).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.anns import search as search_lib
from repro.anns.api import SearchParams, SearchResult
from repro.anns.backends.ivf import (nprobe_for, round_nprobe,
                                     shortlist_width)
from repro.anns.backends.quantized import fp32_rescore
from repro.anns.filters import AttributeColumns
from repro.anns.ivf.layout import build_ivf
from repro.anns.ivf.sharding import (ShardedIvfIndex, place_on_mesh,
                                     shard_ivf, shard_memory_bytes,
                                     sharded_stats)
from repro.anns.registry import register
from repro.kernels.distance.ops import pairwise_distance
from repro.kernels.topk.ops import topk_smallest

BIG = search_lib.BIG


def _route(centroids, cell_shard, cell_row, queries, *, nprobe: int,
           metric: str):
    """Coarse stage doubling as routing: top-nprobe cells plus their
    owning shard / local row, all replicated (O(B*nprobe) scalars)."""
    q32 = queries.astype(jnp.float32)
    dc = pairwise_distance(q32, centroids, metric=metric)       # (B, C)
    _, probe = topk_smallest(dc, nprobe)                        # (B, nprobe)
    return q32, cell_shard[probe], cell_row[probe]


def _scan_rerank_block(shard_id, cells_j, v0_j, bq_j, sc_j, bf_j,
                       q32, owner, row, fmask_j=None, *, m_shard: int,
                       metric: str, quantized: bool):
    """One shard's scan + shard-local fp32 rerank.

    Runs unrolled per shard (single device) or inside ``shard_map``
    (mesh) — either way on the same (B, ...) shapes as the unsharded
    ``ivf`` program, and everything here touches only the shard's own
    slices.  A shard owning
    zero cells (``n_shards`` beyond the non-empty cell count) sees an
    all-masked candidate block and returns an all-invalid shortlist.
    Returns (global positions, scan dists, reranked dists, validity,
    scanned count), each (B, m_shard) except the scalar count.

    ``fmask_j`` ((Npad,) bool over this shard's local positions, or
    None) is the filter predicate's bitmask — AND-ed into the same
    validity that guards pad rows, so filtered-out vectors survive
    neither the scan cut nor the rerank, and the merge sees them as BIG.
    """
    B = q32.shape[0]
    mine = owner == shard_id                                # (B, nprobe)
    cand = cells_j[jnp.where(mine, row, 0)]                 # (B, np, pad)
    cand = jnp.where(mine[..., None], cand, -1).reshape(B, -1)
    valid = cand >= 0
    pos = jnp.where(valid, cand, 0)                         # local pos
    if fmask_j is not None:
        valid = valid & fmask_j[pos]
    if quantized:
        vecs = bq_j[pos].astype(jnp.float32) * sc_j[pos][..., None]
    else:
        vecs = bf_j[pos]
    d = search_lib._qdist(q32, vecs, metric, quantized=quantized)
    d = jnp.where(valid, d, BIG)
    nd, keep = jax.lax.top_k(-d, m_shard)
    lpos = jnp.take_along_axis(pos, keep, axis=1)
    kept_valid = jnp.take_along_axis(valid, keep, axis=1)
    # shard-local fp32 rerank: exact re-scoring against this shard's own
    # fp32 slice — the merge then needs scores only, never vectors
    rd = fp32_rescore(bf_j, q32, lpos, metric=metric, valid=kept_valid)
    return lpos + v0_j, -nd, rd, kept_valid, jnp.sum(valid)


def _merge_topk(gpos, sd, rd, valid, *, k: int, m_total: int):
    """Score merge over stacked (S, B, m) shortlists: cut to the global
    top-``m_total`` by scan distance (the same set the rerank-after-concat
    pipeline scored), then read the final top-``k`` off the shard-local
    reranked distances."""
    B = gpos.shape[1]
    gpos = gpos.transpose(1, 0, 2).reshape(B, -1)               # (B, S*m)
    sd = sd.transpose(1, 0, 2).reshape(B, -1)
    rd = rd.transpose(1, 0, 2).reshape(B, -1)
    valid = valid.transpose(1, 0, 2).reshape(B, -1)
    _, keep = jax.lax.top_k(-jnp.where(valid, sd, BIG), m_total)
    short_rd = jnp.take_along_axis(rd, keep, axis=1)
    short_pos = jnp.take_along_axis(gpos, keep, axis=1)
    nd, order = jax.lax.top_k(-short_rd, k)
    return jnp.take_along_axis(short_pos, order, axis=1), -nd


@functools.partial(jax.jit, static_argnames=(
    "nprobe", "k", "m", "metric", "quantized"))
def _sharded_search(centroids, cell_shard, cell_row, cells, vec_start,
                    base_q, scales, base_f, ids, queries, fmask=None, *,
                    nprobe: int, k: int, m: int, metric: str,
                    quantized: bool):
    """(B, d) queries -> (ids (B, k) original ids, dists (B, k) fp32).

    Single-device form: the per-shard scan+rerank body is *unrolled*
    over the (static, small) shard count rather than vmapped — every
    per-shard op then has exactly the shapes of the unsharded ``ivf``
    program, so scan and rerank floats are bit-identical to it (a
    vmapped body adds a leading shard axis and lets XLA reassociate the
    fp32 reductions).  The mesh-placed form is
    :func:`_make_placed_search` — same body on the same squeezed shapes,
    explicit collectives.
    """
    n_shards, _, pad = cells.shape
    q32, owner, row = _route(centroids, cell_shard, cell_row, queries,
                             nprobe=nprobe, metric=metric)
    m_shard = min(m, nprobe * pad)      # static: a shard never needs more

    outs = [_scan_rerank_block(
        jnp.int32(j), cells[j], vec_start[j], base_q[j], scales[j],
        base_f[j], q32, owner, row,
        None if fmask is None else fmask[j],
        m_shard=m_shard, metric=metric, quantized=quantized)
        for j in range(n_shards)]
    gpos, sd, rd, valid = (jnp.stack(t) for t in list(zip(*outs))[:4])
    scanned = sum(o[4] for o in outs)

    m_total = min(m, n_shards * m_shard)
    out_pos, out_d = _merge_topk(gpos, sd, rd, valid, k=k, m_total=m_total)
    return jnp.where(out_d < BIG, ids[out_pos], -1), out_d, scanned


def _make_placed_search(mesh):
    """Mesh form of :func:`_sharded_search`: the per-shard body runs in a
    ``shard_map`` over the ``"shard"`` axis, so the cross-device traffic
    is *exactly* the shortlist ``all_gather`` ((S, B, m) ids+scores) plus
    a scalar ``psum`` — never an (N, d) broadcast, whatever the
    partitioner would have chosen for the vmapped form.

    The coarse route runs inside the ``shard_map`` too, on every device
    over the replicated centroids and queries: its Pallas kernels are
    TPU custom calls, which the partitioner cannot split."""
    from jax.sharding import PartitionSpec as P

    @functools.partial(jax.jit, static_argnames=(
        "nprobe", "k", "m", "metric", "quantized"))
    def placed_search(centroids, cell_shard, cell_row, cells, vec_start,
                      base_q, scales, base_f, ids, queries, fmask=None, *,
                      nprobe: int, k: int, m: int, metric: str,
                      quantized: bool):
        n_shards, _, pad = cells.shape
        m_shard = min(m, nprobe * pad)

        def block(cents, c_shard, c_row, qs, cells_b, v0_b, bq_b, sc_b,
                  bf_b, *rest):
            j = jax.lax.axis_index("shard")
            fm_b = rest[0][0] if rest else None
            q32, owner, row = _route(cents, c_shard, c_row, qs,
                                     nprobe=nprobe, metric=metric)
            gpos, sd, rd, valid, scanned = _scan_rerank_block(
                j, cells_b[0], v0_b[0], bq_b[0], sc_b[0], bf_b[0],
                q32, owner, row, fm_b, m_shard=m_shard, metric=metric,
                quantized=quantized)
            # the merge traffic, in full: (S, B, m_shard) ids+scores
            out = [jax.lax.all_gather(t, "shard")
                   for t in (gpos, sd, rd, valid)]
            return (*out, jax.lax.psum(scanned, "shard"))

        in_specs = (P(), P(), P(), P(),
                    P("shard", None, None), P("shard"),
                    P("shard", None, None), P("shard", None),
                    P("shard", None, None))
        operands = (centroids, cell_shard, cell_row, queries,
                    cells, vec_start, base_q, scales, base_f)
        if fmask is not None:
            # the filter bitmask is shard-local state like the slices:
            # each device ANDs only its own (Npad,) row, no mask traffic
            in_specs += (P("shard", None),)
            operands += (fmask,)
        gpos, sd, rd, valid, scanned = jax.shard_map(
            block, mesh=mesh,
            in_specs=in_specs,
            out_specs=(P(), P(), P(), P(), P()),
            check_vma=False)(*operands)
        m_total = min(m, n_shards * m_shard)
        out_pos, out_d = _merge_topk(gpos, sd, rd, valid,
                                     k=k, m_total=m_total)
        return jnp.where(out_d < BIG, ids[out_pos], -1), out_d, scanned

    return placed_search


@register("sharded")
class ShardedBackend(AttributeColumns):
    """Cell-routed multi-shard IVF (see module docstring)."""

    name = "sharded"
    # state-dict format: v2 ships the rerank store as per-shard
    # ``shardN/base_f`` leaves; v1 (replicated ``base``) still loads.
    # v3 adds optional per-vector attribute columns (``attr/<col>``,
    # global cell-major position order).
    STATE_FORMAT = 3

    def __init__(self, variant=None, *, metric: str = "l2", seed: int = 0):
        if variant is None:
            from repro.anns.engine import VariantConfig
            variant = VariantConfig(backend="sharded")
        self.variant = variant
        self.metric = metric
        self.seed = seed
        self.index: ShardedIvfIndex | None = None
        self._placed_search = None
        self._mesh = None

    # -- AnnsIndex protocol ------------------------------------------------
    def build(self, base: np.ndarray) -> ShardedIvfIndex:
        """Build the unsharded cell-major index (same seed/knobs as the
        ``ivf`` backend => identical cells), then slice it by cells."""
        v = self.variant
        inner = build_ivf(base, nlist=v.nlist, kmeans_iters=v.kmeans_iters,
                          metric=self.metric, seed=self.seed,
                          max_cell=getattr(v, "max_cell", 0) or None)
        self.index = shard_ivf(inner, max(1, int(v.n_shards)))
        self._placed_search = None
        self.attributes = None       # columns describe one base layout
        self._clear_filter_caches()
        return self.index

    def _attr_order(self):
        # global cell-major position space, same permutation `ids` encodes
        return np.asarray(self.index.ids)

    def _clear_filter_caches(self) -> None:
        super()._clear_filter_caches()
        self._shard_fmask = {}

    def _shard_mask_dev(self, predicate):
        """Per-shard (S, Npad) form of the predicate bitmask: the global
        position mask sliced by ``vec_bounds`` into each shard's padded
        local-position row (pad rows False), device_put along the mesh's
        shard axis when placed.  Cached per predicate."""
        hit = self._shard_fmask.get(predicate)
        if hit is not None:
            return hit
        gmask = self._row_mask(predicate)            # (n,) global positions
        idx = self.index
        vb = np.asarray(idx.vec_bounds)
        npad = int(idx.base_q.shape[1])
        m = np.zeros((idx.n_shards, npad), bool)
        for j in range(idx.n_shards):
            v0, v1 = int(vb[j]), int(vb[j + 1])
            m[j, : v1 - v0] = gmask[v0:v1]
        dev = jnp.asarray(m)
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            dev = jax.device_put(dev, NamedSharding(self._mesh,
                                                    P("shard", None)))
        self._shard_fmask[predicate] = dev
        return dev

    def place_on_mesh(self, mesh) -> None:
        """Pin each shard's slice to its device on a ``("shard",)`` mesh
        (see ``repro.launch.mesh.make_shard_mesh``) and switch to the
        shard_map search form with explicit merge collectives."""
        assert self.index is not None, "build() first"
        self.index = place_on_mesh(self.index, mesh)
        self._placed_search = _make_placed_search(mesh)
        self._mesh = mesh
        self._shard_fmask = {}       # re-derive masks with placement

    def stats(self) -> dict:
        assert self.index is not None, "build() first"
        return sharded_stats(self.index)

    def search_ef_ladder(self) -> tuple:
        """Same effort ladder as the unsharded ivf backend (shared
        nprobe mapping is the basis of their equivalence), from the
        built global cell count when available."""
        from repro.anns.backends.ivf import ef_ladder_for_nprobe
        nlist = self.index.nlist if self.index is not None \
            else self.variant.nlist
        return ef_ladder_for_nprobe(self.variant, nlist)

    def _invocation(self, queries, params: SearchParams):
        """Resolve one search call to (positional arrays, static knobs) —
        shared by :meth:`search` and :meth:`lower_search` so HLO-level
        tests inspect exactly the program that serves."""
        idx = self.index
        p = params.resolved(self.variant)
        k = min(p.k, idx.n)
        nprobe = nprobe_for(self.variant, p, idx.nlist)
        # same worst-case floor as the ivf backend: the probed cells must
        # jointly hold k real vectors or the answer cannot fill k slots
        min_probe = idx.min_cells_for(k)
        if nprobe < min_probe:
            nprobe = min(round_nprobe(min_probe), idx.nlist)
        m = shortlist_width(p, k, idx.n, nprobe, idx.cell_pad)
        quantized = True if params.quantized is None else bool(params.quantized)
        args = (idx.centroids, idx.cell_shard, idx.cell_row, idx.cells,
                idx.vec_start, idx.base_q, idx.scales, idx.base_f, idx.ids,
                jnp.asarray(queries, jnp.float32))
        if p.filter is not None:
            args += (self._shard_mask_dev(p.filter),)
        statics = dict(nprobe=nprobe, k=k, m=m, metric=self.metric,
                       quantized=quantized)
        return args, statics

    def _search_fn(self):
        return self._placed_search or _sharded_search

    def search(self, queries, params: SearchParams) -> SearchResult:
        assert self.index is not None, "build() first"
        args, statics = self._invocation(queries, params)
        out_ids, out_d, scanned = self._search_fn()(*args, **statics)
        return SearchResult(ids=out_ids, dists=out_d,
                            steps=statics["nprobe"],
                            expansions=scanned, backend=self.name)

    def lower_search(self, queries, params: SearchParams):
        """AOT-lower the jitted search (the placed form after
        :meth:`place_on_mesh`) for HLO inspection — e.g. bounding merge
        collective bytes with ``repro.dist.hlo.collective_bytes``."""
        assert self.index is not None, "build() first"
        args, statics = self._invocation(queries, params)
        return self._search_fn().lower(*args, **statics)

    def memory_bytes(self) -> int:
        """Total logical footprint: every stacked per-shard array in
        full, replicated routing state once."""
        if self.index is None:
            return 0
        return shard_memory_bytes(self.index)[0]

    def device_memory_bytes(self) -> int:
        """Worst single-device resident bytes under ``place_on_mesh``:
        one shard's slices plus the replicated routing state.  Unlike the
        pre-base_f layout there is no (N, d) fp32 term — this is the
        number that scales the dataset with the mesh."""
        if self.index is None:
            return 0
        return shard_memory_bytes(self.index)[1]

    # -- checkpointing: device-local slices as separate leaves -------------
    def to_state_dict(self) -> dict:
        """Per-shard arrays are saved *unstacked* — one leaf per shard —
        so the checkpoint's per-leaf bounds framing carries exactly the
        slice each serving device loads (same format as every other
        index checkpoint; see ``repro.ckpt.index_io``).  Format v2: the
        fp32 rerank store travels as ``shardN/base_f`` slices; there is
        no replicated ``base`` leaf."""
        idx = self.index
        assert idx is not None, "build() first"
        state = {
            "backend": self.name,
            "state_format": self.STATE_FORMAT,
            "metric": idx.metric,
            "n_shards": idx.n_shards,
            "centroids": np.asarray(idx.centroids),
            "cell_shard": np.asarray(idx.cell_shard),
            "cell_row": np.asarray(idx.cell_row),
            "vec_start": np.asarray(idx.vec_start),
            "ids": np.asarray(idx.ids),
            "offsets": np.asarray(idx.offsets),
            "cell_bounds": np.asarray(idx.cell_bounds),
            "vec_bounds": np.asarray(idx.vec_bounds),
        }
        for j in range(idx.n_shards):
            state[f"shard{j}/cells"] = np.asarray(idx.cells[j])
            state[f"shard{j}/base_q"] = np.asarray(idx.base_q[j])
            state[f"shard{j}/scales"] = np.asarray(idx.scales[j])
            state[f"shard{j}/base_f"] = np.asarray(idx.base_f[j])
        state.update(self._attr_state_leaves())
        return state

    def from_state_dict(self, state: dict) -> None:
        self.metric = state["metric"]
        n_shards = int(state["n_shards"])
        fmt = int(state.get("state_format", 1))
        if fmt >= 2:
            base_f = jnp.stack([jnp.asarray(state[f"shard{j}/base_f"])
                                for j in range(n_shards)])
        else:
            # v1 checkpoints carried a replicated (N, d) rerank store;
            # re-slice it into the stacked per-shard form (byte-identical
            # to what shard_ivf would have produced)
            base = np.asarray(state["base"], np.float32)
            vb = np.asarray(state["vec_bounds"])
            npad = int(np.asarray(state["shard0/base_q"]).shape[0])
            bf = np.zeros((n_shards, npad, base.shape[1]), np.float32)
            for j in range(n_shards):
                v0, v1 = int(vb[j]), int(vb[j + 1])
                bf[j, : v1 - v0] = base[v0:v1]
            base_f = jnp.asarray(bf)
        self.index = ShardedIvfIndex(
            centroids=jnp.asarray(state["centroids"]),
            cell_shard=jnp.asarray(state["cell_shard"]),
            cell_row=jnp.asarray(state["cell_row"]),
            cells=jnp.stack([jnp.asarray(state[f"shard{j}/cells"])
                             for j in range(n_shards)]),
            vec_start=jnp.asarray(state["vec_start"]),
            base_q=jnp.stack([jnp.asarray(state[f"shard{j}/base_q"])
                              for j in range(n_shards)]),
            scales=jnp.stack([jnp.asarray(state[f"shard{j}/scales"])
                              for j in range(n_shards)]),
            base_f=base_f,
            ids=jnp.asarray(state["ids"]),
            offsets=np.asarray(state["offsets"]),
            cell_bounds=np.asarray(state["cell_bounds"]),
            vec_bounds=np.asarray(state["vec_bounds"]),
            metric=state["metric"])
        self._placed_search = None
        self._mesh = None
        self._restore_attr_leaves(state)
