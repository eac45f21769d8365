"""Jitted search programs for the streaming (mutable) IVF family.

Two extensions over the read-only programs in ``backends/ivf.py`` /
``backends/sharded.py``, both flowing through the existing validity-mask
machinery:

- **tombstones** — a ``live`` bool mask over cell-major positions is
  AND-ed into the scan validity exactly where pad slots (-1) already
  are, so a tombstoned vector scores BIG through scan *and* rerank and
  can never displace a real neighbor.
- **delta tail** — a fixed-capacity fp32 segment scanned exactly
  (brute-force, per query batch) next to the int8 cells.  Tail entries
  skip the shortlist cut entirely: their exact distances join the
  reranked base shortlist just before the final top-k, so an inserted
  vector is served with full fp32 accuracy from the moment it lands —
  at max nprobe the result equals an exact search over base ∪ tail
  (the property test's anchor).

Dead tail slots are masked by ``tail_live`` the same way; the final ids
are read off ``ids_ext`` (base position→id table concatenated with the
tail id table) and slots whose distance is still BIG come back as -1.

Everything is fixed-shape: mutations (insert/delete) change array
*contents*, never shapes, so the serving trace survives any number of
mutations — only ``compact()`` (a new base layout) retraces.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.anns import search as search_lib
from repro.anns.backends.quantized import fp32_rescore
from repro.anns.backends.sharded import _route
from repro.kernels.distance.ops import pairwise_distance
from repro.kernels.topk.ops import topk_smallest

BIG = search_lib.BIG


def _tail_dists(q32, tail_vecs, tail_live, metric: str):
    """Exact fp32 distances to every tail slot, dead slots -> BIG."""
    B = q32.shape[0]
    cap, d = tail_vecs.shape
    td = search_lib._qdist(q32, jnp.broadcast_to(tail_vecs, (B, cap, d)),
                           metric)
    return jnp.where(tail_live[None, :], td, BIG)


@functools.partial(jax.jit, static_argnames=(
    "nprobe", "k", "m", "metric", "quantized"))
def stream_ivf_search(centroids, cells, base, base_q, scales, live,
                      tail_vecs, tail_live, ids_ext, queries, *,
                      nprobe: int, k: int, m: int, metric: str,
                      quantized: bool):
    """(B, d) queries -> (ids (B, k), dists (B, k)) over base ∪ tail.

    The base half is the read-only ``_ivf_search`` program with the
    ``live`` tombstone mask folded into scan validity; the tail half is
    an exact fp32 scan whose distances bypass the shortlist cut and meet
    the reranked base shortlist at the final top-k.  Rows beyond the
    live count come back as id -1 / dist BIG (fixed output shape).
    """
    B = queries.shape[0]
    n = base.shape[0]
    cap = tail_vecs.shape[0]
    q32 = queries.astype(jnp.float32)

    dc = pairwise_distance(q32, centroids, metric=metric)      # (B, C)
    _, probe = topk_smallest(dc, nprobe)                       # (B, nprobe)

    cand = cells[probe].reshape(B, -1)                         # (B, np*pad)
    valid = cand >= 0
    pos = jnp.where(valid, cand, 0)
    valid = valid & live[pos]          # tombstones ride the pad-slot mask
    if quantized:
        vecs = base_q[pos].astype(jnp.float32) * scales[pos][..., None]
    else:
        vecs = base[pos]
    d = search_lib._qdist(q32, vecs, metric, quantized=quantized)
    d = jnp.where(valid, d, BIG)

    _, keep = jax.lax.top_k(-d, m)
    short = jnp.take_along_axis(pos, keep, axis=1)             # (B, m)
    short_valid = jnp.take_along_axis(valid, keep, axis=1)
    rd = fp32_rescore(base, q32, short, metric=metric, valid=short_valid)

    td = _tail_dists(q32, tail_vecs, tail_live, metric)        # (B, cap)
    tpos = n + jnp.broadcast_to(jnp.arange(cap, dtype=short.dtype), (B, cap))
    all_pos = jnp.concatenate([short, tpos], axis=1)
    all_d = jnp.concatenate([rd, td], axis=1)
    nd, order = jax.lax.top_k(-all_d, k)
    out_pos = jnp.take_along_axis(all_pos, order, axis=1)
    out_d = -nd
    out_ids = jnp.where(out_d < BIG, ids_ext[out_pos], -1)
    scanned = jnp.sum(valid) + B * jnp.sum(tail_live)
    return out_ids, out_d, scanned


def _stream_scan_block(shard_id, cells_j, v0_j, bq_j, sc_j, bf_j, live_j,
                       tv_j, tl_j, q32, owner, row, *, m_shard: int,
                       metric: str, quantized: bool):
    """One shard's scan + local rerank + local tail scan.

    The base half is ``backends.sharded._scan_rerank_block`` with the
    shard's ``live`` mask folded into scan validity; the tail half is
    the shard's own fixed-capacity exact scan.  Returns the base
    shortlist tuple plus the (B, cap) tail distances — tail entries
    never enter the shortlist cut (see :func:`_stream_merge_topk`).
    """
    B = q32.shape[0]
    mine = owner == shard_id                                # (B, nprobe)
    cand = cells_j[jnp.where(mine, row, 0)]                 # (B, np, pad)
    cand = jnp.where(mine[..., None], cand, -1).reshape(B, -1)
    valid = cand >= 0
    pos = jnp.where(valid, cand, 0)                         # local pos
    valid = valid & live_j[pos]
    if quantized:
        vecs = bq_j[pos].astype(jnp.float32) * sc_j[pos][..., None]
    else:
        vecs = bf_j[pos]
    d = search_lib._qdist(q32, vecs, metric, quantized=quantized)
    d = jnp.where(valid, d, BIG)
    nd, keep = jax.lax.top_k(-d, m_shard)
    lpos = jnp.take_along_axis(pos, keep, axis=1)
    kept_valid = jnp.take_along_axis(valid, keep, axis=1)
    rd = fp32_rescore(bf_j, q32, lpos, metric=metric, valid=kept_valid)
    td = _tail_dists(q32, tv_j, tl_j, metric)
    scanned = jnp.sum(valid) + B * jnp.sum(tl_j)
    return lpos + v0_j, -nd, rd, kept_valid, td, scanned


def _stream_merge_topk(gpos, sd, rd, valid, td, ids_ext, *, k: int,
                       m_total: int, n: int):
    """Merge stacked (S, B, m) base shortlists + (S, B, cap) tail dists.

    The base cut is exactly ``backends.sharded._merge_topk``'s: global
    top-``m_total`` by scan distance, so the surviving base candidate
    set matches the unsharded program's shortlist.  Tail entries are
    appended *uncut* — their exact distances already equal their rerank
    distances, and cutting them by the (int8) scan scores of base
    candidates would let an optimistic quantized distance evict an
    exact one, breaking the sharded ≡ ivf streaming equivalence.
    """
    S, B, cap = td.shape
    gpos = gpos.transpose(1, 0, 2).reshape(B, -1)               # (B, S*m)
    sd = sd.transpose(1, 0, 2).reshape(B, -1)
    rd = rd.transpose(1, 0, 2).reshape(B, -1)
    valid = valid.transpose(1, 0, 2).reshape(B, -1)
    _, keep = jax.lax.top_k(-jnp.where(valid, sd, BIG), m_total)
    short_rd = jnp.take_along_axis(rd, keep, axis=1)
    short_pos = jnp.take_along_axis(gpos, keep, axis=1)

    taild = td.transpose(1, 0, 2).reshape(B, -1)                # (B, S*cap)
    tpos = n + jnp.broadcast_to(
        jnp.arange(S * cap, dtype=gpos.dtype), (B, S * cap))
    all_pos = jnp.concatenate([short_pos, tpos], axis=1)
    all_d = jnp.concatenate([short_rd, taild], axis=1)
    nd, order = jax.lax.top_k(-all_d, k)
    out_pos = jnp.take_along_axis(all_pos, order, axis=1)
    out_d = -nd
    return jnp.where(out_d < BIG, ids_ext[out_pos], -1), out_d


@functools.partial(jax.jit, static_argnames=(
    "nprobe", "k", "m", "metric", "quantized"))
def stream_sharded_search(centroids, cell_shard, cell_row, cells, vec_start,
                          base_q, scales, base_f, live, tail_vecs,
                          tail_live, ids_ext, queries, *, nprobe: int,
                          k: int, m: int, metric: str, quantized: bool):
    """Single-device streaming form: per-shard bodies unrolled (same
    trick as ``_sharded_search`` — bit-identical per-shard floats), then
    the streaming merge.  ``live`` is (S, Npad) over local positions,
    the tails are (S, cap, d) / (S, cap), and ``ids_ext`` concatenates
    the global position→id table with the flattened (S*cap) tail ids.
    """
    n_shards, _, pad = cells.shape
    cap = tail_vecs.shape[1]
    n = ids_ext.shape[0] - n_shards * cap
    q32, owner, row = _route(centroids, cell_shard, cell_row, queries,
                             nprobe=nprobe, metric=metric)
    m_shard = min(m, nprobe * pad)

    outs = [_stream_scan_block(
        jnp.int32(j), cells[j], vec_start[j], base_q[j], scales[j],
        base_f[j], live[j], tail_vecs[j], tail_live[j], q32, owner, row,
        m_shard=m_shard, metric=metric, quantized=quantized)
        for j in range(n_shards)]
    gpos, sd, rd, valid, td = (jnp.stack(t) for t in list(zip(*outs))[:5])
    scanned = sum(o[5] for o in outs)

    m_total = min(m, n_shards * m_shard)
    out_ids, out_d = _stream_merge_topk(gpos, sd, rd, valid, td, ids_ext,
                                        k=k, m_total=m_total, n=n)
    return out_ids, out_d, scanned


def make_placed_stream_search(mesh):
    """Mesh form of :func:`stream_sharded_search`: the per-shard body
    (base scan + local rerank + local tail scan) runs in a ``shard_map``
    over the ``"shard"`` axis; the collectives are the shortlist
    ``all_gather`` — now carrying the (S, B, cap) tail distances too —
    plus the scalar ``psum``.  Mutable leaves (live mask, tail arrays)
    are sharded like the base slices, so a mutation never moves base
    bytes between devices.  As in ``backends.sharded``, the coarse route
    runs inside the ``shard_map`` on every device: its Pallas kernels
    cannot be partitioned automatically."""
    from jax.sharding import PartitionSpec as P

    @functools.partial(jax.jit, static_argnames=(
        "nprobe", "k", "m", "metric", "quantized"))
    def placed_stream_search(centroids, cell_shard, cell_row, cells,
                             vec_start, base_q, scales, base_f, live,
                             tail_vecs, tail_live, ids_ext, queries, *,
                             nprobe: int, k: int, m: int, metric: str,
                             quantized: bool):
        n_shards, _, pad = cells.shape
        cap = tail_vecs.shape[1]
        n = ids_ext.shape[0] - n_shards * cap
        m_shard = min(m, nprobe * pad)

        def block(cents, c_shard, c_row, qs, cells_b, v0_b, bq_b, sc_b,
                  bf_b, live_b, tv_b, tl_b):
            j = jax.lax.axis_index("shard")
            q32, owner, row = _route(cents, c_shard, c_row, qs,
                                     nprobe=nprobe, metric=metric)
            gpos, sd, rd, valid, td, scanned = _stream_scan_block(
                j, cells_b[0], v0_b[0], bq_b[0], sc_b[0], bf_b[0],
                live_b[0], tv_b[0], tl_b[0], q32, owner, row,
                m_shard=m_shard, metric=metric, quantized=quantized)
            out = [jax.lax.all_gather(t, "shard")
                   for t in (gpos, sd, rd, valid, td)]
            return (*out, jax.lax.psum(scanned, "shard"))

        gpos, sd, rd, valid, td, scanned = jax.shard_map(
            block, mesh=mesh,
            in_specs=(P(), P(), P(), P(),
                      P("shard", None, None), P("shard"),
                      P("shard", None, None), P("shard", None),
                      P("shard", None, None), P("shard", None),
                      P("shard", None, None), P("shard", None)),
            out_specs=(P(), P(), P(), P(), P(), P()),
            check_vma=False)(centroids, cell_shard, cell_row, queries,
                             cells, vec_start, base_q, scales, base_f,
                             live, tail_vecs, tail_live)
        m_total = min(m, n_shards * m_shard)
        out_ids, out_d = _stream_merge_topk(gpos, sd, rd, valid, td,
                                            ids_ext, k=k, m_total=m_total,
                                            n=n)
        return out_ids, out_d, scanned

    return placed_stream_search
