"""``"brute_force"`` backend: exact k-NN through the Pallas kernels.

Routes the previously-unused ``kernels.distance.pairwise_distance`` and
``kernels.topk.topk_smallest`` ops (MXU tile-aligned distance matrix +
VPU top-k) into a full backend.  Exact by construction — recall is 1.0 —
so it anchors every QPS-recall curve and serves as ground truth in the
cross-backend agreement tests.

The base is scanned in fixed-size chunks (one tile-aligned kernel launch
per chunk) with a running top-k merge, so memory stays O(B * chunk)
instead of O(B * N).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.anns.api import SearchParams, SearchResult
from repro.anns.filters import AttributeColumns
from repro.anns.registry import register
from repro.anns.search import BIG
from repro.kernels.distance.ops import pairwise_distance
from repro.kernels.topk.ops import topk_smallest

#: the anchor is exact: its MXU dot contracts in float32, not a bf16 pass
EXACT = jax.lax.Precision.HIGHEST


@register("brute_force")
class BruteForceBackend(AttributeColumns):
    name = "brute_force"

    #: state_format 2: optional per-vector attribute columns (attr/<col>)
    STATE_FORMAT = 2

    #: base vectors scanned per kernel launch (tile-aligned)
    chunk = 8192

    def __init__(self, variant=None, *, metric: str = "l2", seed: int = 0):
        self.variant = variant       # unused: exact search has no knobs
        self.metric = metric
        self.seed = seed
        self.index: jax.Array | None = None   # (N, d) fp32 base

    # -- AnnsIndex protocol ------------------------------------------------
    def build(self, base: np.ndarray) -> jax.Array:
        self.index = jnp.asarray(base, jnp.float32)
        self.attributes = None       # columns describe one base layout
        self._clear_filter_caches()
        return self.index

    @staticmethod
    def search_ef_ladder() -> tuple:
        """Exact search has no effort knob: one rung, recall 1.0 — the
        anchor point the autotuner sweeps exactly once."""
        return (64,)

    def search(self, queries, params: SearchParams) -> SearchResult:
        assert self.index is not None, "build() first"
        base = self.index
        n = base.shape[0]
        k = min(params.k, n)
        q = jnp.asarray(queries, jnp.float32)
        # filtered: non-matching rows score BIG before the top-k cut, so
        # this stays the exact (recall=1.0) anchor over the masked base
        fmask = (self._row_mask_dev(params.filter)
                 if params.filter is not None else None)

        vals, ids = [], []
        for lo in range(0, n, self.chunk):
            xc = base[lo: lo + self.chunk]
            d = pairwise_distance(q, xc, metric=self.metric,
                                  precision=EXACT)
            if fmask is not None:
                d = jnp.where(fmask[lo: lo + self.chunk][None, :], d, BIG)
            v, i = topk_smallest(d, min(k, xc.shape[0]))
            vals.append(v)
            ids.append(i + lo)
        if len(vals) == 1:
            out_d, out_i = vals[0], ids[0]
        else:
            allv = jnp.concatenate(vals, axis=1)
            alli = jnp.concatenate(ids, axis=1)
            out_d, order = jax.lax.top_k(-allv, k)
            out_d = -out_d
            out_i = jnp.take_along_axis(alli, order, axis=1)
        if fmask is not None:
            out_i = jnp.where(out_d < BIG, out_i, -1)
        return SearchResult(ids=out_i, dists=out_d, steps=0,
                            expansions=jnp.asarray(n * q.shape[0]),
                            backend=self.name)

    def memory_bytes(self) -> int:
        if self.index is None:
            return 0
        return self.index.size * self.index.dtype.itemsize

    def to_state_dict(self) -> dict:
        assert self.index is not None, "build() first"
        return {"backend": self.name, "metric": self.metric,
                "state_format": self.STATE_FORMAT,
                "base": np.asarray(self.index),
                **self._attr_state_leaves()}

    def from_state_dict(self, state: dict) -> None:
        self.metric = state["metric"]
        self.index = jnp.asarray(state["base"])
        self._restore_attr_leaves(state)
