"""The ``ivf`` deployment: ``registry.create("ivf")`` -> ``build`` ->
``AsyncServeTier``, with its float64 plain reference
(:mod:`chipbench.references.ivf`) and the work one served batch needs.

The configuration's ``index`` block gives the ``IVF_BASELINE`` knobs;
``SearchParams(ef=64)`` probes exactly ``index.nprobe`` cells.

The data block's metric maps to the backend's by :data:`METRICS`, and
any other raises: the backend ranks every metric that is not ``"ip"`` by
l2, so an unmapped name would be served as l2 in silence.  What the
system guarantees: every admitted request is answered with ``k``
distinct original ids and their float32 rerank distances, ascending.  An
l2 deployment's served distance is the squared l2 distance; an angular
deployment's is the float32 ``-q.x`` of its unit vectors, so
ANN-Benchmarks' angular distance (one minus the cosine) is one plus it.
Those vectors come normalized from ``chipbench/data.py``: the benchmark
does the step that ANN-Benchmarks leaves to the system, and the served
path never normalizes.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from chipbench.references import ivf as reference

#: the jitted search as it is named in a device trace's "XLA Modules"
SEARCH_MODULE = "jit__ivf_search"
#: the data block's metric -> the backend's (and the reference's)
METRICS = {"l2": "l2", "angular": "ip"}
#: the coarse route's Pallas kernels (distance, top-k), by the instruction
#: name their ops carry in a device trace (read by hand on a v5e trace)
COARSE_KERNELS = ("%distance", "%topk_smallest")


def backend_metric(cfg: dict) -> str:
    """The backend's metric for the configuration's data block."""
    name = cfg["data"]["metric"]
    if name not in METRICS:
        raise ValueError(f"the ivf system serves the metrics "
                         f"{sorted(METRICS)}, not {name!r}")
    return METRICS[name]


def variant(cfg: dict):
    from repro.anns.engine import IVF_BASELINE
    ix = cfg["index"]
    return dataclasses.replace(
        IVF_BASELINE, backend="ivf", nlist=ix["nlist"], nprobe=ix["nprobe"],
        max_cell=ix["max_cell"], kmeans_iters=ix["kmeans_iters"],
        rerank_factor=ix["rerank_factor"])


def params(cfg: dict):
    from repro.anns.api import SearchParams
    return SearchParams(k=cfg["serve"]["k"], ef=64, quantized=True)


def build(cfg: dict, base: np.ndarray, seed: int):
    """The built backend and the host seconds around ``build`` (k-means
    and layout), ended by a device sync."""
    from repro.anns import registry
    backend = registry.create("ivf", variant(cfg), metric=backend_metric(cfg),
                              seed=int(seed) % 2**63)
    t0 = time.perf_counter()
    idx = backend.build(base)
    jax.block_until_ready((idx.centroids, idx.cells, idx.ids, idx.base,
                           idx.base_q, idx.scales))
    return backend, time.perf_counter() - t0


def describe(backend) -> dict:
    idx = backend.index
    return {"nlist": idx.nlist, "cell_pad": idx.cell_pad,
            "index_bytes": int(backend.memory_bytes())}


def partition(backend) -> reference.Partition:
    """The build's partition, read back to the host: centroids, and each
    cell's original ids in the order the cell stores them."""
    idx = backend.index
    ids = np.asarray(idx.ids)
    off = np.asarray(idx.offsets)
    return reference.Partition(
        centroids=np.asarray(idx.centroids, np.float32),
        members=[ids[off[c]:off[c + 1]] for c in range(len(off) - 1)])


def stated(cfg: dict) -> dict:
    """(nprobe, m, k, metric) as the configuration states them: the
    reference takes them from there, never from the program."""
    k = cfg["serve"]["k"]
    return {"nprobe": cfg["index"]["nprobe"],
            "m": max(k, cfg["index"]["rerank_factor"] * k), "k": k,
            "metric": backend_metric(cfg)}


def limits(cfg: dict) -> dict:
    """Each compared number's limit: the configuration's, and its
    ``nprobe`` for the probe floor."""
    return dict(cfg["limits"], probe_floor=cfg["index"]["nprobe"])


def batch_work(part: reference.Partition, probe: np.ndarray, batch: list,
               cfg: dict) -> tuple:
    """(operations, bytes) one batch of the search needs at least, from
    the search's semantics, not from any implementation.

    ``probe``: (n_pool, nprobe) cells per pool query; ``batch``: pool
    indices of the requests served together.  Bytes: each probed cell's
    real vectors (int8 code and a float32 scale) once per batch, over the
    union of the batch's probes; each query's ``m`` float32 rerank rows;
    the centroids; the queries.  Operations: a multiply and an add per
    dimension for each query's probed real vectors, its coarse distances
    and its rerank rows.  Pad slots count for nothing.
    """
    d = cfg["data"]["d"]
    st = stated(cfg)
    sizes = part.sizes
    nlist = len(sizes)
    cells = probe[np.asarray(batch)]
    per_query_vectors = sizes[cells].sum()
    union = sizes[np.unique(cells)].sum()
    b = len(batch)
    n_bytes = (union * (d + 4) + b * st["m"] * d * 4 + nlist * d * 4
               + b * d * 4)
    ops = 2 * d * (per_query_vectors + b * nlist + b * st["m"])
    return float(ops), float(n_bytes)
