"""Plain float64 reference of the IVF search, and the comparison that
decides ``correct``.  It imports nothing of the system under test.

From the system's build it takes only the partition: the centroids and,
per cell, the original ids of its members in the order the cell stores
them.  Everything else it recomputes from the benchmark's own base: the
symmetric per-vector int8 codes and scales, the coarse route, the cell
scan, the shortlist cut, the exact rerank and the ids.  Ties break by
lowest index at every cut (stable sorts), as the kernels do.

``metric`` is the configuration's, as ``systems/<system>.py:stated``
maps it, never the program's: ``"l2"`` ranks by squared l2 distance,
``"ip"`` (an angular deployment's unit vectors) by ``-q.x``, at every
stage: the coarse route over the centroids, the scan over the
dequantised codes and the exact rerank.

``rerank="bf16"`` is the control: the same search with the rerank's dot
product taken as one bfloat16 pass with float32 accumulation, which is
what a float32 dot at default precision does on a TPU.  The configuration
states a float32 rerank, so the control must come out not correct.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import ml_dtypes
import numpy as np


@dataclass
class Partition:
    centroids: np.ndarray        # (C, d) float32
    members: list                # per cell: original ids, in cell order

    @property
    def sizes(self) -> np.ndarray:
        return np.array([len(m) for m in self.members], np.int64)


def quantize(rows: np.ndarray) -> tuple:
    """Symmetric per-vector int8: x ~= code * scale, scale = max|x| / 127,
    in float32 as the configuration states it."""
    x = rows.astype(np.float32)
    scale = np.maximum(np.abs(x).max(axis=1), np.float32(1e-12)) \
        / np.float32(127.0)
    codes = np.clip(np.rint(x / scale[:, None]), -127, 127)
    return codes, scale


def probe_floor(sizes: np.ndarray, k: int) -> int:
    """The fewest cells that always hold ``k`` vectors: the smallest j
    such that the j smallest cells do.  A query whose probed cells could
    hold fewer than ``k`` has no answer of ``k`` distinct ids, so the
    program raises a stated nprobe under this floor, and then does
    other work than the configuration states."""
    cum = np.cumsum(np.sort(sizes))
    return int(np.searchsorted(cum, min(k, int(cum[-1]))) + 1)


def _metric(metric: str) -> str:
    if metric not in ("l2", "ip"):
        raise ValueError(f"reference metric must be l2 or ip, not "
                         f"{metric!r}")
    return metric


def _dist(rows: np.ndarray, q: np.ndarray, metric: str) -> np.ndarray:
    """float64 distance of each row to ``q``: squared l2, or ``-q.x``."""
    if _metric(metric) == "ip":
        return -(rows @ q)
    return ((rows - q) ** 2).sum(1)


def probes(partition: Partition, queries: np.ndarray, nprobe: int, *,
           metric: str) -> np.ndarray:
    """(nq, nprobe) cells each query probes: float64 coarse route."""
    c = partition.centroids.astype(np.float64)
    q = np.asarray(queries, np.float64)
    if _metric(metric) == "ip":
        d = -(q @ c.T)
    else:
        d = ((q * q).sum(1)[:, None] + (c * c).sum(1)[None, :]
             - 2.0 * q @ c.T)
    return np.argsort(d, axis=1, kind="stable")[:, :nprobe]


def _bf16(x: np.ndarray) -> np.ndarray:
    return x.astype(np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


#: rows the scan takes at a time, so that a search's memory does not grow
#: with its cells (the check runs several searches at once)
SCAN_BLOCK = 2048


def search_one(q: np.ndarray, probe: np.ndarray, partition: Partition,
               base: np.ndarray, *, m: int, k: int, metric: str,
               rerank: str = "f64") -> tuple:
    """One query through probe -> int8 scan -> shortlist of m -> rerank
    -> top k.  Returns (ids (k,), dists (k,))."""
    cand = np.concatenate([partition.members[c] for c in probe])
    q64 = q.astype(np.float64)
    scan = np.empty(len(cand))
    for lo in range(0, len(cand), SCAN_BLOCK):
        codes, scale = quantize(base[cand[lo:lo + SCAN_BLOCK]])
        deq = codes * scale.astype(np.float64)[:, None]
        scan[lo:lo + SCAN_BLOCK] = _dist(deq, q64, metric)
    short = np.argsort(scan, kind="stable")[:min(m, len(cand))]
    srows = base[cand[short]]
    if rerank == "f64":
        exact = _dist(srows.astype(np.float64), q64, metric)
    elif rerank == "bf16":
        qf = q.astype(np.float32)
        dots = _bf16(srows) @ _bf16(qf)
        if metric == "ip":
            exact = (-dots).astype(np.float64)
        else:
            exact = ((qf * qf).sum() + (srows * srows).sum(1) - 2.0 * dots
                     ).astype(np.float64)
    else:
        raise ValueError(f"unknown rerank {rerank!r}")
    top = np.argsort(exact, kind="stable")[:k]
    return cand[short[top]], exact[top]


#: the numbers compared, each against the limit the configuration gives
NUMBERS = ("unanswered", "bad_answers", "probe_floor", "dist_err", "id_miss")


def dist_errors(answers: list, queries: np.ndarray, base: np.ndarray, *,
                metric: str) -> np.ndarray:
    """Per answer: the worst gap between a served distance and the
    float64 distance of the served id to that request's own query, over
    the scale its float32 arithmetic rounds at: ``|q|^2 + |x|^2`` for an
    l2 expansion, ``|q|*|x|`` for an ip dot."""
    out = np.empty(len(answers))
    for j, (qi, ids, dists) in enumerate(answers):
        q = queries[qi].astype(np.float64)
        x = base[ids].astype(np.float64)
        d64 = _dist(x, q, metric)
        if metric == "ip":
            scale = np.sqrt((q * q).sum()) * np.sqrt((x * x).sum(1))
        else:
            scale = (q * q).sum() + (x * x).sum(1)
        out[j] = np.max(np.abs(np.asarray(dists, np.float64) - d64) / scale)
    return out


def bad_answer(ids: np.ndarray, dists: np.ndarray, n: int, k: int) -> bool:
    """Wrong on its face: short, an id out of range, an id repeated, or
    distances not ascending."""
    ids = np.asarray(ids)
    return bool(len(ids) != k or ids.min() < 0 or ids.max() >= n
                or len(np.unique(ids)) != k
                or np.any(np.diff(np.asarray(dists)) < 0))


def compare(answers: list, n_unanswered: int, queries: np.ndarray,
            base: np.ndarray, partition: Partition, *, nprobe: int,
            m: int, k: int, metric: str, sample: np.ndarray) -> dict:
    """The numbers ``correct`` is decided by.

    ``answers``: (query index, served ids, served dists) of every answered
    request of the window.  ``dist_err`` is over all of them; ``id_miss``
    (the share of the reference's ids missing from the served answer) over
    the answers at positions ``sample``, searched on a few threads (numpy
    releases the interpreter lock inside each array operation), each
    probing the ``nprobe`` cells the configuration states.  ``probe_floor``
    is held to that ``nprobe``: above it, the program probes more cells
    than stated, and so does other work.
    """
    n = len(base)
    bad = {j for j, (_, ids, d) in enumerate(answers)
           if bad_answer(ids, d, n, k)}
    good = [a for j, a in enumerate(answers) if j not in bad]
    errs = dist_errors(good, queries, base, metric=metric) if good \
        else np.zeros(1)
    picked = [answers[j] for j in sample if j not in bad]
    qis = np.array([a[0] for a in picked], np.int64)
    miss = []
    if len(picked):
        pr = probes(partition, queries[qis], nprobe, metric=metric)
        with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as ex:
            refs = list(ex.map(
                lambda a: search_one(queries[a[0]], a[1], partition, base,
                                     m=m, k=k, metric=metric),
                zip(qis, pr)))
        miss = [1.0 - len(np.intersect1d(ids, ref[0])) / k
                for (_, ids, _), ref in zip(picked, refs)]
    return {"unanswered": float(n_unanswered), "bad_answers": float(len(bad)),
            "probe_floor": float(probe_floor(partition.sizes, k)),
            "dist_err": float(errs.max()),
            "id_miss": float(np.mean(miss)) if miss else 0.0}


def control_answers(picked: list, queries: np.ndarray, base: np.ndarray,
                    partition: Partition, *, nprobe: int, m: int,
                    k: int, metric: str) -> list:
    """The control put in the program's place: its answers to the same
    requests, from the bf16 rerank."""
    qis = np.array([a[0] for a in picked], np.int64)
    pr = probes(partition, queries[qis], nprobe, metric=metric)
    return [(qi, *search_one(queries[qi], p, partition, base, m=m, k=k,
                             metric=metric, rerank="bf16"))
            for qi, p in zip(qis, pr)]
