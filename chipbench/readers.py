"""Arithmetic the metric readers (``metrics/<name>.py``) share."""
from __future__ import annotations

import math

import numpy as np

from chipbench import peaks as peaks_lib
from chipbench import traces


def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile by nearest rank: a sample that was measured, and
    an infinite one (a request never answered) stays infinite."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return math.nan
    return float(v[max(0, math.ceil(q * len(v)) - 1)])


def recall_at_10(run):
    """recall@10 of the served ids against the exact ground truth, over
    every answered request due in the window."""
    gt = run.data.gt
    rows = [len(np.intersect1d(r.ids[:10], gt[r.qi, :10])) / 10.0
            for r in run.answered()]
    return float(np.mean(rows)) if rows else None


def search_module(run):
    """[device seconds, events] of the jitted search in the traced window,
    or ``None`` where the trace holds none."""
    if run.trace is None:
        return None
    hits = traces.matching(run.trace["modules"], (run.system.SEARCH_MODULE,))
    secs, count = sum(h[0] for h in hits), sum(h[1] for h in hits)
    return [secs, count] if count > 0 and secs > 0 else None


def search_device_ms(run):
    mod = search_module(run)
    return None if mod is None else mod[0] / mod[1] * 1e3


def coarse_kernels_ms(run):
    mod = search_module(run)
    if mod is None:
        return None
    hits = traces.matching(run.trace["ops"], run.system.COARSE_KERNELS)
    secs = sum(h[0] for h in hits)
    return secs / mod[1] * 1e3 if secs > 0 else None


def least_ms_per_batch(run):
    """Mean least time of the window's batches (work counted from the
    search's semantics, peaks from the table), and the bound that sets
    most of them."""
    batches = run.batches()
    if not batches or run.peaks is None:
        return None
    probe = run.probe()
    times, bounds = [], []
    for b in batches:
        ops, n_bytes = run.system.batch_work(run.partition, probe, b,
                                             run.config)
        t, bound = peaks_lib.least_seconds(ops, n_bytes, run.peaks)
        times.append(t)
        bounds.append(bound)
    return float(np.mean(times)) * 1e3, max(set(bounds), key=bounds.count)


def search_roofline(run):
    dev = search_device_ms(run)
    least = least_ms_per_batch(run)
    if dev is None or least is None:
        return None
    run.info["least_ms"], run.info["bound"] = least     # printed by the run
    return 100.0 * least[0] / dev


def device_idle_share(run):
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def median_compute_ms(run):
    v = [r.compute_ms for r in run.answered()]
    return float(np.median(v)) if v else None


def queue_wait_p95_ms(run):
    v = [r.queue_wait_ms for r in run.answered()]
    return nearest_rank(v, 0.95) if v else None
