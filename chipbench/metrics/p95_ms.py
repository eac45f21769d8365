"""The 95th percentile latency, by nearest rank, over every request due
in the window, each timed from when it was due to when its reply arrived
(host clock).  A request with no reply counts as infinitely late."""

from chipbench.readers import nearest_rank


def read(run):
    reqs = run.in_window()
    return nearest_rank([r.latency_ms() for r in reqs], 0.95) if reqs \
        else None
