"""Chip benchmark for the serving path: one cell (a deployment under a
traffic mix) per run, driven by ``BENCHMARK.json`` at the checkout root.

Run one cell once from the checkout root::

    python3 chipbench/run.py --workload sift1m.closed --seed 7 \
        --seconds 10 --trace 0

Everything that belongs to one deployment, one traffic mix or one metric
sits in a file of its own, found by the name ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: the deployment (data shape, index, serving);
  its ``system`` names ``systems/<system>.py`` (how to build and serve it,
  and its float64 plain reference and work count);
- ``traffic/<traffic>.json``: the parameters of one mix, read by the one
  generator in :mod:`chipbench.traffic`;
- ``metrics/<metric>.py``: a ``read(run)`` that returns the number, or
  ``None`` where the run has nothing to read.
"""
