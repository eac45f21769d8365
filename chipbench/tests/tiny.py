"""A tiny build of the sift1m configuration, l2 or made angular, shared
by the CPU tests."""
from __future__ import annotations

import functools

from chipbench import data as data_lib
from chipbench import harness, spec
from chipbench.tests.conftest import ANGULAR, TINY


@functools.lru_cache(maxsize=None)
def built(seed: int = 0, angular: bool = False):
    """(config, system, data, backend, partition) of a tiny build."""
    bench = spec.Bench()
    cfg = harness._merge(bench.config("sift1m"), TINY)
    if angular:
        cfg = harness._merge(cfg, ANGULAR)
    system = bench.system(cfg["system"])
    dat = data_lib.make(cfg["data"], seed, cfg["serve"]["k"])
    backend, _ = system.build(cfg, dat.base, seed)
    return cfg, system, dat, backend, system.partition(backend)


def served(backend, system, cfg, queries) -> list:
    """(query index, ids, dists) as the program serves each pool query."""
    from repro.runtime.server import execute_search_batch
    b = cfg["serve"]["max_batch"]
    out = []
    for lo in range(0, len(queries), b):
        ids, dists, _ = execute_search_batch(
            backend.search, queries[lo:lo + b], system.params(cfg),
            max_batch=b)
        out += [(lo + i, ids[i], dists[i]) for i in range(len(ids))]
    return out
