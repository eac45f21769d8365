"""The float64 reference against the brute-force anchor, and the work
count against a brute count, on a tiny build."""
import numpy as np

from chipbench.references import ivf as ref
from chipbench.tests.tiny import built


def test_reference_at_every_cell_is_the_brute_force_anchor():
    from repro.anns import SearchParams, registry
    cfg, _, dat, _, part = built()
    anchor = registry.create("brute_force", metric="l2")
    anchor.build(dat.base)
    want = np.asarray(anchor.search(dat.queries, SearchParams(k=10)).ids)
    every = np.arange(len(part.members))
    for qi, q in enumerate(dat.queries):
        ids, dists = ref.search_one(q, every, part, dat.base,
                                    m=len(dat.base), k=10, metric="l2")
        assert set(ids) == set(want[qi]), qi
        assert np.all(np.diff(dists) >= 0)


def test_ip_reference_at_every_cell_is_a_float64_brute_force():
    """An angular build: at nprobe = nlist and a shortlist of the whole
    base, the ip reference gives the k largest float64 ``q.x``."""
    cfg, system, dat, _, part = built(angular=True)
    assert system.stated(cfg)["metric"] == "ip"
    b = dat.base.astype(np.float64)
    every = np.arange(len(part.members))
    for qi, q in enumerate(dat.queries):
        want = -(b @ q.astype(np.float64))
        ids, dists = ref.search_one(q, every, part, dat.base,
                                    m=len(dat.base), k=10, metric="ip")
        assert set(ids) == set(np.argsort(want, kind="stable")[:10]), qi
        np.testing.assert_allclose(dists, want[ids], rtol=0, atol=1e-12)
        assert np.all(np.diff(dists) >= 0)


def test_partition_covers_the_base_once():
    _, _, dat, _, part = built()
    ids = np.concatenate(part.members)
    assert np.array_equal(np.sort(ids), np.arange(len(dat.base)))


def test_work_count_matches_a_brute_count():
    cfg, system, dat, _, part = built()
    st = system.stated(cfg)
    probe = ref.probes(part, dat.queries, st["nprobe"],
                       metric=st["metric"])
    d = cfg["data"]["d"]
    batch = [3, 5, 9, 3, 17, 40, 41, 63]
    cells, per_query = set(), 0
    for qi in batch:
        for c in probe[qi]:
            cells.add(int(c))
            per_query += len(part.members[c])
    union = sum(len(part.members[c]) for c in cells)
    n_cells = len(part.members)
    want_bytes = (union * d + union * 4 + len(batch) * st["m"] * d * 4
                  + n_cells * d * 4 + len(batch) * d * 4)
    want_ops = sum(2 * d for qi in batch for c in probe[qi]
                   for _ in part.members[c]) \
        + 2 * d * n_cells * len(batch) + 2 * d * st["m"] * len(batch)
    assert per_query * 2 * d + 2 * d * (n_cells + st["m"]) * len(batch) \
        == want_ops
    ops, n_bytes = system.batch_work(part, probe, batch, cfg)
    assert (ops, n_bytes) == (float(want_ops), float(want_bytes))
