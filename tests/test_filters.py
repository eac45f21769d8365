"""Filtered-search unit layer: predicate algebra, typed fail-fast paths,
dataset attribute generation, filtered ground truth, and the frontier's
filter-aware serialization.

The cross-backend exactness bar lives in ``test_differential.py``; the
streaming attribute lifecycle lives in ``test_stream.py``.  This file
pins everything underneath:

- :class:`FilterPredicate` canonicalization (sorted unique values, so
  equal predicates hash equal — every mask cache keys on that), the CLI
  grammar, and the typed errors (:class:`EmptyPredicate`,
  :class:`UnknownAttribute`, :class:`AttributeMismatch`).
- fail-fast at the serving boundary: a malformed filter is rejected at
  ``AnnsServer.submit`` / ``set_attributes`` with a typed error, never
  discovered inside a jitted batch.
- ``exact_ground_truth`` tie-breaking: duplicate base vectors always
  yield the lowest id (stable argsort) — the regression that made gt,
  and therefore measured recall, backend-dependent.
- attribute columns ride a *separate* salted rng stream: base/query/gt
  bytes are byte-identical whatever columns are requested.
"""
import dataclasses

import numpy as np
import pytest

from repro.anns import SearchParams, make_dataset, registry
from repro.anns.datasets import (exact_ground_truth, filtered_recall_at_k,
                                 selectivity_filter)
from repro.anns.engine import VariantConfig, family_baseline
from repro.anns.filters import (AttributeMismatch, EmptyPredicate,
                                FilterError, FilterPredicate,
                                UnknownAttribute, check_attributes,
                                describe_filter, parse_filter,
                                require_filterable)

# ---------------------------------------------------------------------------
# predicate algebra
# ---------------------------------------------------------------------------


def test_predicate_canonicalizes_sorted_unique():
    a = FilterPredicate("cat", (5, 1, 3, 1, 5))
    b = FilterPredicate.isin("cat", [3, 5, 1])
    assert a.values == (1, 3, 5)
    assert a == b and hash(a) == hash(b)
    assert FilterPredicate.eq("cat", 7).values == (7,)


def test_predicate_parse_grammar_roundtrip():
    p = parse_filter("cat=3|1|5")
    assert (p.attr, p.values) == ("cat", (1, 3, 5))
    assert parse_filter(p.describe()) == p
    assert str(parse_filter("bucket=4")) == "bucket=4"
    assert describe_filter(None) == ""


@pytest.mark.parametrize("bad", ["cat", "=3", "cat=", "cat=a|b", "cat=1.5"])
def test_predicate_parse_rejects_malformed(bad):
    with pytest.raises(FilterError):
        parse_filter(bad)


def test_empty_predicate_set_raises_typed():
    with pytest.raises(EmptyPredicate):
        FilterPredicate("cat", ())
    with pytest.raises(EmptyPredicate):
        FilterPredicate.isin("cat", [])


def test_predicate_mask_and_selectivity():
    attrs = {"cat": np.array([0, 1, 2, 1, 0], np.int32)}
    p = FilterPredicate.isin("cat", [1])
    assert p.mask(attrs, 5).tolist() == [False, True, False, True, False]
    assert p.selectivity(attrs) == pytest.approx(0.4)
    with pytest.raises(UnknownAttribute):
        p.mask({}, 5)
    with pytest.raises(UnknownAttribute):
        FilterPredicate.eq("tenant", 0).mask(attrs, 5)
    with pytest.raises(AttributeMismatch):
        p.mask(attrs, 6)          # length mismatch vs the target


def test_check_attributes_typed_failures():
    ok = check_attributes({"cat": np.arange(4, dtype=np.int64)}, 4)
    assert ok["cat"].dtype == np.int32
    with pytest.raises(AttributeMismatch):
        check_attributes("nope", 4)
    with pytest.raises(AttributeMismatch):
        check_attributes({}, 4)
    with pytest.raises(AttributeMismatch):
        check_attributes({"cat": np.zeros(4, np.float32)}, 4)
    with pytest.raises(AttributeMismatch):
        check_attributes({"cat": np.zeros((4, 2), np.int32)}, 4)
    with pytest.raises(AttributeMismatch):
        check_attributes({"cat": np.zeros(5, np.int32)}, 4)


# ---------------------------------------------------------------------------
# fail-fast at the backend / serving boundary
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small_ds():
    return make_dataset("sift-128-euclidean", n_base=200, n_query=4,
                        k_gt=10, seed=1)


@pytest.fixture(scope="module")
def brute(small_ds):
    b = registry.create("brute_force",
                        family_baseline("brute_force"),
                        metric=small_ds.metric)
    b.build(small_ds.base)
    return b


def test_search_without_attributes_raises_typed(small_ds, brute):
    b = registry.create("brute_force", family_baseline("brute_force"),
                        metric=small_ds.metric)
    b.build(small_ds.base)
    with pytest.raises(UnknownAttribute, match="set_attributes"):
        b.search(small_ds.queries,
                 SearchParams(k=5, filter=FilterPredicate.eq("cat", 0)))


def test_set_attributes_length_mismatch_raises(small_ds, brute):
    with pytest.raises(AttributeMismatch, match="200"):
        brute.set_attributes({"cat": np.zeros(7, np.int32)})


def test_search_unknown_attribute_raises(small_ds, brute):
    brute.set_attributes(small_ds.attrs)
    with pytest.raises(UnknownAttribute, match="tenant"):
        brute.search(small_ds.queries,
                     SearchParams(k=5, filter=FilterPredicate.eq("tenant", 3)))


def test_server_submit_fail_fast(small_ds):
    """A filtered operating point is rejected at enqueue — typed — when
    the served backend cannot honor it."""
    from repro.runtime.server import AnnsServer
    b = registry.create("brute_force", family_baseline("brute_force"),
                        metric=small_ds.metric)
    b.build(small_ds.base)
    flt = SearchParams(k=5, filter=FilterPredicate.eq("cat", 0))
    with pytest.raises(UnknownAttribute, match="no attribute columns"):
        AnnsServer(b, params=flt).submit(small_ds.queries[0])
    b.set_attributes(small_ds.attrs)
    bad = SearchParams(k=5, filter=FilterPredicate.eq("tenant", 0))
    with pytest.raises(UnknownAttribute, match="tenant"):
        AnnsServer(b, params=bad).submit(small_ds.queries[0])
    with pytest.raises(FilterError, match="FilterPredicate"):
        AnnsServer(b, params=SearchParams(k=5, filter="cat=0")).submit(
            small_ds.queries[0])
    # the well-formed predicate serves end to end
    srv = AnnsServer(b, params=flt)
    srv.submit(small_ds.queries[0])
    (resp,) = srv.run()
    mask = flt.filter.mask(small_ds.attrs, 200)
    assert all(mask[i] for i in resp.ids if i >= 0)


def test_require_filterable_accepts_none():
    require_filterable(None, None)            # unfiltered: nothing to check


# ---------------------------------------------------------------------------
# exact gt: stable tie-breaking (regression)
# ---------------------------------------------------------------------------


def test_exact_gt_ties_break_by_ascending_id():
    """Duplicate base vectors: the lowest id must win every tie, on both
    metrics — unstable tie order made gt depend on the sort backend."""
    rng = np.random.default_rng(0)
    uniq = rng.standard_normal((30, 16)).astype(np.float32)
    base = np.repeat(uniq, 2, axis=0)          # rows 2i and 2i+1 identical
    queries = uniq[:8] + 1e-3 * rng.standard_normal((8, 16)).astype(np.float32)
    for metric in ("l2", "ip"):
        gt = exact_ground_truth(base, queries, 10, metric)
        # identical vectors are adjacent id pairs: wherever both of a
        # pair appear, the even (lower) id must come first
        for row in gt:
            pos = {int(v): j for j, v in enumerate(row)}
            for v, j in pos.items():
                twin = v + 1 if v % 2 == 0 else v - 1
                if twin in pos:
                    lo, hi = sorted((v, twin))
                    assert pos[lo] < pos[hi], (metric, row)
        # and the whole computation is deterministic
        assert np.array_equal(gt, exact_ground_truth(base, queries, 10,
                                                     metric))


@pytest.mark.parametrize("k", [1, 10, 63, 64, 80])
def test_stable_smallest_equals_stable_argsort(k):
    """The partial selection behind the ground truth returns exactly the
    stable full sort's prefix, ties at the k-th value included."""
    from repro.anns.datasets import stable_smallest
    rng = np.random.default_rng(k)
    d = rng.integers(0, 12, size=(20, 64)).astype(np.float32)  # many ties
    want = np.argsort(d, axis=1, kind="stable")[:, :k]
    assert np.array_equal(stable_smallest(d, k), want)


# ---------------------------------------------------------------------------
# dataset attributes + filtered gt
# ---------------------------------------------------------------------------


def test_attribute_stream_never_perturbs_base_bytes():
    a = make_dataset("sift-128-euclidean", n_base=150, n_query=5, k_gt=5)
    b = make_dataset("sift-128-euclidean", n_base=150, n_query=5, k_gt=5,
                     attributes={"tenant": 3})
    assert a.base.tobytes() == b.base.tobytes()
    assert a.queries.tobytes() == b.queries.tobytes()
    assert a.gt.tobytes() == b.gt.tobytes()
    assert sorted(a.attrs) == ["bucket", "cat"]
    assert sorted(b.attrs) == ["tenant"]
    # deterministic across calls
    c = make_dataset("sift-128-euclidean", n_base=150, n_query=5, k_gt=5)
    assert all(np.array_equal(a.attrs[x], c.attrs[x]) for x in a.attrs)


def test_filtered_gt_masks_pads_and_caches(small_ds):
    pred = selectivity_filter(small_ds, 0.02)
    gt = small_ds.filtered_gt(pred, k=10)
    assert gt.shape == (4, 10)
    mask = pred.mask(small_ds.attrs, 200)
    n_match = int(mask.sum())
    real = gt[gt >= 0]
    assert mask[real].all()                     # only matching rows
    # fewer matches than k: every row padded to exactly the match count
    if n_match < 10:
        assert (gt >= 0).sum(axis=1).tolist() == [n_match] * 4
    assert small_ds.filtered_gt(pred, k=10) is gt     # cache hit
    # the cache distinguishes k
    assert small_ds.filtered_gt(pred, k=5).shape == (4, 5)


def test_selectivity_filter_dials_fraction(small_ds):
    for sel in (0.5, 0.1, 0.02):
        pred = selectivity_filter(small_ds, sel)
        assert abs(pred.selectivity(small_ds.attrs) - sel) < 0.12
    with pytest.raises(FilterError):
        selectivity_filter(small_ds, 0.5, attr="missing")


def test_filtered_recall_ignores_pads():
    gt = np.array([[3, 7, -1], [1, 2, 4]])
    found = np.array([[7, -1, -1], [1, 2, 4]])
    # row 0: 1 of 2 true matches; row 1: 3 of 3 => 4/5
    assert filtered_recall_at_k(found, gt, 3) == pytest.approx(4 / 5)
    empty = np.full((2, 3), -1)
    assert filtered_recall_at_k(empty, empty, 3) == 1.0


# ---------------------------------------------------------------------------
# frontier: filter-aware points
# ---------------------------------------------------------------------------


def test_operating_point_filter_roundtrip_and_domination():
    from repro.anns.tune import OperatingPoint, dominates, pareto_prune
    pred = FilterPredicate.isin("cat", [0, 1, 2])
    flt = OperatingPoint(backend="ivf",
                         params=SearchParams(k=10, ef=64, filter=pred),
                         recall=0.5, qps=100.0, selectivity=0.03)
    unf = OperatingPoint(backend="ivf", params=SearchParams(k=10, ef=64),
                         recall=0.99, qps=5000.0)
    # a filtered point's recall is against a different gt: never
    # comparable, never pruned by the unfiltered frontier
    assert not dominates(unf, flt) and not dominates(flt, unf)
    assert set(pareto_prune([unf, flt])) == {unf, flt}
    d = flt.to_json_dict()
    assert d["params"]["filter"] == "cat=0|1|2"
    assert d["selectivity"] == pytest.approx(0.03)
    rt = OperatingPoint.from_json_dict(d)
    assert rt.params.filter == pred and rt == flt
    # unfiltered round-trip stays filter-free
    assert OperatingPoint.from_json_dict(unf.to_json_dict()) == unf


def test_sweep_carries_filter_axis(small_ds):
    """sweep_target's filters axis: filtered points are scored against
    the filtered gt and stamped with their selectivity."""
    from repro.anns.tune import sweep_target
    b = registry.create("ivf",
                        VariantConfig(backend="ivf", nlist=8,
                                      kmeans_iters=2),
                        metric=small_ds.metric)
    b.build(small_ds.base)
    b.set_attributes(small_ds.attrs)
    pred = selectivity_filter(small_ds, 0.5)
    pts = sweep_target(b, small_ds, k=5, repeats=1,
                       filters=(None, pred))
    sels = {p.params.filter: p.selectivity for p in pts}
    assert sels[None] == 1.0
    assert sels[pred] == pytest.approx(pred.selectivity(small_ds.attrs))
    # max-effort filtered rung probes every cell: near-exact against the
    # filtered gt (int8 scan default; the fp32 exactness bar is
    # test_differential's)
    top = max((p for p in pts if p.params.filter == pred),
              key=lambda p: p.params.ef)
    assert top.recall >= 0.9
