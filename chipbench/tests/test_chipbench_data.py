import hashlib

import numpy as np
import pytest

from chipbench import data as data_lib

SPEC = {"n": 2048, "d": 16, "n_query": 64, "metric": "l2", "clusters": 4,
        "intrinsic_dim": 4, "centre_scale": 1.5, "spread": 1.0,
        "noise": 0.5}


def test_same_seed_same_bytes_and_big_seeds_differ():
    a = data_lib.make(SPEC, 2**31 + 7, 10)
    b = data_lib.make(SPEC, 2**31 + 7, 10)
    c = data_lib.make(SPEC, 2**31 + 7 + 2**32, 10)
    for x, y in ((a.base, b.base), (a.queries, b.queries), (a.gt, b.gt)):
        assert x.tobytes() == y.tobytes()
    assert a.base.tobytes() != c.base.tobytes()


def _nn_dist(x, base, skip_self=False):
    d = ((x[:, None, :] - base[None]) ** 2).sum(-1)
    if skip_self:
        d[np.arange(len(x)), np.arange(len(x))] = np.inf
    return np.sqrt(d.min(1))


def test_queries_come_from_the_base_mixture():
    """A held-out query lies as close to the base as a base point does to
    the rest of the base; a query from a centre the base lacks does not."""
    dat = data_lib.make(SPEC, 3, 10)
    q_nn = np.median(_nn_dist(dat.queries, dat.base))
    b_nn = np.median(_nn_dist(dat.base[:256], dat.base, skip_self=True))
    assert 0.8 < q_nn / b_nn < 1.25
    rng = np.random.default_rng(0)
    stray = 1.5 * rng.standard_normal((64, SPEC["d"])).astype(np.float32)
    assert np.median(_nn_dist(stray, dat.base)) > 1.5 * b_nn


def test_exact_knn_matches_float64_brute_force():
    dat = data_lib.make(SPEC, 5, 10)
    b = dat.base.astype(np.float64)
    q = dat.queries.astype(np.float64)
    d = ((q[:, None, :] - b[None]) ** 2).sum(-1)
    want = np.sort(np.take_along_axis(d, np.argsort(d, 1)[:, :10], 1), 1)
    got = np.sort(np.take_along_axis(d, dat.gt.astype(np.int64), 1), 1)
    np.testing.assert_allclose(got, want, rtol=1e-6)


#: an angular block with uneven clusters: n not a multiple of clusters
ANGULAR = dict(SPEC, n=2050, n_query=512, metric="angular")


def test_angular_rows_have_unit_norm():
    dat = data_lib.make(ANGULAR, 2**31 + 9, 10)
    assert dat.base.shape == (2050, 16) and dat.base.dtype == np.float32
    for x in (dat.base, dat.queries):
        np.testing.assert_allclose(np.linalg.norm(x, axis=1), 1.0,
                                   atol=4e-7)


def test_angular_queries_come_from_the_base_mixture():
    """Held-out angular queries lie as close to the base as base points do,
    and fall in each cluster about as often as the base does."""
    dat = data_lib.make(ANGULAR, 3, 10)
    q_nn = np.median(_nn_dist(dat.queries, dat.base))
    b_nn = np.median(_nn_dist(dat.base[:256], dat.base, skip_self=True))
    assert 0.8 < q_nn / b_nn < 1.25
    sizes = data_lib.cluster_sizes(2050, 4)
    label = np.repeat(np.arange(4), sizes)
    d = ((dat.queries[:, None, :] - dat.base[None]) ** 2).sum(-1)
    share = np.bincount(label[d.argmin(1)], minlength=4) / len(d)
    np.testing.assert_allclose(share, sizes / sizes.sum(), atol=0.07)


def test_angular_ground_truth_is_a_float64_cosine_brute_force():
    dat = data_lib.make(ANGULAR, 5, 10)
    b = dat.base.astype(np.float64)
    q = dat.queries.astype(np.float64)
    cos = 1.0 - (q @ b.T) / np.outer(np.linalg.norm(q, axis=1),
                                     np.linalg.norm(b, axis=1))
    want = np.sort(np.take_along_axis(cos, np.argsort(cos, 1)[:, :10], 1), 1)
    got = np.sort(np.take_along_axis(cos, dat.gt.astype(np.int64), 1), 1)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


@pytest.mark.parametrize("n,clusters", [
    (1_183_514, 64), (2050, 4), (1000, 7), (10, 3), (1_000_000, 16),
    (97, 5)])
def test_uneven_sizes_sum_to_n(n, clusters):
    sizes = data_lib.cluster_sizes(n, clusters)
    assert sizes.sum() == n and len(sizes) == clusters
    assert np.all(np.diff(sizes) <= 0) and sizes[0] - sizes[-1] <= 1
    assert np.count_nonzero(sizes > n // clusters) == n % clusters


@pytest.mark.parametrize("metric", ["cosine", "ip", "L2"])
def test_unknown_data_metric_raises(metric):
    with pytest.raises(ValueError, match=repr(metric)):
        data_lib.make(dict(SPEC, metric=metric), 1, 10)


#: SHA-256 of SPEC's base, queries and ground truth at seed 2**31 + 7, as
#: the generator made them before it learned angular blocks and uneven
#: clusters (an l2 block with n a multiple of clusters runs the same
#: program)
PINNED = {
    "base": "e15c72139f02f4ebdaf016e745aa8e4ffd425902d00cf9831fa896aaaf2d0cf1",
    "queries":
        "70c61ee43b8f8e16fc5a00cddff37ce4ed6e6bbdcc6204cd58f4b58697af94e0",
    "gt": "a997d6327e34e4dc1dee1f536081ef3b78fda6080af361506c5df0592e8cedaf",
}


def test_l2_bytes_are_pinned():
    dat = data_lib.make(SPEC, 2**31 + 7, 10)
    got = {name: hashlib.sha256(getattr(dat, name).tobytes()).hexdigest()
           for name in PINNED}
    assert got == PINNED
