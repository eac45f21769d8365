import numpy as np

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
