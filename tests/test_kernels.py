"""Per-kernel shape/dtype sweeps against the pure-jnp ref oracles
(interpret mode executes the Pallas kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.anns import search as search_lib
from repro.anns.ivf.layout import layout_from_assignments
from repro.kernels.cellscan.ops import cell_blocks, cell_scan, chunk_rows
from repro.kernels.cellscan.ref import cell_blocks_ref, cell_scan_ref
from repro.kernels.distance.ops import pairwise_distance
from repro.kernels.distance.ref import distance_ref
from repro.kernels.flash.ops import causal_attention
from repro.kernels.flash.ref import flash_ref
from repro.kernels.qdist.ops import quantize_int8, quantized_distance
from repro.kernels.qdist.ref import qdist_ref
from repro.kernels.topk.ops import topk_smallest
from repro.kernels.topk.ref import topk_smallest_ref

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# distance
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("nq,nx,d", [
    (128, 256, 128), (100, 300, 96), (8, 1000, 25), (256, 512, 960),
    (1, 128, 784), (17, 33, 100),
])
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_distance_matches_ref(nq, nx, d, metric):
    q = jax.random.normal(KEY, (nq, d), jnp.float32)
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (nx, d), jnp.float32)
    got = pairwise_distance(q, x, metric=metric)
    want = distance_ref(q, x, metric)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_distance_dtypes(dtype):
    q = jax.random.normal(KEY, (64, 128), dtype)
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (128, 128), dtype)
    got = pairwise_distance(q, x, metric="l2")
    want = distance_ref(q, x, "l2")
    tol = 1e-3 if dtype == jnp.float32 else 2.0
    np.testing.assert_allclose(got, want, rtol=1e-2, atol=tol)


def test_distance_l2_nonnegative_and_zero_diag():
    x = jax.random.normal(KEY, (64, 32), jnp.float32)
    d = pairwise_distance(x, x, metric="l2")
    assert float(jnp.min(d)) > -1e-3
    np.testing.assert_allclose(np.diag(np.asarray(d)), 0.0, atol=1e-3)


# ---------------------------------------------------------------------------
# topk
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("nq,nx,k", [
    (8, 128, 10), (5, 1000, 32), (16, 333, 100), (1, 50, 5), (9, 2048, 64),
])
def test_topk_matches_ref(nq, nx, k):
    d = jax.random.normal(KEY, (nq, nx), jnp.float32)
    v1, i1 = topk_smallest(d, k)
    v2, i2 = topk_smallest_ref(d, k)
    np.testing.assert_allclose(v1, v2, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_topk_sorted_ascending():
    d = jax.random.normal(KEY, (8, 256), jnp.float32)
    v, _ = topk_smallest(d, 16)
    v = np.asarray(v)
    assert (np.diff(v, axis=1) >= -1e-7).all()


def test_topk_with_ties():
    d = jnp.zeros((8, 64), jnp.float32).at[:, 10].set(-1.0)
    v, i = topk_smallest(d, 3)
    assert (np.asarray(i[:, 0]) == 10).all()
    # remaining picks are the lowest indices among ties (stable)
    assert (np.asarray(i[:, 1]) == 0).all()


# ---------------------------------------------------------------------------
# qdist
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("nq,nx,d", [(16, 256, 128), (7, 300, 25),
                                     (64, 128, 960)])
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_qdist_matches_ref(nq, nx, d, metric):
    q = jax.random.normal(KEY, (nq, d), jnp.float32)
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (nx, d), jnp.float32)
    xq, s = quantize_int8(x)
    got = quantized_distance(q, xq, s, metric=metric)
    want = qdist_ref(q, xq, s, metric)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-3)


def test_quantization_error_bounded():
    x = jax.random.normal(KEY, (128, 64), jnp.float32) * 3.0
    xq, s = quantize_int8(x)
    err = np.abs(np.asarray(xq, np.float32) * np.asarray(s)[:, None]
                 - np.asarray(x))
    # per-vector max error <= scale/2 (round-to-nearest)
    assert (err <= np.asarray(s)[:, None] * 0.5 + 1e-6).all()


def test_qdist_close_to_exact_distance():
    q = jax.random.normal(KEY, (8, 128), jnp.float32)
    x = jax.random.normal(jax.random.fold_in(KEY, 2), (64, 128), jnp.float32)
    xq, s = quantize_int8(x)
    approx = quantized_distance(q, xq, s, metric="l2")
    exact = distance_ref(q, x, "l2")
    rel = np.abs(np.asarray(approx) - np.asarray(exact)) / np.asarray(exact)
    assert float(np.median(rel)) < 0.02


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,Hq,Hk,D,win,cap", [
    (2, 256, 4, 2, 64, 0, 0.0),
    (1, 256, 8, 8, 128, 0, 50.0),
    (2, 256, 4, 1, 80, 128, 0.0),
    (1, 512, 2, 2, 64, 0, 0.0),
    (1, 128, 16, 4, 128, 64, 30.0),
])
def test_flash_matches_ref(B, S, Hq, Hk, D, win, cap):
    q = jax.random.normal(KEY, (B, S, Hq, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, Hk, D), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, Hk, D), jnp.float32)
    got = causal_attention(q, k, v, q_scale=D ** -0.5, window=win, softcap=cap)
    want = flash_ref(q, k, v, q_scale=D ** -0.5, window=win, softcap=cap)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_flash_causality():
    """Changing future kv must not change past outputs."""
    B, S, H, D = 1, 256, 2, 64
    q = jax.random.normal(KEY, (B, S, H, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, H, D), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, H, D), jnp.float32)
    o1 = causal_attention(q, k, v, q_scale=D ** -0.5)
    k2 = k.at[:, S // 2:].set(0.0)
    v2 = v.at[:, S // 2:].set(9.0)
    o2 = causal_attention(q, k2, v2, q_scale=D ** -0.5)
    np.testing.assert_allclose(o1[:, : S // 2], o2[:, : S // 2],
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# cell scan
# ---------------------------------------------------------------------------
BIG = search_lib.BIG


def _cell_layout(d: int, base=None):
    """Five cells: exactly one chunk, one row past a chunk, empty, 3 rows,
    half a chunk (one chunk wider than the pad when the chunk covers it)."""
    c = chunk_rows(d, 2048)
    sizes = np.array([c, c + 1, 0, 3, c // 2 + 5])
    a = np.repeat(np.arange(len(sizes)), sizes)
    if base is None:
        base = np.asarray(jax.random.normal(KEY, (len(a), d), jnp.float32))
    idx = layout_from_assignments(base, a, np.zeros((len(sizes), d),
                                                    np.float32), metric="l2")
    return idx, cell_blocks(idx.base_q, idx.scales, idx.offsets,
                            idx.cell_pad)


CELL_PROBES = {
    "one_chunk": [[0, 3, 4], [0, 0, 3]],
    "chunk_plus_one": [[1, 0, 4], [1, 3, 1]],
    "empty_cell": [[2, 0, 1], [3, 2, 4]],
    "all_pad": [[2, 2, 2], [2, 2, 2]],
    "fmask": [[0, 1, 4], [1, 3, 4]],
}


@pytest.mark.parametrize("case", list(CELL_PROBES))
@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("d", [16, 128, 960])
def test_cell_scan_matches_ref_and_gather(d, metric, case):
    """The kernel against its oracle and against the per-row gather it
    replaced (``_ivf_search``'s int8 scan before the kernel), on the
    slots the search keeps; BIG wherever the kernel reads no chunk."""
    idx, blocks = _cell_layout(d)
    probe = jnp.asarray(CELL_PROBES[case], jnp.int32)
    q = jax.random.normal(jax.random.fold_in(KEY, d), (2, d), jnp.float32)
    pad = idx.cell_pad
    got = cell_scan(q, blocks, probe, pad=pad, metric=metric)
    ref = cell_scan_ref(q, blocks, probe, pad=pad, metric=metric)

    cand = idx.cells[probe].reshape(2, -1)
    valid = cand >= 0
    pos = jnp.where(valid, cand, 0)
    if case == "fmask":
        fmask = jax.random.bernoulli(jax.random.fold_in(KEY, 5), 0.5,
                                     (idx.n,))
        valid = valid & fmask[pos]
    vecs = idx.base_q[pos].astype(jnp.float32) * idx.scales[pos][..., None]
    gather = search_lib._qdist(q, vecs, metric, quantized=True)
    # rtol 1e-6 of the summed terms' size: an ip dot or an l2 difference
    # near 0 keeps the rounding of its terms
    qn = jnp.linalg.norm(q, axis=1)[:, None]
    vn = jnp.linalg.norm(vecs, axis=-1)
    size = qn * vn if metric == "ip" else (qn + vn) ** 2
    keep = np.asarray(valid)
    for want in (ref, gather):
        err = np.abs(np.asarray(got - want))[keep]
        assert (err <= 1e-6 * np.asarray(size)[keep]).all(), err.max()
    dead = np.asarray(ref) >= BIG
    assert (np.asarray(got)[dead] == BIG).all()
    assert np.isfinite(np.asarray(got)).all()
    if case == "all_pad":
        assert dead.all() and not keep.any()


def test_cell_scan_slots_map_onto_the_cells_table():
    """Slot ``j·pad + r`` of the kernel's row is row ``r`` of the
    ``j``-th probed cell: the same slot ``cells[probe].reshape(B, -1)``
    names.  Each row is the unit vector with scale = its position + 1,
    so an ip scan against e_0 reads the position back exactly."""
    d = 960
    idx, _ = _cell_layout(d)
    base_q = jnp.zeros((idx.n, d), jnp.int8).at[:, 0].set(1)
    scales = jnp.arange(1, idx.n + 1, dtype=jnp.float32)
    blocks = cell_blocks(base_q, scales, idx.offsets, idx.cell_pad)
    probe = jnp.asarray([[4, 1, 2, 0], [3, 3, 1, 4]], jnp.int32)
    q = jnp.zeros((2, d), jnp.float32).at[:, 0].set(1.0)
    got = np.asarray(cell_scan(q, blocks, probe, pad=idx.cell_pad,
                               metric="ip"))
    cand = np.asarray(idx.cells[probe].reshape(2, -1))
    assert got.shape == cand.shape
    np.testing.assert_array_equal(-got[cand >= 0] - 1, cand[cand >= 0])
    # every other slot reads a zero row of a live chunk or no chunk at all
    assert np.isin(got[cand < 0], np.float32([0.0, BIG])).all()


@pytest.mark.parametrize("sizes", [
    "edges",                    # one chunk, a row past one, empty, 3, half
    [0, 0, 7, 300, 129, 0],     # empty cells first and last
    [5, 0],
])
@pytest.mark.parametrize("d", [16, 128, 960])
def test_cell_blocks_match_the_row_gather(d, sizes):
    """The alignment kernel lays out exactly the blocks a gather of the
    rows gives: every cell from a chunk boundary, zeros past its end."""
    if sizes == "edges":
        c = chunk_rows(d, 2048)
        sizes = [c, c + 1, 0, 3, c // 2 + 5]
    a = np.repeat(np.arange(len(sizes)), sizes)
    base = np.asarray(jax.random.normal(KEY, (len(a), d), jnp.float32))
    idx = layout_from_assignments(base, a, np.zeros((len(sizes), d),
                                                    np.float32), metric="l2")
    got = cell_blocks(idx.base_q, idx.scales, idx.offsets, idx.cell_pad)
    want = cell_blocks_ref(idx.base_q, idx.scales, idx.offsets,
                           idx.cell_pad)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
