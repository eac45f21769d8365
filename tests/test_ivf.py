"""IVF subsystem tests: k-means trainer (Pallas-kernel assignment vs the
numpy reference, empty-cell reseeding, determinism), cell-major layout
invariants, the ``"ivf"`` backend's exact-anchor agreement at max nprobe
on a >=10k-vector set, checkpoint shipping, and the backend-choice GRPO
wiring."""
import dataclasses
import re

import numpy as np
import pytest

from repro.anns import SearchParams, make_dataset, registry
from repro.anns.api import AnnsIndex
from repro.anns.backends.ivf import NPROBE_LADDER, round_nprobe
from repro.anns.datasets import recall_at_k
from repro.anns.engine import GLASS_BASELINE, IVF_BASELINE
from repro.anns.ivf import (assign, assign_ref, build_ivf, ivf_stats,
                            kmeans_fit, kmeans_ref, lloyd_step)


@pytest.fixture(scope="module")
def blobs():
    rng = np.random.default_rng(7)
    centers = rng.standard_normal((12, 48)).astype(np.float32) * 3.0
    x = (centers[rng.integers(0, 12, size=3000)]
         + rng.standard_normal((3000, 48)).astype(np.float32))
    return x.astype(np.float32)


@pytest.fixture(scope="module")
def big_ds():
    # acceptance scale: >= 10k base vectors
    return make_dataset("sift-128-euclidean", n_base=10_000, n_query=32)


@pytest.fixture(scope="module")
def ivf_backend(big_ds):
    b = registry.create(
        "ivf", dataclasses.replace(IVF_BASELINE, nlist=64, kmeans_iters=6),
        metric=big_ds.metric)
    b.build(big_ds.base)
    return b


@pytest.fixture(scope="module")
def exact_anchor(big_ds):
    b = registry.create("brute_force", metric=big_ds.metric)
    b.build(big_ds.base)
    return b.search(big_ds.queries, SearchParams(k=10))


# ---------------------------------------------------------------------------
# k-means trainer
# ---------------------------------------------------------------------------

def test_assignment_parity_kernel_vs_numpy(blobs):
    """Pallas-kernel assignment must match the numpy oracle; any
    disagreement must be a genuine distance near-tie, not a bug."""
    rng = np.random.default_rng(0)
    centroids = blobs[rng.choice(len(blobs), 32, replace=False)]
    a_k, d_k = assign(blobs, centroids, metric="l2")
    a_r, d_r = assign_ref(blobs, centroids, metric="l2")
    agree = a_k == a_r
    assert agree.mean() >= 0.995, agree.mean()
    if not agree.all():
        np.testing.assert_allclose(d_k[~agree], d_r[~agree],
                                   rtol=1e-3, atol=1e-2)
    np.testing.assert_allclose(d_k[agree], d_r[agree], rtol=1e-4, atol=1e-2)


def test_kmeans_reduces_inertia_and_matches_ref(blobs):
    """Full-batch Lloyd's must monotonically improve; the kernel-assigned
    trainer and the numpy twin follow the same trajectory."""
    cent_k = kmeans_fit(blobs, 16, iters=5, seed=3)
    cent_r = kmeans_ref(blobs, 16, iters=5, seed=3)
    # same RNG stream + same update arithmetic => near-identical centroids
    np.testing.assert_allclose(cent_k, cent_r, rtol=1e-3, atol=1e-3)
    _, d0 = assign_ref(blobs, blobs[:16], metric="l2")
    _, d1 = assign_ref(blobs, cent_k, metric="l2")
    assert d1.mean() < d0.mean()


def test_kmeans_deterministic_under_fixed_key(blobs):
    a = kmeans_fit(blobs, 24, iters=4, seed=11)
    b = kmeans_fit(blobs, 24, iters=4, seed=11)
    np.testing.assert_array_equal(a, b)
    c = kmeans_fit(blobs, 24, iters=4, seed=12)
    assert not np.array_equal(a, c)


def test_empty_cell_reseeding(blobs):
    """A centroid stranded far from all data attracts zero points; one
    Lloyd's step must reseed it onto a real (farthest) data point."""
    centroids = np.concatenate(
        [blobs[:7], np.full((1, blobs.shape[1]), 1e4, np.float32)])
    counts = np.zeros(8, np.int64)
    info = lloyd_step(blobs[:500], centroids, counts, full_batch=True)
    assert info["n_reseeded"] >= 1
    assert info["batch_counts"][7] == 0          # it was empty this step
    # the reseeded centroid is now an actual batch point, not the outlier
    match = (centroids[7][None, :] == blobs[:500]).all(axis=1)
    assert match.any()


def test_kmeans_clamps_nlist_to_n(blobs):
    cent = kmeans_fit(blobs[:5], 64, iters=2, seed=0)
    assert cent.shape == (5, blobs.shape[1])


# ---------------------------------------------------------------------------
# cell-major layout
# ---------------------------------------------------------------------------

def test_layout_invariants(blobs):
    idx = build_ivf(blobs, nlist=32, kmeans_iters=3, metric="l2", seed=0)
    offsets = idx.offsets
    assert offsets[0] == 0 and offsets[-1] == len(blobs)
    assert (np.diff(offsets) >= 0).all()
    ids = np.asarray(idx.ids)
    assert sorted(ids.tolist()) == list(range(len(blobs)))   # permutation
    # cell-major blocks really hold the remapped vectors
    np.testing.assert_array_equal(np.asarray(idx.base), blobs[ids])
    # padded rows agree with the CSR offsets
    cells = np.asarray(idx.cells)
    for c in range(idx.nlist):
        size = int(offsets[c + 1] - offsets[c])
        np.testing.assert_array_equal(
            cells[c, :size], np.arange(offsets[c], offsets[c + 1]))
        assert (cells[c, size:] == -1).all()
    # every member's nearest centroid is its own cell
    a, _ = assign_ref(blobs, np.asarray(idx.centroids), metric="l2")
    for c in range(idx.nlist):
        members = ids[int(offsets[c]): int(offsets[c + 1])]
        assert (a[members] == c).all()
    stats = ivf_stats(idx)
    assert stats["n"] == len(blobs) and stats["nlist"] == 32


@pytest.mark.parametrize("d,want", [
    # chunk 256 rows: the 257-row cell reads two chunks, the empty none
    (960, (256 + 512 + 0 + 256) / 4 / 264),
    # one 384-row chunk (the pad rounded up to a lane tile) per live cell
    (128, (384 + 384 + 0 + 384) / 4 / 264),
])
def test_ivf_stats_scan_read_share(d, want):
    from repro.anns.ivf.layout import layout_from_assignments
    sizes = np.array([256, 257, 0, 10])
    a = np.repeat(np.arange(4), sizes)
    x = np.random.default_rng(0).standard_normal((len(a), d))
    idx = layout_from_assignments(x, a, np.zeros((4, d), np.float32),
                                  metric="l2")
    assert idx.cell_pad == 264
    assert ivf_stats(idx)["scan_read_share"] == pytest.approx(want)


def test_small_probed_block_still_returns_k(blobs):
    """Regression: nprobe=1 over tiny cells used to hand fp32_rerank a
    shortlist narrower than k (top_k ValueError).  The backend must widen
    the probe until the block holds k candidates."""
    v = dataclasses.replace(IVF_BASELINE, nlist=64, nprobe=1,
                            kmeans_iters=2)
    b = registry.create("ivf", v)
    b.build(blobs[:64])              # nlist == n -> singleton cells
    res = b.search(blobs[:4], SearchParams(k=10, ef=64))
    assert res.ids.shape == (4, 10)
    assert len(set(np.asarray(res.ids)[0].tolist())) == 10   # no dup fill


def test_pad_slots_never_displace_real_neighbors(blobs):
    """Regression: pad entries surviving into the rerank shortlist used to
    be re-scored as the *real* vector at cell-major position 0, flooding
    the answer with duplicates of one id.  The validity mask must travel
    through the rerank."""
    v = dataclasses.replace(IVF_BASELINE, nlist=16, nprobe=1,
                            kmeans_iters=2, rerank_factor=8)
    b = registry.create("ivf", v)
    b.build(blobs[:64])
    # low ef keeps nprobe at its floor; wide rerank_factor makes the
    # shortlist far larger than any probed cell
    res = b.search(blobs[:8], SearchParams(k=10, ef=4))
    ids = np.asarray(res.ids)
    for row in ids:
        assert len(set(row.tolist())) == 10, row      # k distinct ids


@pytest.mark.parametrize("quantized", [True, False], ids=["int8", "fp32"])
def test_every_search_op_runs_under_one_stage_scope(blobs, quantized):
    """Each op of the lowered search carries exactly one of the stage
    scopes (``ivf.coarse``/``scan``/``cut``/``rerank``) in its
    ``op_name``, so a device trace can time every stage apart.  Only
    parameters, constants (and ops made of constants alone) and the
    result tuple are exempt."""
    v = dataclasses.replace(IVF_BASELINE, nlist=8, nprobe=2, kmeans_iters=2)
    b = registry.create("ivf", v)
    b.build(blobs[:600])
    lowered = b.lower_search(blobs[:4], SearchParams(k=5, quantized=quantized))
    text = lowered.compiler_ir("hlo").as_hlo_module().to_string()
    entry = text[text.index("\nENTRY"):]
    entry = entry[entry.index("{\n") + 2:entry.index("\n}")]
    constant, checked = set(), 0
    for line in entry.splitlines():
        name, rhs = line.strip().removeprefix("ROOT ").split(" = ", 1)
        kind = re.search(r"\s([a-z][a-z-]*)\(", " " + rhs).group(1)
        args = rhs[rhs.index(kind + "(") + len(kind) + 1:].split(")")[0]
        operands = re.findall(r"%[\w.-]+", args)
        if kind == "constant" or (operands and set(operands) <= constant):
            constant.add(name)
            continue
        if kind in ("parameter", "tuple"):
            continue
        scopes = [part for op_name in re.findall(r'op_name="([^"]*)"', line)
                  for part in op_name.split("/") if part.startswith("ivf.")]
        assert len(scopes) == 1, line
        checked += 1
    assert checked > 20


def test_nprobe_ladder_monotone():
    prev = 0
    for p in range(1, 300):
        r = round_nprobe(p)
        assert r >= p and r >= prev
        prev = r
    for rung in NPROBE_LADDER:
        assert round_nprobe(rung) == rung


# ---------------------------------------------------------------------------
# "ivf" backend: protocol + exact-anchor agreement
# ---------------------------------------------------------------------------

def test_ivf_satisfies_protocol(ivf_backend):
    assert isinstance(ivf_backend, AnnsIndex)
    assert ivf_backend.memory_bytes() > 0


def test_ivf_matches_brute_force_at_max_nprobe(big_ds, ivf_backend,
                                               exact_anchor):
    """nprobe == nlist scans every cell: the cell-major scan + fp32
    rerank must reproduce the exact anchor at recall >= 0.99 (int8
    quantization is the only remaining approximation)."""
    # ef scaled so the ladder-mapped nprobe saturates at nlist
    ef_max = 64 * ivf_backend.index.nlist
    res = ivf_backend.search(big_ds.queries,
                             SearchParams(k=10, ef=ef_max, rerank_factor=4))
    rec = recall_at_k(np.asarray(res.ids), np.asarray(exact_anchor.ids), 10)
    assert rec >= 0.99, rec
    d = np.asarray(res.dists)
    assert (np.diff(d, axis=1) >= -1e-5).all()   # fp32 rerank: ascending


def test_ivf_recall_grows_with_nprobe(big_ds, ivf_backend, exact_anchor):
    recs = []
    for ef in (16, 64, 512):
        res = ivf_backend.search(big_ds.queries, SearchParams(k=10, ef=ef))
        recs.append(recall_at_k(np.asarray(res.ids),
                                np.asarray(exact_anchor.ids), 10))
    # wider probes scan candidate supersets: recall trends up (small
    # slack absorbs int8-shortlist noise between adjacent rungs)
    assert recs[1] >= recs[0] - 0.02 and recs[2] >= recs[1] - 0.02, recs
    assert recs[2] > recs[0], recs
    assert recs[2] >= 0.9, recs


def test_ivf_fp32_scan_override(big_ds, ivf_backend, exact_anchor):
    """quantized=False must bypass the int8 codes (exact fp32 cell scans:
    with all cells probed the result is exactly the anchor)."""
    ef_max = 64 * ivf_backend.index.nlist
    res = ivf_backend.search(
        big_ds.queries,
        SearchParams(k=10, ef=ef_max, quantized=False, rerank_factor=4))
    rec = recall_at_k(np.asarray(res.ids), np.asarray(exact_anchor.ids), 10)
    assert rec >= 0.99, rec


def test_ivf_state_dict_and_ckpt_roundtrip(big_ds, ivf_backend, tmp_path):
    """to_state_dict -> repro.ckpt -> from_state_dict on a fresh host
    object serves identical results (the ship-without-rebuild path)."""
    from repro import ckpt
    path = str(tmp_path / "ivf_index.ckpt")
    ckpt.save_index(path, ivf_backend)
    clone = ckpt.load_index(path, variant=ivf_backend.variant)
    assert clone.name == "ivf"
    p = SearchParams(k=10, ef=64)
    a = ivf_backend.search(big_ds.queries, p)
    b = clone.search(big_ds.queries, p)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_allclose(np.asarray(a.dists), np.asarray(b.dists),
                               rtol=1e-6)
    assert clone.memory_bytes() == ivf_backend.memory_bytes()


def test_ivf_served_through_anns_server(big_ds, ivf_backend):
    from repro.runtime.server import AnnsServer
    srv = AnnsServer(ivf_backend, max_batch=8,
                     params=SearchParams(k=10, ef=128))
    for i in range(5):
        srv.submit(big_ds.queries[i], k=5 if i % 2 else 10)
    out = srv.run()
    assert [len(r.ids) for r in out] == [10, 5, 10, 5, 10]
    direct = ivf_backend.search(big_ds.queries[:1],
                                SearchParams(k=10, ef=128))
    np.testing.assert_array_equal(out[0].ids, np.asarray(direct.ids)[0])


# ---------------------------------------------------------------------------
# GRPO action-space wiring
# ---------------------------------------------------------------------------

def test_backend_module_in_grammar():
    from repro.core import prompting
    from repro.core.variant_space import (BACKEND_CHOICES, MODULES,
                                          Program, program_from_variant)
    assert "ivf" in BACKEND_CHOICES
    assert "backend" in MODULES and "ivf" in MODULES
    # token round-trip for every backend choice
    for i, name in enumerate(BACKEND_CHOICES):
        prog = Program("backend", (i,))
        toks = prompting.program_tokens(prog)
        assert prompting.decode_program("backend", toks) == prog
        assert prog.apply_to(GLASS_BASELINE).backend == name
    # inverse mapping from the running variant
    assert program_from_variant("backend", GLASS_BASELINE).choices == (0,)
    assert program_from_variant("ivf", IVF_BASELINE).knobs()["nlist"] == 64


def test_grpo_smoke_backend_choice_token():
    """End-to-end GRPO smoke over the 'backend' module: the policy
    samples a backend-choice token, it decodes to a variant, the variant
    is evaluated against its family baseline, and the policy updates —
    without error (acceptance criterion for the family action axis)."""
    import dataclasses as dc

    import jax

    from repro.configs import get_config
    from repro.core import CrinnOptimizer, LoopConfig, Policy
    from repro.core.prompting import VOCAB_SIZE
    from repro.models import Runtime, model

    cfg = dc.replace(get_config("crinn-policy-100m"), num_layers=1,
                     d_model=64, num_heads=2, num_kv_heads=2, head_dim=32,
                     d_ff=128, dtype="float32")
    assert cfg.padded_vocab >= VOCAB_SIZE
    rt = Runtime(mesh=None, attn_chunk=64, logit_chunk=64, remat="none")
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    policy = Policy(cfg, params, rt)
    ds = make_dataset("glove-25-angular", n_base=1200, n_query=48)
    loop = LoopConfig(group_size=2, iterations_per_module=1,
                      ef_sweep=(16, 32, 64), bench_repeats=1, seed=1)
    opt = CrinnOptimizer(policy, ds, loop)
    variant = opt.run_module("backend", verbose=False)
    assert opt.baselines.has(variant.backend)
    assert opt.db.size("backend") >= 1
    assert len(opt.history) == 1
    assert all(np.isfinite(opt.history[0].rewards))
