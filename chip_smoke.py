"""Chip smoke: the serving path, brought up on a TPU through its normal
entry points (``registry.create`` -> ``build`` -> ``AsyncServeTier``).

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the sharded phase on four chips

One chip runs two phases:

1. **differential** at small N: every registered backend at the top rung
   of its effort ladder must return the brute-force anchor's ids
   exactly, unfiltered and under a 10% predicate; the anchor must match
   the dataset's ground truth and a float64 host-numpy reference.
2. **SIFT-1M**: ANN-Benchmarks' SIFT-128-euclidean deployment shape
   (10^6 base vectors, 128-d, l2), generated from a seed, served by the
   ``ivf`` backend at one ``NPROBE_LADDER`` rung through
   ``AsyncServeTier`` at ``max_batch`` 64.  It fails unless the
   brute-force anchor reaches recall@10 >= 0.999, ``ivf`` stays within
   0.02 of the recall a float64 host-numpy reference of the same search
   (same build, same rung) reaches on a subset of the queries, the
   served program holds Pallas kernels compiled for the chip
   (``tpu_custom_call``), and the tier's accounting invariant holds.

``--four-chips`` runs only the sharded phase: ``sharded`` with
``n_shards=4`` placed on a ``("shard",)`` mesh of four devices, compared
with ``ivf`` on the same build — equal ids at the all-cells probe on a
mid-size build, recall@10 within 0.01 of ``ivf``'s on the 1M build.

Every time and rate printed is a one-off smoke reading, not a benchmark.
The script refuses to run without a TPU, and any failed check exits
non-zero.  The last line of stdout is the JSON verdict
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

from repro.anns import SearchParams, make_dataset, registry  # noqa: E402
from repro.anns.api import search_ef_ladder  # noqa: E402
from repro.anns.datasets import (exact_ground_truth, recall_at_k,  # noqa: E402
                                 selectivity_filter)
from repro.anns.engine import IVF_BASELINE, family_baseline  # noqa: E402

DATASET = "sift-128-euclidean"
K = 10
MAX_BATCH = 64
SEED = 0
#: the graph family's differential runs on ``tests/test_differential.py``'s
#: own l2 dataset: its top rung (ef=512) covers every node only when
#: n <= 512, and only nodes reachable from the entry points are visited
GRAPH_DIFF = dict(n_base=240, n_query=16, seed=3)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Scale of one smoke run.  Widths are the deployment's; only these
    counts change between the chip run and a CPU rehearsal."""
    diff_n: int = 20_000         # differential, exhaustive-ladder backends
    diff_queries: int = 64
    n_base: int = 1_000_000      # SIFT-128-euclidean base
    n_query: int = 1_000
    nlist: int = 1024            # ~977 vectors per cell
    mid_n: int = 100_000         # four-chip all-cells comparison
    mid_nlist: int = 64


#: the served operating point: ``ef=64`` probes the variant's ``nprobe``
NPROBE = 16
#: balanced-assignment cap: bounds ``cell_pad``, hence the (B, nprobe*pad)
#: scan gather, at ~2x the mean cell
MAX_CELL = 2048
#: mini-batch Lloyd's samples 4096 vectors per iteration: 64 iterations
#: see ~256 per centroid at nlist=1024
KMEANS_ITERS = 64
#: queries the host reference re-runs (float64 numpy is slow at 10^6)
REF_QUERIES = 256
RECALL_SLACK = 0.02
SHARDED_RECALL_SLACK = 0.01


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


class Clock:
    """Named phase timer; every reading is a one-off smoke reading."""

    def __init__(self):
        self.t = {}

    def __call__(self, name: str, fn, *args, **kwargs):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        self.t[name] = time.perf_counter() - t0
        say(f"{name} {self.t[name]:.3f} s (smoke reading)")
        return out


def ivf_variant(nlist: int, backend: str = "ivf", **kw):
    return dataclasses.replace(IVF_BASELINE, backend=backend, nlist=nlist,
                               nprobe=NPROBE, max_cell=MAX_CELL,
                               kmeans_iters=KMEANS_ITERS, **kw)


def sift_dataset(n_base: int, n_query: int, clock: Clock):
    """Base and queries, then the exact ground truth, timed apart."""
    ds = clock("data", make_dataset, DATASET, n_base=n_base,
               n_query=n_query, k_gt=0, seed=SEED)
    ds.gt = clock("ground_truth", exact_ground_truth, ds.base, ds.queries,
                  K, ds.metric)
    ds.k_gt = K
    return ds


def top_rung_ids(backend, queries, predicate=None) -> np.ndarray:
    """Row-sorted ids at the backend's top ladder rung, fp32 scan."""
    ef = search_ef_ladder(backend)[-1]
    res = backend.search(queries, SearchParams(k=K, ef=ef, quantized=False,
                                               filter=predicate))
    return np.sort(np.asarray(res.ids), axis=1)


def _diff_variant(name):
    v = dataclasses.replace(family_baseline(name), backend=name)
    if name in ("ivf", "sharded", "stream_ivf", "stream_sharded"):
        v = dataclasses.replace(v, nlist=32, kmeans_iters=4)
    if name in ("sharded", "stream_sharded"):
        v = dataclasses.replace(v, n_shards=2)
    return v


def _graph_family(name) -> bool:
    """Brute force and the IVF family probe every cell at the top of
    their ladder at any N; the graph family does not (see GRAPH_DIFF)."""
    return name in ("graph", "quantized_prefilter")


def anchor_matches_float64(anchor, ds, n_rows: int = 16) -> None:
    """The anchor's ids and distances against a float64 host-numpy
    reference: returned distances within 1e-5 of the norm scale (a single
    bf16 pass would be off by ~1e-3), and the returned set an exact
    top-k up to float32 ties."""
    q = ds.queries[:n_rows].astype(np.float64)
    b = ds.base.astype(np.float64)
    qn = (q * q).sum(1)[:, None]
    bn = (b * b).sum(1)[None, :]
    d64 = qn + bn - 2.0 * q @ b.T
    res = anchor.search(ds.queries[:n_rows], SearchParams(k=K))
    ids, dists = np.asarray(res.ids), np.asarray(res.dists)
    rows = np.arange(n_rows)[:, None]
    scale = qn + bn[0, ids]
    err = np.abs(dists - d64[rows, ids]) / scale
    say(f"anchor vs float64: max distance error {err.max():.3e} of the "
        f"norm scale")
    check(err.max() < 1e-5, f"anchor distances off float64 by "
          f"{err.max():.3e} of the norm scale")
    want = np.sort(d64, axis=1)[:, :K]
    got = np.sort(d64[rows, ids], axis=1)
    gap = np.abs(got - want) / (qn + want)
    check(gap.max() < 1e-6, f"anchor top-{K} is not the float64 top-{K} "
          f"(worst gap {gap.max():.3e})")


def phase_differential(sizes: Sizes) -> None:
    """Every registered backend at its top rung == the brute-force anchor."""
    names = registry.available()
    groups = [
        (dict(n_base=sizes.diff_n, n_query=sizes.diff_queries, seed=SEED),
         [n for n in names if not _graph_family(n)]),
        (GRAPH_DIFF, [n for n in names if _graph_family(n)])]
    for data, names in groups:
        n, seed = data["n_base"], data["seed"]
        ds = make_dataset(DATASET, k_gt=K, **data)
        pred = selectivity_filter(ds, 0.1)
        anchor = registry.create("brute_force", metric=ds.metric, seed=seed)
        anchor.build(ds.base)
        anchor.set_attributes(ds.attrs)
        want = top_rung_ids(anchor, ds.queries)
        check(np.array_equal(want, np.sort(ds.gt[:, :K], axis=1)),
              f"brute_force != exact ground truth at n={n}")
        want_f = top_rung_ids(anchor, ds.queries, pred)
        check(np.array_equal(want_f, np.sort(ds.filtered_gt(pred, k=K),
                                             axis=1)),
              f"filtered brute_force != filtered ground truth at n={n}")
        if n == sizes.diff_n:
            anchor_matches_float64(anchor, ds)
        for name in names:
            if name == "brute_force":
                continue
            b = registry.create(name, _diff_variant(name), metric=ds.metric,
                                seed=seed)
            b.build(ds.base)
            b.set_attributes(ds.attrs)
            for label, p, ref in (("unfiltered", None, want),
                                  ("filtered 0.1", pred, want_f)):
                got = top_rung_ids(b, ds.queries, p)
                bad = np.flatnonzero((got != ref).any(axis=1))
                check(not len(bad), f"{name} {label} differs from the "
                      f"anchor at n={n} on rows {bad[:5].tolist()}")
            say(f"differential n={n} {name}: ids == brute_force "
                f"(unfiltered, filtered 0.1)")


def serve_through_tier(target, ds, params: SearchParams):
    """Every query once through ``AsyncServeTier``, as
    ``repro.launch.serve`` does; returns (ids, seconds, totals)."""
    from repro.serve import AsyncServeTier, TenantSpec, resolve_tenants
    tenants = resolve_tenants([TenantSpec("default")], default_params=params)
    tier = AsyncServeTier(target, tenants, max_batch=MAX_BATCH,
                          max_queue=256)

    async def episode():
        tier.start()
        t0 = time.perf_counter()
        out = []
        for s in range(0, len(ds.queries), 256):
            futs = [tier.submit(q, "default") for q in ds.queries[s:s + 256]]
            out.extend(await asyncio.gather(*futs))
        dt = time.perf_counter() - t0
        await tier.close(drain=True)
        return out, dt

    responses, dt = asyncio.run(episode())
    ids = np.stack([r.ids for r in responses])
    return ids, dt, tier.telemetry.totals()


def warm(target, ds, params: SearchParams) -> None:
    """Compile the one ``max_batch`` program the tier serves."""
    from repro.runtime.server import execute_search_batch
    execute_search_batch(target.search, ds.queries[:1], params,
                         max_batch=MAX_BATCH)


def build(name: str, variant, ds):
    b = registry.create(name, variant, metric=ds.metric, seed=SEED)
    b.build(ds.base)
    return b


def serve_and_score(label: str, target, ds, params: SearchParams,
                    clock: Clock) -> tuple:
    clock(f"compile_{label}", warm, target, ds, params)
    ids, dt, tot = clock(f"serve_{label}", serve_through_tier, target, ds,
                         params)
    check(tot.accounted(), f"{label}: tier accounting broken "
          f"(admitted={tot.admitted} served={tot.served})")
    check(tot.served == len(ds.queries), f"{label}: served {tot.served} "
          f"of {len(ds.queries)}")
    rec = recall_at_k(ids, ds.gt, K)
    say(f"{label}: served {tot.served} requests, {tot.served / dt:.1f} QPS "
        f"(smoke reading), recall@{K}={rec:.4f}, accounting ok")
    return rec, ids


def host_ivf_reference(ivf, queries, params: SearchParams) -> np.ndarray:
    """The ``ivf`` search in float64 numpy on the host, over the same
    built index and the same resolved (nprobe, m, k): coarse probe,
    dequantised int8 scan, shortlist of m, exact rerank.  Ties break by
    lowest index at every cut, as the kernels do."""
    _, st = ivf._invocation(queries, params)
    idx = ivf.index
    cents = np.asarray(idx.centroids, np.float64)
    cells, ids = np.asarray(idx.cells), np.asarray(idx.ids)
    base = np.asarray(idx.base, np.float64)
    base_q, scales = np.asarray(idx.base_q), np.asarray(idx.scales)
    out = np.empty((len(queries), st["k"]), np.int64)
    for r, q in enumerate(np.asarray(queries, np.float64)):
        dc = ((cents - q) ** 2).sum(1)
        probe = np.argsort(dc, kind="stable")[:st["nprobe"]]
        pos = cells[probe].reshape(-1)
        pos = pos[pos >= 0]
        vecs = base_q[pos].astype(np.float64) * scales[pos, None]
        d = ((vecs - q) ** 2).sum(1)
        short = pos[np.argsort(d, kind="stable")[:st["m"]]]
        rd = ((base[short] - q) ** 2).sum(1)
        out[r] = ids[short[np.argsort(rd, kind="stable")[:st["k"]]]]
    return out


def phase_sift(sizes: Sizes, clock: Clock) -> tuple:
    """SIFT-128 at ``sizes.n_base`` through ``ivf`` and the tier; returns
    (served ids, anchor recall, the built ivf backend, the dataset)."""
    ds = sift_dataset(sizes.n_base, sizes.n_query, clock)
    anchor = clock("build_brute_force", build, "brute_force", None, ds)
    res = clock("anchor_search", anchor.search, ds.queries, SearchParams(k=K))
    anchor_rec = recall_at_k(np.asarray(res.ids), ds.gt, K)
    say(f"brute_force anchor recall@{K}={anchor_rec:.4f}")
    del anchor, res
    ivf = clock("build_ivf", build, "ivf", ivf_variant(sizes.nlist), ds)
    say(f"ivf layout: nlist={ivf.index.nlist} cell_pad={ivf.index.cell_pad} "
        f"{ivf.memory_bytes() / 1e6:.1f} MB")
    _, ids = serve_and_score("ivf", ivf, ds, SearchParams(k=K, ef=64),
                             clock)
    return ids, anchor_rec, ivf, ds


def assert_kernels_compiled(target, queries) -> None:
    """The served program holds Pallas kernels lowered for the chip."""
    params = SearchParams(k=K, ef=64)
    text = target.lower_search(queries[:MAX_BATCH], params).compile().as_text()
    check("tpu_custom_call" in text,
          "served ivf program has no tpu_custom_call: kernels did not "
          "compile for the chip")
    say("served ivf program holds tpu_custom_call kernels")


def smoke_one_chip(sizes: Sizes) -> None:
    clock = Clock()
    clock("differential", phase_differential, sizes)
    ids, anchor_rec, ivf, ds = phase_sift(sizes, clock)
    assert_kernels_compiled(ivf, ds.queries)
    check(anchor_rec >= 0.999, f"brute_force recall@{K}={anchor_rec:.4f} "
          f"< 0.999")
    n = min(REF_QUERIES, len(ds.queries))
    ref = clock("host_reference", host_ivf_reference, ivf, ds.queries[:n],
                SearchParams(k=K, ef=64))
    rec, rec_ref = (recall_at_k(x[:n], ds.gt[:n], K) for x in (ids, ref))
    same = np.mean([len(set(a) & set(b)) / K for a, b in zip(ids[:n], ref)])
    say(f"ivf vs float64 host reference on {n} queries: recall@{K} "
        f"{rec:.4f} vs {rec_ref:.4f}, {same:.4f} of ids shared")
    check(rec >= rec_ref - RECALL_SLACK,
          f"ivf recall@{K}={rec:.4f} more than {RECALL_SLACK} below the "
          f"host reference's {rec_ref:.4f}")


def shard_devices(backend) -> set:
    idx = backend.index
    return set().union(*(a.sharding.device_set
                         for a in (idx.cells, idx.base_q, idx.base_f)))


def place_four(backend) -> None:
    """Mesh placement on exactly four devices; never the unrolled form."""
    from repro.launch.mesh import make_shard_mesh
    backend.place_on_mesh(make_shard_mesh(4))
    devs = shard_devices(backend)
    check(len(devs) == 4, f"shard arrays span {len(devs)} devices, not 4")
    check(backend._search_fn() is backend._placed_search,
          "sharded backend is not serving the placed program")


def smoke_four_chips(sizes: Sizes) -> None:
    import jax
    check(jax.device_count() >= 4, f"{jax.device_count()} devices < 4")
    clock = Clock()

    # mid-size build: equal ids at the all-cells probe
    # (few queries: the all-cells probe gathers the whole base per query)
    ds = make_dataset(DATASET, n_base=sizes.mid_n, n_query=16, k_gt=K,
                      seed=SEED)
    v = ivf_variant(sizes.mid_nlist)
    ivf = build("ivf", v, ds)
    sh = build("sharded", dataclasses.replace(v, backend="sharded",
                                              n_shards=4), ds)
    place_four(sh)
    ef = search_ef_ladder(ivf)[-1]
    p = SearchParams(k=K, ef=ef)
    a = np.sort(np.asarray(ivf.search(ds.queries, p).ids), axis=1)
    b = np.sort(np.asarray(sh.search(ds.queries, p).ids), axis=1)
    bad = np.flatnonzero((a != b).any(axis=1))
    check(not len(bad), f"sharded(4) != ivf at the all-cells probe on "
          f"rows {bad[:5].tolist()}")
    say(f"mid n={sizes.mid_n} nlist={ivf.index.nlist}: sharded(4 devices) "
        f"ids == ivf at the all-cells probe (ef={ef})")
    del ivf, sh

    # 1M build: recall at the served rung within 0.01 of ivf's
    ds = sift_dataset(sizes.n_base, sizes.n_query, clock)
    ivf = clock("build_ivf", build, "ivf", ivf_variant(sizes.nlist), ds)
    params = SearchParams(k=K, ef=64)
    rec_ivf, _ = serve_and_score("ivf", ivf, ds, params, clock)
    del ivf
    sh = clock("build_sharded", build, "sharded",
               ivf_variant(sizes.nlist, backend="sharded", n_shards=4), ds)
    place_four(sh)
    say(f"sharded: {sh.device_memory_bytes() / 1e6:.1f} MB/device on "
        f"{len(shard_devices(sh))} devices")
    rec_sh, _ = serve_and_score("sharded", sh, ds, params, clock)
    check(abs(rec_sh - rec_ivf) <= SHARDED_RECALL_SLACK,
          f"sharded recall {rec_sh:.4f} vs ivf {rec_ivf:.4f}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-vs-ivf phase on four chips")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    say(f"compile cache at {enable_compile_cache()}")
    say(f"device {dev.device_kind} x{jax.device_count()}; every time and "
        f"rate below is a one-off smoke reading, not a benchmark")
    t0 = time.perf_counter()
    if args.four_chips:
        smoke_four_chips(Sizes())
    else:
        smoke_one_chip(Sizes())
    say(f"total {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
