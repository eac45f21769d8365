"""The sweeps that fixed the numbers in the configuration and mix files.
Run on the chip, once, from the checkout root::

    python3 chipbench/sweep.py --config sift1m --knee open

1. Probe floors: for each of ``FLOOR_SEEDS``, the data and the build of
   that seed, and the build's probe floor (the fewest cells that always
   hold k vectors; the program raises a stated nprobe under it).
2. Rungs, on ``SEED``: build once, then for each ``NPROBE_LADDER`` rung
   at or above ``FLOOR_MARGIN`` times the highest floor seen, compile the
   served ``max_batch`` program and read its memory (arguments plus
   temporaries); a rung is eligible while that stays at or under
   ``MAX_GB``.  Each eligible rung searches ``N_QUERIES`` held-out
   queries; the operating point is the lowest rung with recall@10 at or
   above ``TARGET``, else the highest eligible rung.  Only a rung above
   the floor is served as stated, so only such a rung keeps the work of
   a run the same from seed to seed; the margin is for the seeds no
   survey saw, whose floors reach past the highest of a few dozen.
3. Knee (``--knee <mix>``, at the chosen rung, windows of the benchmark's
   ``run_seconds``): one closed-loop window gives the capacity, then
   open-loop windows at fractions of it.  The knee is the highest offered
   rate below the first one that falls behind: served less than
   ``KEEP_UP`` of it, or left two batches or more unanswered at the
   window's close (one running, one queued is the most a queue that keeps
   up holds).  The mix's rate is ``0.8 *`` the knee.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

SEED = 0
FLOOR_SEEDS = tuple(range(1, 25))
FLOOR_MARGIN = 2
N_QUERIES = 2048
#: arguments plus temporaries of the served program: room beside the
#: tier's own arrays on a 16 GB chip
MAX_GB = 12.0
#: recall@10 at which the CRINN paper reports QPS
TARGET = 0.90
KEEP_UP = 0.98
FRACTIONS = (0.5, 0.6, 0.7, 0.75, 0.8, 0.85, 0.9, 0.95, 1.0, 1.05)


def floor_survey(system, cfg) -> list:
    from chipbench import data as data_lib
    k = cfg["serve"]["k"]
    rows = []
    for seed in FLOOR_SEEDS:
        data = data_lib.make(cfg["data"], seed, k)
        backend, build_s = system.build(cfg, data.base, seed)
        part = system.partition(backend)
        row = {"seed": seed, "build_s": build_s,
               "probe_floor": system.reference.probe_floor(part.sizes, k),
               **system.describe(backend)}
        print("floor", json.dumps(row), flush=True)
        rows.append(row)
        del data, backend, part
        gc.collect()
    return rows


def rung_sweep(backend, system, cfg, data, *, lowest: int):
    import jax
    from repro.anns.backends.ivf import NPROBE_LADDER
    from repro.runtime.server import execute_search_batch
    max_batch = cfg["serve"]["max_batch"]
    params = system.params(cfg)
    rows, chosen = [], None
    q = data.queries[:N_QUERIES]
    base_variant = backend.variant
    for rung in [r for r in NPROBE_LADDER
                 if lowest <= r <= backend.index.nlist]:
        backend.variant = dataclasses.replace(base_variant, nprobe=rung)
        mem = backend.lower_search(q[:max_batch], params).compile() \
            .memory_analysis()
        gb = (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 1e9
        row = {"nprobe": rung, "args_gb": mem.argument_size_in_bytes / 1e9,
               "temp_gb": mem.temp_size_in_bytes / 1e9,
               "eligible": gb <= MAX_GB}
        if row["eligible"]:
            ids, t = [], []
            for lo in range(0, len(q), max_batch):
                t0 = time.perf_counter()
                got, _, _ = execute_search_batch(
                    backend.search, q[lo:lo + max_batch], params,
                    max_batch=max_batch)
                t.append(time.perf_counter() - t0)
                ids.append(got)
            ids = np.concatenate(ids)
            hits = [len(np.intersect1d(a[:10], g[:10])) for a, g in
                    zip(ids, data.gt[:N_QUERIES])]
            row["recall_at_10"] = float(np.mean(hits)) / 10.0
            row["batch_ms_median"] = float(np.median(t[1:])) * 1e3
            if chosen is None and row["recall_at_10"] >= TARGET:
                chosen = rung
        print("rung", json.dumps(row), flush=True)
        rows.append(row)
        if not row["eligible"] or row.get("recall_at_10", 0) >= 0.995:
            break
    eligible = [r["nprobe"] for r in rows if r["eligible"]]
    if not eligible:
        raise SystemExit(f"sweep: no rung from {lowest} up fits "
                         f"{MAX_GB} GB")
    backend.variant = base_variant
    jax.clear_caches()
    return rows, chosen if chosen is not None else max(eligible)


def knee_sweep(backend, system, cfg, data, mix, *, rung, seconds):
    from chipbench import data as data_lib
    from chipbench import traffic
    from chipbench.harness import TENANT
    from repro.serve import AsyncServeTier, TenantSpec, resolve_tenants
    max_batch = cfg["serve"]["max_batch"]
    backend.variant = dataclasses.replace(backend.variant, nprobe=rung)
    params = system.params(cfg)

    def tier():
        tenants = resolve_tenants([TenantSpec(TENANT)], default_params=params)
        return AsyncServeTier(backend, tenants, max_batch=max_batch,
                              max_queue=int(mix["queue_per_slot"]) * max_batch)

    async def window(m):
        t = tier()
        t.start()
        await asyncio.gather(*[t.submit(q, TENANT)
                               for q in data.queries[:2 * max_batch]])
        gc.collect()
        gc.freeze()                     # as the harness does
        try:
            win = await traffic.drive(m, t, TENANT, data.queries,
                                      max_batch=max_batch, seconds=seconds,
                                      rng=data_lib.host_rng(SEED, 1))
            await t.close(drain=True)
        finally:
            gc.unfreeze()
        return win

    closed = asyncio.run(window({"kind": "closed", "clients_per_slot": 4}))
    cap = sum(r.done <= closed.t1 for r in closed.requests
              if r.answered) / seconds
    print("closed", json.dumps({"qps": cap}), flush=True)
    rows, knee = [], None
    for f in FRACTIONS:
        rate = f * cap
        win = asyncio.run(window(dict(mix, rate_per_s=rate)))
        due = [r for r in win.requests if r.due < win.t1]
        served = sum(r.answered and r.done <= win.t1 for r in due)
        backlog = len(due) - served
        lat = sorted(r.latency_ms() for r in due)
        late = traffic.lateness_ms(win)
        row = {"fraction": f, "offered": rate, "served": served / seconds,
               "backlog_at_close": backlog,
               "p50_ms": lat[len(lat) // 2],
               "p95_ms": lat[max(0, int(np.ceil(0.95 * len(lat))) - 1)],
               "max_lateness_ms": float(late[:, 1].max()) if len(late)
               else 0.0}
        row["keeps_up"] = (row["served"] >= KEEP_UP * rate
                           and backlog < 2 * max_batch)
        if row["keeps_up"] and all(r["keeps_up"] for r in rows):
            knee = rate
        print("rate", json.dumps(row), flush=True)
        rows.append(row)
    return cap, rows, knee


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--knee", default=None, help="open mix to find the knee of")
    args = ap.parse_args(argv)

    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 3
    from chipbench import data as data_lib
    from chipbench import spec
    from chipbench.harness import CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    bench = spec.Bench()
    cfg = bench.config(args.config)
    system = bench.system(cfg["system"])
    floors = floor_survey(system, cfg)
    t = time.perf_counter()
    data = data_lib.make(cfg["data"], SEED, cfg["serve"]["k"])
    print(f"sweep: data {time.perf_counter() - t:.3f} s", flush=True)
    backend, build_s = system.build(cfg, data.base, SEED)
    floor = max([r["probe_floor"] for r in floors] + [
        system.reference.probe_floor(system.partition(backend).sizes,
                                     cfg["serve"]["k"])])
    print(f"sweep: build {build_s:.3f} s "
          f"{json.dumps(system.describe(backend))}; highest floor {floor}",
          flush=True)
    rows, rung = rung_sweep(backend, system, cfg, data,
                            lowest=FLOOR_MARGIN * floor)
    out = {"config": args.config, "seed": SEED, "floors": floors,
           "highest_floor": floor, "rungs": rows, "chosen_nprobe": rung}
    if args.knee:
        cap, krows, knee = knee_sweep(
            backend, system, cfg, data, bench.traffic(args.knee), rung=rung,
            seconds=float(bench.doc["run_seconds"]))
        out.update(closed_qps=cap, rates=krows, knee=knee,
                   rate_per_s=None if knee is None else 0.8 * knee)
    out_dir = spec.ROOT / ".chipbench"
    out_dir.mkdir(exist_ok=True)
    (out_dir / f"sweep_{args.config}.json").write_text(
        json.dumps(out, indent=1))
    print(json.dumps({k: v for k, v in out.items()
                      if k not in ("floors", "rungs", "rates")}))
    return 0


if __name__ == "__main__":
    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    sys.exit(main())
