"""One run of one cell: set-up, the measured window, the check against
the plain reference, and the result line.

Set-up (``setup_s``) is everything from the process's start to the
window: JAX start-up, data and ground truth on the device, the index
build, and a warm-up through the tier that compiles (or loads from the
persistent cache) the one ``max_batch`` program the window serves.  The
window then runs the cell's traffic mix for ``--seconds``.  The check
runs after the window, once the device state is freed, and is not part
of any timed number.
"""
from __future__ import annotations

import argparse
import asyncio
import gc
import json
import math
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from chipbench import data as data_lib
from chipbench import peaks as peaks_lib
from chipbench import spec as spec_lib
from chipbench import traces, traffic

TENANT = "bench"
#: fixed paths inside the checkout (listed in ``.gitignore``): JAX keys
#: its persistent cache by directory, so it never moves
CACHE_DIR = spec_lib.ROOT / ".chipbench" / "jax_cache"
TRACE_DIR = spec_lib.ROOT / ".chipbench" / "trace"
#: salts that keep the host-side draws of one seed apart
ORDER_SALT, SAMPLE_SALT = 1, 2


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclass
class Run:
    """Everything a metric reader may read."""
    cell: spec_lib.Cell
    config: dict
    mix: dict
    system: object
    seed: int
    seconds: float
    setup_s: float = math.nan
    build_s: float = math.nan
    window: traffic.Window | None = None
    data: data_lib.Data | None = None
    partition: object = None
    trace: dict | None = None
    peaks: dict | None = None
    info: dict = field(default_factory=dict)
    _probe: np.ndarray | None = None

    def in_window(self) -> list:
        """Requests due inside the window (all an open loop sends)."""
        return [r for r in self.window.requests if r.due < self.window.t1]

    def answered(self) -> list:
        return [r for r in self.in_window() if r.answered]

    def batches(self) -> list:
        """Pool indices of the requests served together, batch by batch:
        the tier stamps every response of one batch with the same
        ``compute_ms``."""
        groups: dict = {}
        for r in self.answered():
            groups.setdefault(r.compute_ms, []).append(r.qi)
        return list(groups.values())

    def probe(self) -> np.ndarray:
        """(n_pool, nprobe) cells each pool query probes (reference)."""
        if self._probe is None:
            st = self.system.stated(self.config)
            self._probe = self.system.reference.probes(
                self.partition, self.data.queries, st["nprobe"],
                metric=st["metric"])
        return self._probe


class GcPauses:
    """Garbage collections while on, as (start offset in s, pause in
    ms, generation): a collection holds the interpreter lock, so it
    stalls the event loop and the tier's thread alike."""

    def __init__(self):
        self.on = False
        self.t0 = 0.0
        self.pauses = []
        self._start = None

    def __call__(self, phase, info):
        if not self.on:
            return
        now = time.perf_counter()
        if phase == "start":
            self._start = now
        elif self._start is not None:
            self.pauses.append((self._start - self.t0,
                                (now - self._start) * 1e3,
                                info["generation"]))
            self._start = None

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


class CompileCounter:
    """Counts XLA programs built, and persistent-cache hits, while on."""

    def __init__(self):
        self.on = False
        self.built = 0
        self.hits = 0

    def _duration(self, event, _secs, **_kw):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.built += 1

    def _event(self, event, **_kw):
        if self.on and event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def __enter__(self):
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self._duration)
        monitoring.unregister_event_listener(self._event)


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(base[k], v) if isinstance(v, dict) and k in base \
            else v
    return out


def host_gib() -> tuple:
    """(resident, peak resident) memory of this process, in GiB."""
    import resource
    rss = math.nan
    for line in Path("/proc/self/status").read_text().splitlines():
        if line.startswith("VmRSS:"):
            rss = int(line.split()[1]) / 2**20
    return rss, resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


async def _episode(run: Run, tier, pool, counter: CompileCounter,
                   pauses: GcPauses, *, trace_dir: Path | None,
                   t_start: float) -> None:
    import jax
    cfg = run.config
    max_batch = cfg["serve"]["max_batch"]
    tier.start()
    warm = [tier.submit(q, TENANT) for q in pool[:2 * max_batch]]
    await asyncio.gather(*warm)
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # millions of events, and overhead
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    span = {}
    loop = asyncio.get_running_loop()

    def on_start(win):
        run.setup_s = win.t0 - t_start
        counter.on = True
        pauses.on, pauses.t0 = True, win.t0
        if trace_dir is not None:
            span["s"] = jax.profiler.TraceAnnotation(traces.WINDOW_SPAN)
            span["s"].__enter__()

        def close():
            counter.on = pauses.on = False
            if "s" in span:
                span.pop("s").__exit__(None, None, None)
        loop.call_later(win.seconds, close)

    rng = data_lib.host_rng(run.seed, ORDER_SALT)
    # set-up's objects (JAX's modules, the data, the index) move to the
    # permanent generation: a full collection then walks only what the
    # window allocates, instead of pausing the serve loop for ~0.1 s
    gc.collect()
    gc.freeze()
    try:
        run.window = await traffic.drive(
            run.mix, tier, TENANT, pool, max_batch=max_batch,
            seconds=run.seconds, rng=rng, on_start=on_start)
        await tier.close(drain=True)
    finally:
        gc.unfreeze()
    if trace_dir is not None:
        jax.profiler.stop_trace()


def check(run: Run) -> dict:
    """The numbers compared with the system's plain reference, each with
    its limit; a limit of ``None`` marks a number read but not compared
    (one that no control reading separates from sound runs)."""
    ref = run.system.reference
    reqs = run.in_window()
    answers = [(r.qi, r.ids, r.dists) for r in reqs if r.answered]
    n_sample = min(run.config["check"]["sample"], len(answers))
    sample = np.sort(data_lib.host_rng(run.seed, SAMPLE_SALT).choice(
        len(answers), size=n_sample, replace=False))
    unanswered = sum(not (r.answered or r.refused) for r in reqs)
    nums = ref.compare(answers, unanswered, run.data.queries,
                       run.data.base, run.partition, sample=sample,
                       **run.system.stated(run.config))
    limits = run.system.limits(run.config)
    return {name: {"value": nums[name], "limit": limits[name]}
            for name in ref.NUMBERS}


def execute(cell_name: str, seed: int, seconds: float, trace: bool, *,
            bench: spec_lib.Bench | None = None, t_start: float | None = None,
            require_chip: bool = True, config_override: dict | None = None,
            trace_dir: Path = TRACE_DIR, say=print, inspect=None) -> dict:
    """Run one cell once; returns the result line's object.

    ``inspect(run)``, where given, is called once the check is done (the
    control readings in :mod:`chipbench.control` use it)."""
    t_start = time.perf_counter() if t_start is None else t_start

    def phase(name: str) -> None:
        """Say, as it ends, that a phase has ended, with the host memory
        then: a run killed for memory leaves the last phase it ended."""
        rss, peak = host_gib()
        say(f"chipbench: {name} done at {time.perf_counter() - t_start:.3f}"
            f" s; host RSS {rss:.2f} GiB, peak {peak:.2f} GiB")

    bench = bench or spec_lib.Bench()
    cell = bench.cell(cell_name)
    cfg = _merge(bench.config(cell.config), config_override or {})
    run = Run(cell=cell, config=cfg, mix=bench.traffic(cell.traffic),
              system=bench.system(cfg["system"]), seed=seed, seconds=seconds)
    metrics = bench.metrics_for(cell.name, per_layer=trace)
    readers = {m.name: bench.reader(m.name) for m in metrics}

    import jax
    from repro.serve import AsyncServeTier, TenantSpec, resolve_tenants
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
                     f"found {len(devs)} {devs[0].platform} device(s)")
    if require_chip:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    run.peaks = peaks_lib.for_kind(devs[0].device_kind) if require_chip \
        else None
    phase("start-up")

    t = time.perf_counter()
    run.data = data_lib.make(cfg["data"], seed, cfg["serve"]["k"])
    run.info["data_s"] = time.perf_counter() - t
    phase("data")
    backend, run.build_s = run.system.build(cfg, run.data.base, seed)
    run.info.update(run.system.describe(backend))
    phase("build")
    max_batch = cfg["serve"]["max_batch"]
    tenants = resolve_tenants([TenantSpec(TENANT)],
                              default_params=run.system.params(cfg))
    tier = AsyncServeTier(backend, tenants, max_batch=max_batch,
                          max_queue=int(run.mix["queue_per_slot"]) * max_batch)
    with CompileCounter() as counter, GcPauses() as pauses:
        asyncio.run(_episode(run, tier, run.data.queries, counter, pauses,
                             trace_dir=trace_dir if trace else None,
                             t_start=t_start))
    run.info["compiles_in_window"] = counter.built - counter.hits
    run.info["cache_loads_in_window"] = counter.hits
    tot = tier.telemetry.totals()
    run.info["sheds"] = (tot.shed_overload, tot.shed_deadline,
                         tot.shed_closed)
    device = device_info(jax, cell.chips)
    run.partition = run.system.partition(backend)
    del tier, backend
    gc.collect()
    phase("warm-up and window")
    if trace:
        run.trace = traces.reduce(traces.events(str(trace_dir)))
        if run.trace is not None:
            device["busy_s"] = run.trace["busy_s"]
            device["window_s"] = run.trace["window_s"]
        phase("trace reduction")

    t = time.perf_counter()
    checks = check(run)
    run.info["check_s"] = time.perf_counter() - t
    phase("check")
    correct = all(c["limit"] is None or c["value"] <= c["limit"]
                  for c in checks.values())
    if inspect is not None:
        inspect(run)

    values = {}
    for m in metrics:
        v = readers[m.name](run)
        if v is not None and not math.isfinite(v):
            # e.g. a 95th percentile past the 5% of requests that failed:
            # no number stands for it, and JSON has none
            say(f"chipbench: {m.name} is {v}: left out of the result")
        elif v is not None:
            values[m.name] = {"value": float(v), "unit": m.unit}
    if "bound" in run.info:
        say(f"chipbench: roofline least time {run.info['least_ms']:.6f} ms a "
            f"batch, bound by {run.info['bound']}")
    reqs = run.in_window()
    late = traffic.lateness_ms(run.window)
    say(f"chipbench: {cell.name} seed={seed} setup_s={run.setup_s:.3f} "
        f"build_s={run.build_s:.3f} data_s={run.info['data_s']:.3f} "
        f"check_s={run.info['check_s']:.3f}")
    say(f"chipbench: index nlist={run.info['nlist']} cell_pad="
        f"{run.info['cell_pad']} bytes={run.info['index_bytes']}; peak HBM "
        f"{device['memory_peak_bytes']} bytes")
    sizes = [len(b) for b in run.batches()]
    say(f"chipbench: requests due {len(reqs)} answered "
        f"{sum(r.answered for r in reqs)} batches {len(sizes)} mean fill "
        f"{np.mean(sizes) if sizes else 0:.2f}/{max_batch}; compiles in "
        f"window {run.info['compiles_in_window']} (cache loads "
        f"{run.info['cache_loads_in_window']}); sheds overload/deadline/"
        f"closed {run.info['sheds']}")
    if len(late):
        due, ms = late[int(np.argmax(late[:, 1]))]
        say(f"chipbench: generator lateness ms p50 "
            f"{np.percentile(late[:, 1], 50):.3f} p99 "
            f"{np.percentile(late[:, 1], 99):.3f} max {ms:.3f} at {due:.3f} s")
    slow = sorted(pauses.pauses, key=lambda p: -p[1])[:3]
    say(f"chipbench: garbage collections in window {len(pauses.pauses)}, "
        f"longest (at s, ms, generation) "
        + ", ".join(f"({a:.3f}, {b:.3f}, {g})" for a, b, g in slow))
    errors = sorted({r.error for r in reqs if r.error})
    if errors:
        say(f"chipbench: request errors: {errors[:5]}")
    result = {"correct": bool(correct), "attempted": len(reqs),
              "failed": sum(not r.answered for r in reqs),
              "metrics": values, "device": device}
    if trace and run.trace is not None:
        result["breakdown"] = {"device_ops": traces.top_ops(run.trace),
                               "idle_gaps": run.trace["idle_gaps"]}
    result["checks"] = checks
    return result


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = execute(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=t_start,
                         say=lambda s: print(s, flush=True))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        limit = "none (not compared)" if c["limit"] is None \
            else repr(c["limit"])
        print(f"check {name} {c['value']!r} limit {limit}",
              file=sys.stderr)
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0
