"""Serving-tier tests: backpressure invariants, continuous batching on
the static jit buckets, per-tenant SLOs, telemetry, and e2e episodes.

Layers:

- **unit** — latency histogram quantiles/merge; tenant-spec grammar;
  the ladder-snapped batch-``k`` policy and ``snap_down_to_ladder``;
  submit-time query validation (the fail-fast that used to surface as
  an opaque ``np.stack`` crash inside flush).
- **property** (proptest harness) — the admission queue never exceeds
  its bound and its depth accounting is exact under random
  admit/pop/drain interleavings.
- **backpressure invariants** — shed requests always get *typed*
  rejections (``Overloaded``/``DeadlineExceeded``/``ServerClosed``),
  never silent drops; drain-on-shutdown serves everything admitted;
  ``admitted == served + shed_deadline + shed_closed`` holds at close.
- **jit hygiene** — continuous batching adds no retrace buckets beyond
  the swept ladders (``_ivf_search._cache_size()`` flat under mixed
  partial batches), and the ``AnnsServer`` k-clamp regression: a live
  ``n`` between ladder rungs snaps *down* instead of minting one trace
  per distinct ``n`` on a mutating backend.
- **multi-tenancy** — weighted (stride) scheduling ratio; tenants
  sharing a pick share batches; SLO isolation (a lax flood cannot pull
  a strict tenant's recall below its target).
- **e2e** — in-process asyncio episodes (deterministic overload burst,
  deadline shedding) and a subprocess ``serve --async --tenants`` run
  asserting the greppable ``serve:`` markers.
"""
import asyncio
import dataclasses
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from proptest import given, integers, lists
from repro.anns import SearchParams, make_dataset, registry
from repro.anns.api import EF_LADDER, round_ef, snap_down_to_ladder
from repro.anns.datasets import recall_at_k
from repro.anns.engine import family_baseline
from repro.anns.tune import OperatingPoint, frontier_from_points
from repro.runtime.server import AnnsServer, batch_k_policy, validate_query
from repro.serve import (AdmissionQueue, AsyncServeTier, ContinuousBatcher,
                         DeadlineExceeded, LatencyHistogram, Overloaded,
                         ServeRejection, ServeRequest, ServerClosed,
                         TenantSpec, Ticket,
                         attach_drift_monitors, parse_tenant_specs,
                         resolve_tenants)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

N_BASE, N_QUERY = 1500, 32
P8 = SearchParams(k=10, ef=8)
P16 = SearchParams(k=10, ef=16)
P64 = SearchParams(k=10, ef=64)
MAX_BATCH = 8


@pytest.fixture(scope="module")
def ds():
    return make_dataset("sift-128-euclidean", n_base=N_BASE,
                        n_query=N_QUERY)


@pytest.fixture(scope="module")
def ivf(ds):
    v = dataclasses.replace(family_baseline("ivf"), nlist=16,
                            kmeans_iters=2)
    b = registry.create("ivf", v, metric=ds.metric, seed=0)
    b.build(ds.base)
    return b


def _tenants(*specs, params=P16):
    """Explicit-params tenants (no frontier) for scheduler tests."""
    return resolve_tenants(list(specs), default_params=params)


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def test_histogram_quantiles_and_mean():
    h = LatencyHistogram()
    for _ in range(100):
        h.record(10.0)
    assert h.count == 100
    assert h.mean_ms == pytest.approx(10.0)
    # constant distribution: every quantile is the (clipped) sample
    assert h.quantile(0.5) == pytest.approx(10.0)
    assert h.quantile(0.99) == pytest.approx(10.0)
    assert h.snapshot()["p95_ms"] == pytest.approx(10.0)


def test_histogram_quantile_bucket_accuracy():
    h = LatencyHistogram()
    vals = [0.5, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0]
    for v in vals:
        h.record(v)
    # log-bucketed: each quantile lands within one bucket ratio (~19%)
    # of the true order statistic
    assert h.quantile(0.05) <= 0.5 * 1.2
    p50 = h.quantile(0.5)
    assert 8.0 / 1.2 <= p50 <= 8.0 * 1.2
    assert h.quantile(1.0) == pytest.approx(256.0)


def test_histogram_empty_and_merge():
    a, b = LatencyHistogram(), LatencyHistogram()
    assert a.quantile(0.5) == 0.0 and a.mean_ms == 0.0
    a.record(1.0)
    b.record(100.0)
    a.merge(b)
    assert a.count == 2
    assert a.max_ms == 100.0
    assert a.sum_ms == pytest.approx(101.0)


# ---------------------------------------------------------------------------
# tenant specs
# ---------------------------------------------------------------------------

def test_parse_tenant_specs():
    specs = parse_tenant_specs("strict:0.95:4:200,lax:0.85")
    assert specs[0] == TenantSpec("strict", 0.95, 4.0, 200.0)
    assert specs[1] == TenantSpec("lax", 0.85, 1.0, None)


@pytest.mark.parametrize("bad", [
    "strict",                    # no recall
    "a:0.9,a:0.8",               # duplicate name
    "a:1.5",                     # recall out of [0, 1]
    "a:0.9:0",                   # weight <= 0
    "a:0.9:1:-5",                # deadline <= 0
    "a:0.9:1:2:3",               # too many fields
    "",                          # empty
    "a:recall",                  # non-numeric
])
def test_parse_tenant_specs_rejects(bad):
    with pytest.raises(ValueError):
        parse_tenant_specs(bad)


def test_resolve_tenants_frontier_picks_and_infeasible():
    def op(ef, recall, qps):
        return OperatingPoint(backend="ivf",
                              params=SearchParams(k=10, ef=ef),
                              recall=recall, qps=qps, p50_ms=1.0,
                              memory_bytes=1000,
                              device_memory_bytes=1000)
    frontier = frontier_from_points(
        [op(8, 0.80, 4000.0), op(32, 0.92, 2000.0), op(128, 0.99, 500.0)],
        dataset="d", n_base=100, n_query=10, k=10)
    tenants = resolve_tenants(
        [TenantSpec("strict", 0.95), TenantSpec("lax", 0.75)],
        frontier=frontier)
    # each tenant gets its own constrained max-QPS pick, on the ladder
    assert tenants["strict"].params.ef == 128
    assert tenants["lax"].params.ef == 8
    assert all(t.params.ef in EF_LADDER for t in tenants.values())
    from repro.anns.tune import InfeasibleSLO
    with pytest.raises(InfeasibleSLO):
        resolve_tenants([TenantSpec("impossible", 0.999)],
                        frontier=frontier)


def test_attach_drift_monitors_names_verdicts():
    pt = OperatingPoint(backend="ivf", params=P16, recall=0.95,
                        qps=1000.0, p50_ms=1.0, memory_bytes=1,
                        device_memory_bytes=1)
    tenants = resolve_tenants([TenantSpec("strict", 0.9)],
                              frontier=frontier_from_points(
                                  [pt], dataset="d", n_base=1, n_query=1,
                                  k=10))
    attach_drift_monitors(tenants, recall_margin=0.02, min_observations=1)
    st = tenants["strict"]
    assert st.monitor is not None and st.monitor.name == "strict"
    v = st.observe_served(recall=0.5, latency_ms=1.0)
    assert v.triggered and v.name == "strict"
    assert v.describe().startswith("[strict] ")


# ---------------------------------------------------------------------------
# batch-k policy / ladder snapping (satellite: the k-clamp fix)
# ---------------------------------------------------------------------------

def test_snap_down_to_ladder():
    assert snap_down_to_ladder(8, EF_LADDER) == 8
    assert snap_down_to_ladder(100, EF_LADDER) == 96
    assert snap_down_to_ladder(512, EF_LADDER) == 512
    assert snap_down_to_ladder(10_000, EF_LADDER) == 512
    # below the ladder there is no rung to snap to: the raw value stands
    assert snap_down_to_ladder(5, EF_LADDER) == 5


def test_batch_k_policy_is_always_on_ladder_or_default():
    assert batch_k_policy(10, 10, None) == 10          # default k wins
    assert batch_k_policy(10, 50, None) == round_ef(50)  # up onto ladder
    assert batch_k_policy(10, 64, 5000) == 64          # big index: no clamp
    # the regression: a live n between rungs snaps DOWN onto the ladder
    # instead of serving k=n (one jit trace per distinct n)
    assert batch_k_policy(10, 64, 43) == 32
    assert batch_k_policy(10, 64, 64) == 64            # n on-rung: exact fit
    assert batch_k_policy(10, 64, 5) == 5              # tiny index


def test_stream_kclamp_does_not_retrace_per_live_n():
    """AnnsServer on a mutating backend: inserts change ``n_live``
    between flushes while requests ask for k > n.  The ladder-snapped
    clamp keeps the jitted search on one (k, m) bucket — the old
    ``min(k, n)`` minted a fresh trace per distinct live n."""
    from repro.anns.stream.search import stream_ivf_search

    rng = np.random.default_rng(0)
    base = rng.standard_normal((40, 32)).astype(np.float32)
    v = dataclasses.replace(family_baseline("stream_ivf"), nlist=4,
                            kmeans_iters=2, tail_cap=64)
    b = registry.create("stream_ivf", v, metric="l2", seed=0)
    b.build(base)
    server = AnnsServer(b, max_batch=4, params=SearchParams(k=10, ef=8))

    def flush_k64():
        for q in base[:3]:
            server.submit(q, k=64)
        return server.run()

    out = flush_k64()                       # warm: n_live=40 -> k snaps to 32
    assert out[0].ids.shape[0] <= 64
    before = stream_ivf_search._cache_size()
    for _ in range(3):                      # n_live walks 42, 44, 46 — all
        b.insert(rng.standard_normal((2, 32)).astype(np.float32))
        flush_k64()                         # inside the same [32, 48) rung gap
    # the old min(k, n) clamp served k=42/44/46: three fresh traces here
    assert stream_ivf_search._cache_size() - before == 0


# ---------------------------------------------------------------------------
# submit-time validation (satellite: fail fast, not np.stack in flush)
# ---------------------------------------------------------------------------

def test_validate_query_shapes_and_dtypes():
    q = validate_query([1.0, 2.0, 3.0])
    assert q.shape == (3,)
    with pytest.raises(ValueError, match=r"pass query\[0\]"):
        validate_query(np.zeros((1, 4), np.float32))
    with pytest.raises(ValueError, match="1-D"):
        validate_query(np.zeros((2, 4), np.float32))
    with pytest.raises(ValueError, match="dim 4 but the index holds 8"):
        validate_query(np.zeros(4, np.float32), dim=8)
    with pytest.raises(TypeError, match="not numeric"):
        validate_query(np.array(["a", "b"]))


def test_anns_server_submit_fails_fast(ds, ivf):
    server = AnnsServer(ivf, max_batch=MAX_BATCH, params=P16)
    with pytest.raises(ValueError, match=r"pass query\[0\]"):
        server.submit(ds.queries[:1])            # (1, d) matrix
    with pytest.raises(ValueError, match="index holds 128"):
        server.submit(np.zeros(64, np.float32))  # wrong dim
    with pytest.raises(TypeError):
        server.submit(np.array([None] * 128))    # non-numeric
    server.submit(ds.queries[0])                 # the valid shape passes
    assert len(server.run()) == 1


def test_batcher_submit_validates_and_knows_tenants(ds, ivf):
    b = ContinuousBatcher(ivf, _tenants(TenantSpec("a")),
                          max_batch=MAX_BATCH)
    with pytest.raises(KeyError, match="unknown tenant"):
        b.submit(ds.queries[0], "nope")
    with pytest.raises(ValueError, match=r"pass query\[0\]"):
        b.submit(ds.queries[:1], "a")
    with pytest.raises(ValueError, match="index holds 128"):
        b.submit(np.zeros(3, np.float32), "a")
    assert b.pending() == 0                      # nothing was enqueued


# ---------------------------------------------------------------------------
# admission queue: bound + typed rejection invariants
# ---------------------------------------------------------------------------

def _req(tenant="t", group=P16):
    return ServeRequest(tenant=tenant, query=np.zeros(4, np.float32),
                        k=10, group=group, ticket=Ticket())


def test_queue_bound_typed_overload():
    q = AdmissionQueue(3)
    for _ in range(3):
        q.admit(_req())
    with pytest.raises(Overloaded) as ei:
        q.admit(_req())
    assert ei.value.depth == 3 and ei.value.bound == 3
    assert ei.value.tenant == "t"
    assert q.depth == 3                          # the shed never queued


def test_queue_closed_typed():
    q = AdmissionQueue(3)
    q.close()
    with pytest.raises(ServerClosed):
        q.admit(_req())


def test_queue_fifo_within_group_and_shed_expired():
    q = AdmissionQueue(8)
    reqs = [_req() for _ in range(4)]
    reqs[1].deadline = 1.0
    reqs[3].deadline = 5.0
    for r in reqs:
        q.admit(r)
    expired = q.shed_expired(now=2.0)
    assert expired == [reqs[1]]                  # only the passed deadline
    assert q.depth == 3
    batch = q.pop_batch(P16, 10)
    assert batch == [reqs[0], reqs[2], reqs[3]]  # FIFO, expired gone
    assert q.depth == 0


@given(n_examples=20, ops=lists(integers(0, 3), 5, 60),
       bound=integers(1, 8))
def test_queue_depth_accounting_property(ops, bound):
    q = AdmissionQueue(bound)
    admitted = removed = 0
    for op in ops:
        if op <= 1:
            try:
                q.admit(_req())
                admitted += 1
            except Overloaded:
                pass
        elif op == 2:
            removed += len(q.pop_batch(P16, 3))
        else:
            removed += len(q.pop_all())
        assert 0 <= q.depth <= bound
        assert q.depth == admitted - removed
        assert q.tenant_depth("t") == q.depth


def test_ticket_resolves_once_and_get_raises_typed():
    t = Ticket()
    t.reject(Overloaded("full", tenant="a", depth=1, bound=1))
    assert t.done
    with pytest.raises(Overloaded):
        t.get()
    t2 = Ticket()
    t2.resolve("r")
    assert t2.get() == "r"


# ---------------------------------------------------------------------------
# continuous batcher: serving, accounting, shutdown
# ---------------------------------------------------------------------------

def test_batcher_serves_and_accounts(ds, ivf):
    b = ContinuousBatcher(ivf, _tenants(TenantSpec("a")),
                          max_batch=MAX_BATCH, max_queue=64)
    tks = [b.submit(ds.queries[i % N_QUERY], "a") for i in range(20)]
    served = b.drain()
    assert served == 20 and b.pending() == 0
    found = np.stack([t.get().ids for t in tks])
    assert found.shape == (20, 10)
    rec = recall_at_k(found[:N_QUERY], ds.gt[:20], 10)
    assert rec > 0.5                 # real answers, not padding rows
    tot = b.telemetry.totals()
    assert tot.admitted == tot.served == 20
    assert tot.accounted()
    # queue-wait/compute/total histograms all saw every request
    assert tot.queue_wait.count == tot.compute.count == 20


def test_batcher_close_drain_serves_everything_admitted(ds, ivf):
    b = ContinuousBatcher(ivf, _tenants(TenantSpec("a")),
                          max_batch=MAX_BATCH, max_queue=64)
    tks = [b.submit(ds.queries[i % N_QUERY], "a") for i in range(13)]
    served = b.close(drain=True)
    assert served == 13
    assert all(t.done and t.error is None for t in tks)
    with pytest.raises(ServerClosed):            # post-close admission
        b.submit(ds.queries[0], "a")
    tot = b.telemetry.totals()
    assert tot.accounted() and tot.shed_closed == 0


def test_batcher_close_nodrain_rejects_typed(ds, ivf):
    b = ContinuousBatcher(ivf, _tenants(TenantSpec("a")),
                          max_batch=MAX_BATCH, max_queue=64)
    tks = [b.submit(ds.queries[i % N_QUERY], "a") for i in range(5)]
    b.close(drain=False)
    for t in tks:
        assert t.done
        with pytest.raises(ServerClosed):
            t.get()
    tot = b.telemetry.totals()
    assert tot.shed_closed == 5 and tot.served == 0
    assert tot.accounted()


class _HostOnlyArray:
    """Stands in for a device array: converts to numpy but refuses
    device-side slicing — ``execute_search_batch`` must slice pad rows
    off on the host (a device slice dispatches, and on first use
    compiles, a lax.slice per distinct partial-batch size, stalling the
    serve loop whenever a new size shows up under load)."""

    def __init__(self, a):
        self._a = np.asarray(a)

    def __getitem__(self, key):
        raise AssertionError("result sliced on device, not host")

    def __array__(self, dtype=None):
        a = self._a
        return a.astype(dtype) if dtype is not None else a


def test_execute_search_batch_slices_on_host():
    from types import SimpleNamespace

    from repro.runtime.server import execute_search_batch

    seen = {}

    def fake_search(padded, params):
        seen["shape"] = padded.shape
        ids = np.tile(np.arange(params.k), (len(padded), 1))
        return SimpleNamespace(ids=_HostOnlyArray(ids),
                               dists=_HostOnlyArray(ids.astype(np.float32)))

    ids, dists, compute_s = execute_search_batch(
        fake_search, np.zeros((3, 4), np.float32), P16, max_batch=8)
    assert seen["shape"] == (8, 4)          # padded to the one jit shape
    assert ids.shape == (3, 10) and isinstance(ids, np.ndarray)
    assert dists.shape == (3, 10) and compute_s >= 0.0


def test_failing_batch_rejects_its_tickets(ds, ivf, monkeypatch):
    b = ContinuousBatcher(ivf, _tenants(TenantSpec("a")),
                          max_batch=MAX_BATCH, max_queue=64)
    tks = [b.submit(ds.queries[i], "a") for i in range(3)]

    def boom(*a, **kw):
        raise RuntimeError("device fell over")

    monkeypatch.setattr("repro.serve.scheduler.execute_search_batch", boom)
    with pytest.raises(RuntimeError, match="device fell over"):
        b.step()
    for t in tks:                   # popped tickets resolved, not stranded
        assert t.done
        with pytest.raises(RuntimeError, match="device fell over"):
            t.get()
    assert b.telemetry.totals().accounted()


def test_serve_loop_failure_rejects_queue_typed(ds, ivf, monkeypatch):
    async def main():
        tier = AsyncServeTier(ivf, _tenants(TenantSpec("a")),
                              max_batch=4, max_queue=64)
        tier.start()

        def boom(*a, **kw):
            raise RuntimeError("device fell over")

        monkeypatch.setattr(
            "repro.serve.scheduler.execute_search_batch", boom)
        futs = [tier.submit(ds.queries[i], "a") for i in range(6)]
        res = await asyncio.gather(*futs, return_exceptions=True)
        # the batch that ran gets the real error; the rest of the queue
        # is rejected typed when the serve loop dies — nothing hangs
        kinds = {type(r) for r in res}
        assert kinds <= {RuntimeError, ServerClosed} and res
        assert all(isinstance(r, BaseException) for r in res)
        with pytest.raises(ServerClosed):       # door is closed now
            tier.submit(ds.queries[0], "a")
        with pytest.raises(RuntimeError, match="device fell over"):
            await tier.close(drain=True)        # close surfaces the crash
        assert tier.telemetry.totals().accounted()

    asyncio.run(main())


def test_batcher_deadline_shed_typed(ds, ivf):
    class FakeClock:
        t = 0.0

        def __call__(self):
            return self.t

    clock = FakeClock()
    b = ContinuousBatcher(ivf, _tenants(TenantSpec("a")),
                          max_batch=MAX_BATCH, max_queue=64, clock=clock)
    live = b.submit(ds.queries[0], "a")                     # no deadline
    doomed = b.submit(ds.queries[1], "a", deadline_ms=10.0)
    clock.t = 1.0                                 # 1s later: 10ms budget gone
    b.step()
    assert doomed.done
    with pytest.raises(DeadlineExceeded) as ei:
        doomed.get()
    assert ei.value.waited_ms == pytest.approx(1000.0)
    assert live.done and live.error is None       # the live one was served
    tot = b.telemetry.totals()
    assert tot.shed_deadline == 1 and tot.served == 1 and tot.accounted()


def test_tenant_default_deadline_applies(ds, ivf):
    class FakeClock:
        t = 0.0

        def __call__(self):
            return self.t

    clock = FakeClock()
    b = ContinuousBatcher(
        ivf, _tenants(TenantSpec("a", deadline_ms=50.0)),
        max_batch=MAX_BATCH, max_queue=64, clock=clock)
    tk = b.submit(ds.queries[0], "a")             # inherits spec deadline
    clock.t = 1.0
    b.step()
    with pytest.raises(DeadlineExceeded):
        tk.get()


# ---------------------------------------------------------------------------
# jit hygiene: continuous batching adds no retrace buckets
# ---------------------------------------------------------------------------

def test_continuous_batching_no_new_jit_buckets(ds, ivf, tmp_path):
    """Mixed partial batches (1..max_batch requests) all pad to the one
    compiled (max_batch, d) bucket at the tenant's params — zero new
    traces once that bucket is warm, profiler on or off.  Under a
    profiler trace each batch is one ``serve.batch`` span carrying its
    batch number and rows, with its five step spans inside it."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from repro.anns.backends.ivf import _ivf_search

    tenants = _tenants(TenantSpec("a"), TenantSpec("b"))
    b = ContinuousBatcher(ivf, tenants, max_batch=MAX_BATCH, max_queue=64)
    b.submit(ds.queries[0], "a")
    b.drain()                                     # warm the batch bucket
    before = _ivf_search._cache_size()
    sizes = (1, 3, 5, 8, 2, 7)                    # every partial-batch size
    with jax.profiler.trace(str(tmp_path)):
        for size in sizes:
            for i in range(size):
                b.submit(ds.queries[i % N_QUERY], "a" if i % 2 else "b")
            b.drain()
    assert _ivf_search._cache_size() - before == 0
    assert b.telemetry.totals().accounted()

    [path] = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                           / "*.xplane.pb"))
    spans = [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("serve.")]
    batches = sorted(s for s in spans if s[0] == "serve.batch")
    assert [s[3]["rows"] for s in batches] == list(sizes)
    assert [s[3]["seq"] for s in batches] == list(range(2, 2 + len(sizes)))
    steps = ["serve.d2h", "serve.deliver", "serve.dispatch", "serve.form",
             "serve.wait"]
    for _, start, end, _ in batches:
        inside = sorted(s[0] for s in spans if s[0] != "serve.batch"
                        and start <= s[1] and s[2] <= end)
        assert inside == steps
    assert len(spans) == len(sizes) * (1 + len(steps))


# ---------------------------------------------------------------------------
# multi-tenancy: shared batches, weighted scheduling, SLO isolation
# ---------------------------------------------------------------------------

def test_tenants_sharing_params_share_one_batch(ds, ivf):
    tenants = _tenants(TenantSpec("a"), TenantSpec("b"))   # same P16 pick
    b = ContinuousBatcher(ivf, tenants, max_batch=MAX_BATCH, max_queue=64)
    for i in range(4):
        b.submit(ds.queries[i], "a")
        b.submit(ds.queries[i], "b")
    assert b.step() == 8                           # one batch, both tenants
    snap = b.telemetry.snapshot()
    assert snap["queue"]["batches"] == 1
    assert snap["tenants"]["a"]["served"] == 4
    assert snap["tenants"]["b"]["served"] == 4


def test_distinct_picks_never_mix_in_a_batch(ds, ivf):
    tenants = {
        **_tenants(TenantSpec("hi"), params=P64),
        **_tenants(TenantSpec("lo"), params=P8),
    }
    b = ContinuousBatcher(ivf, tenants, max_batch=MAX_BATCH, max_queue=64)
    for i in range(6):
        b.submit(ds.queries[i], "hi")
        b.submit(ds.queries[i], "lo")
    while b.pending():
        served = b.step()
        assert served <= 6        # a params-group holds one tenant's 6 max
    assert b.telemetry.snapshot()["queue"]["batches"] == 2


def test_weighted_stride_scheduling_ratio(ds, ivf):
    """Weight-4 tenant gets ~4x the service rate of a weight-1 tenant
    under contention (distinct groups, so batches can't be shared)."""
    tenants = {
        **_tenants(TenantSpec("a", weight=4.0), params=P16),
        **_tenants(TenantSpec("b", weight=1.0), params=P8),
    }
    b = ContinuousBatcher(ivf, tenants, max_batch=4, max_queue=128)
    for i in range(40):
        b.submit(ds.queries[i % N_QUERY], "a")
        b.submit(ds.queries[i % N_QUERY], "b")
    while tenants["a"].served < 40:
        b.step()
    # when A's 40 finish, stride scheduling has given B at most ~1/4 as
    # much service (one 4-slot batch of slack)
    assert tenants["b"].served <= 40 / 4 + 4
    b.close(drain=True)
    assert b.telemetry.totals().accounted()


def test_slo_isolation_lax_flood_cannot_dilute_strict_recall(ds, ivf):
    """The structural isolation claim: a lax tenant flooding the queue
    delays a strict tenant but can never pull its recall down, because
    batches never mix operating points."""
    tenants = {
        **_tenants(TenantSpec("strict", 0.9), params=P64),
        **_tenants(TenantSpec("lax", 0.5, weight=8.0), params=P8),
    }
    b = ContinuousBatcher(ivf, tenants, max_batch=MAX_BATCH,
                          max_queue=256)
    rng = np.random.default_rng(0)
    strict_tks = []
    for i in range(N_QUERY):
        for _ in range(4):        # 4:1 lax flood around every strict query
            b.submit(ds.queries[int(rng.integers(N_QUERY))], "lax")
        strict_tks.append(b.submit(ds.queries[i], "strict"))
    b.close(drain=True)
    found = np.stack([t.get().ids for t in strict_tks])
    rec = recall_at_k(found, ds.gt, 10)
    assert rec >= 0.9, f"strict recall {rec} diluted by lax flood"
    assert b.telemetry.totals().accounted()


# ---------------------------------------------------------------------------
# async tier e2e (in-process)
# ---------------------------------------------------------------------------

def test_async_overload_burst_is_deterministic_and_typed(ds, ivf):
    """Submitting before the serve loop starts makes overload exact:
    max_queue admitted, the rest typed Overloaded — then every admitted
    request is served on drain and the depth gauge never passed the
    bound."""
    max_queue = 16

    async def episode():
        tier = AsyncServeTier(ivf, _tenants(TenantSpec("a")),
                              max_batch=MAX_BATCH, max_queue=max_queue)
        futs, overloaded = [], 0
        for i in range(3 * max_queue):
            try:
                futs.append(tier.submit(ds.queries[i % N_QUERY], "a"))
            except Overloaded:
                overloaded += 1
        assert len(futs) == max_queue
        assert overloaded == 2 * max_queue
        tier.start()
        res = await asyncio.gather(*futs)
        assert len(res) == max_queue
        assert all(r.ids.shape == (10,) for r in res)
        await tier.close(drain=True)
        return tier

    tier = asyncio.run(episode())
    snap = tier.telemetry.snapshot()
    assert snap["queue"]["depth_max"] <= max_queue
    tot = tier.telemetry.totals()
    assert tot.served == max_queue
    assert tot.shed_overload == 2 * max_queue
    assert tot.accounted()


def test_async_deadline_shed_returns_typed_rejection(ds, ivf):
    async def episode():
        tier = AsyncServeTier(ivf, _tenants(TenantSpec("a")),
                              max_batch=MAX_BATCH, max_queue=64)
        # sub-microsecond deadlines: expired before any batch can form
        futs = [tier.submit(ds.queries[i], "a", deadline_ms=1e-4)
                for i in range(6)]
        tier.start()
        res = await asyncio.gather(*futs, return_exceptions=True)
        await tier.close(drain=True)
        assert all(isinstance(r, DeadlineExceeded) for r in res)
        assert all(r.tenant == "a" for r in res)
        return tier

    tier = asyncio.run(episode())
    tot = tier.telemetry.totals()
    assert tot.shed_deadline == 6 and tot.served == 0 and tot.accounted()


def test_async_mixed_tenants_under_load(ds, ivf):
    """Both tenants' traffic through one tier concurrently: everything
    admitted is served, recall per tenant reflects its own params."""
    tenants = {
        **_tenants(TenantSpec("hi", 0.9), params=P64),
        **_tenants(TenantSpec("lo", 0.5), params=P16),
    }

    async def episode():
        tier = AsyncServeTier(ivf, tenants, max_batch=MAX_BATCH,
                              max_queue=128)
        tier.start()
        futs = {"hi": [], "lo": []}
        for i in range(N_QUERY):
            futs["hi"].append(tier.submit(ds.queries[i], "hi"))
            futs["lo"].append(tier.submit(ds.queries[i], "lo"))
        out = {n: await asyncio.gather(*fs) for n, fs in futs.items()}
        await tier.close(drain=True)
        return tier, out

    tier, out = asyncio.run(episode())
    for name in ("hi", "lo"):
        found = np.stack([r.ids for r in out[name]])
        rec = recall_at_k(found, ds.gt, 10)
        assert rec >= (0.9 if name == "hi" else 0.5)
    assert tier.telemetry.totals().accounted()


# ---------------------------------------------------------------------------
# subprocess e2e: the scripted multi-tenant episode
# ---------------------------------------------------------------------------

def _serve(args, timeout=600):
    env = dict(os.environ, PYTHONPATH=SRC)
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", *args],
        env=env, capture_output=True, text=True, timeout=timeout)


def test_serve_async_multitenant_subprocess():
    r = _serve(["--backend", "ivf", "--nlist", "16", "--n-base", "800",
                "--n-query", "48", "--tune", "--tune-ef-cap", "64",
                "--async", "--tenants", "strict:0.9:4,lax:0.7",
                "--max-queue", "32", "--max-batch", "16", "--k", "10"])
    assert r.returncode == 0, r.stderr
    out = r.stdout
    # deterministic overload: exactly max_queue admitted, 2x shed typed
    assert re.search(r"serve: overload burst admitted=32 shed=64 "
                     r"\(typed Overloaded\)", out), out
    # every tenant's measured recall meets its own SLO
    for name, target in (("strict", 0.9), ("lax", 0.7)):
        m = re.search(rf"serve: tenant {name} recall=([\d.]+) "
                      rf"target=([\d.]+) (ok|MISS)", out)
        assert m, out
        assert float(m.group(1)) >= target and m.group(3) == "ok", out
    assert "serve: accounting ok" in out, out
    assert "serve: episode ok" in out, out
    # graceful close: nothing silently dropped
    m = re.search(r"serve: closed served=(\d+) shed_overload=(\d+) "
                  r"shed_deadline=(\d+) shed_closed=(\d+)", out)
    assert m, out
    assert int(m.group(4)) == 0                   # drain served the queue


def test_serve_async_flag_validation():
    r = _serve(["--tenants", "a:0.9"])            # --tenants without --async
    assert r.returncode != 0
    assert "--async" in r.stderr
    r = _serve(["--async", "--tenants", "a:0.9"])  # no frontier source
    assert r.returncode != 0
    assert "frontier" in r.stderr
    r = _serve(["--max-queue", "8"])              # --max-queue sans --async
    assert r.returncode != 0


# ---------------------------------------------------------------------------
# served-recall accounting: sheds must not shift rows onto the wrong gt
# ---------------------------------------------------------------------------

def test_served_recall_scores_responses_against_their_own_gt_rows():
    """Pure accounting check: with response 1 shed, responses for
    queries 0 and 2 must score against gt rows 0 and 2 — the old
    ``gt[:n_ok]`` form scored the second response against row 1."""
    from repro.launch.serve import served_recall

    gt = np.asarray([[10, 11], [20, 21], [30, 31]])
    found = [np.asarray([10, 11]), np.asarray([30, 31])]  # query 1 shed
    assert served_recall(found, [0, 2], gt, 2) == 1.0
    # the naive prefix alignment calls the same episode half wrong
    assert recall_at_k(np.stack(found), gt[:2], 2) == 0.5
    assert np.isnan(served_recall([], [], gt, 2))   # fully shed: no sample


def test_mid_stream_shed_does_not_shift_recall_rows(ds, ivf):
    """Regression through the real batcher: force one deadline shed in
    the middle of a stream and check the served-index bookkeeping keeps
    every later response on its own ground-truth row."""
    from repro.launch.serve import served_recall

    class FakeClock:
        t = 0.0

        def __call__(self):
            return self.t

    clock = FakeClock()
    b = ContinuousBatcher(ivf, _tenants(TenantSpec("a")),
                          max_batch=MAX_BATCH, max_queue=64, clock=clock)
    n, shed_at = 6, 2
    toks = [(i, b.submit(ds.queries[i], "a",
                         deadline_ms=10.0 if i == shed_at else None))
            for i in range(n)]
    clock.t = 1.0            # the 10ms budget expires before any batch runs
    while any(not tk.done for _, tk in toks):
        b.step()

    found, served = [], []
    for i, tk in toks:
        try:
            r = tk.get()
        except ServeRejection:
            continue
        found.append(np.asarray(r.ids))
        served.append(i)
    assert served == [i for i in range(n) if i != shed_at]
    rec = served_recall(found, served, ds.gt, 10)
    assert rec == pytest.approx(recall_at_k(
        np.stack(found), np.asarray(ds.gt)[np.asarray(served)], 10))
    # the pre-fix scoring—stack and compare against gt[:n_ok]—drags
    # every post-shed response onto the previous query's gt row
    naive = recall_at_k(np.stack(found), np.asarray(ds.gt)[:len(found)], 10)
    assert rec > naive + 0.3
    tot = b.telemetry.totals()
    assert tot.shed_deadline == 1 and tot.served == n - 1
