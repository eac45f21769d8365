"""Batched serving loops.

``AnnsServer`` — dynamic-batching front for the ANNS engine: requests are
coalesced up to ``max_batch`` (padding to the jitted batch shape so one
compiled search serves any load level), the paper's "batch processing
amortises memory access" refinement at the serving layer.

The batch-forming core — query validation, the ladder-snapped batch-``k``
policy, and the pad-search-slice execution step — lives in module
functions (:func:`validate_query`, :func:`batch_k_policy`,
:func:`execute_search_batch`) shared with the async multi-tenant tier
(:mod:`repro.serve.scheduler`), so both serving fronts form bit-identical
batches against the same jit buckets.

``GenerateServer`` — prefill+decode service for the policy LM (the shape
the ``decode_*`` dry-run cells lower).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.anns.api import (EF_LADDER, SearchParams, round_ef,
                            snap_down_to_ladder)
from repro.anns.engine import Engine


@dataclass
class AnnsRequest:
    query: np.ndarray          # (d,)
    k: int = 10
    t_submit: float = field(default_factory=time.perf_counter)


@dataclass
class AnnsResponse:
    ids: np.ndarray
    dists: np.ndarray
    latency_ms: float


# ---------------------------------------------------------------------------
# batch-forming core (shared with repro.serve.scheduler)
# ---------------------------------------------------------------------------

def search_callable(target):
    """The batched-search entry point of an Engine facade or a bare
    AnnsIndex backend."""
    return target.query if isinstance(target, Engine) else target.search


def index_size(target) -> int | None:
    """Vectors currently searchable on ``target`` (Engine or backend).

    Re-read per batch, never cached: a streaming backend mutates
    mid-session, so a size captured at construction would clamp ``k``
    against stale ``n``.
    """
    idx = getattr(target, "index", None)
    if idx is None:
        return None
    backend = target.backend if isinstance(target, Engine) else target
    n_live = getattr(backend, "n_live", None)   # mutable backends
    if callable(n_live):
        return int(n_live())
    n = getattr(idx, "n", None)                 # GraphIndex / IvfIndex
    if n is not None:
        return int(n)
    shape = getattr(idx, "shape", None)         # raw base matrix
    return int(shape[0]) if shape else None


def index_dim(target) -> int | None:
    """Vector dimensionality of ``target``'s built index, or None when
    nothing is built yet (validation then falls back to shape checks
    only)."""
    idx = getattr(target, "index", None)
    if idx is None:
        return None
    for attr in ("base", "centroids"):          # graph/ivf, sharded
        arr = getattr(idx, attr, None)
        if arr is not None and getattr(arr, "ndim", 0) >= 2:
            return int(arr.shape[-1])
    shape = getattr(idx, "shape", None)         # raw base matrix
    return int(shape[1]) if shape and len(shape) == 2 else None


def validate_query(query, dim: int | None = None) -> np.ndarray:
    """Fail fast on a malformed query at submit time.

    A wrong shape or dtype used to surface only inside ``flush`` as an
    opaque ``np.stack`` / dtype-cast crash, long after the caller's
    frame was gone.  Accepted: a 1-D numeric ``(d,)`` vector whose ``d``
    matches the index dimensionality (when an index is built).
    """
    q = np.asarray(query)
    if q.dtype == object or not np.issubdtype(q.dtype, np.number):
        raise TypeError(
            f"query dtype {q.dtype} is not numeric — pass a float "
            f"vector (it is cast to float32 at batch time)")
    if q.ndim != 1:
        hint = (" (a single-row matrix: pass query[0])"
                if q.ndim == 2 and q.shape[0] == 1 else "")
        raise ValueError(
            f"query must be a 1-D (d,) vector, got shape {q.shape}{hint}")
    if dim is not None and q.shape[0] != dim:
        raise ValueError(
            f"query has dim {q.shape[0]} but the index holds "
            f"{dim}-dimensional vectors")
    return q


def batch_k_policy(k_default: int, kmax: int, n: int | None) -> int:
    """The ``k`` one batch is searched at, always on the static ladder.

    Heterogeneous-k traffic searches at the largest requested ``k``
    (rounded up onto :data:`~repro.anns.api.EF_LADDER` so mixed loads
    reuse compiled traces); an index holding fewer than that many
    vectors clamps the result, and the clamp snaps *down* onto the
    ladder — a raw ``min(k, n)`` lands off-ladder and mints a fresh jit
    trace per distinct live ``n`` on mutable backends.
    """
    k = k_default if kmax <= k_default else round_ef(kmax)
    if n is not None and k > n:
        k = snap_down_to_ladder(n, EF_LADDER)
    return max(1, k)


def execute_search_batch(search_fn, queries: np.ndarray,
                         params: SearchParams, *, max_batch: int):
    """Pad one (b, d) query block to the jitted ``max_batch`` shape, run
    the batched search, and block until results are ready.

    Returns ``(ids, dists, compute_s)`` with the pad rows already sliced
    off — ``compute_s`` is the wall-clock of the search itself, the
    number the queue-wait/compute latency split is built from.

    Three profiler spans cover it: ``serve.dispatch`` (the pad, the
    query upload and the jit dispatch), ``serve.wait`` (the device
    finishing) and ``serve.d2h`` (the copies back to the host).
    """
    b, d = queries.shape
    if b > max_batch:
        raise ValueError(f"batch of {b} exceeds max_batch={max_batch}")
    with TraceAnnotation("serve.dispatch"):
        padded = queries.astype(np.float32, copy=False)
        if b < max_batch:
            padded = np.concatenate(
                [padded, np.zeros((max_batch - b, d), np.float32)], axis=0)
        t0 = time.perf_counter()
        res = search_fn(padded, params)
    with TraceAnnotation("serve.wait"):
        jax.block_until_ready(res.ids)
    compute_s = time.perf_counter() - t0
    # slice the pad rows off on the host: slicing the device array would
    # dispatch (and on first use, compile) a lax.slice per distinct b,
    # stalling the serve loop ~tens of ms whenever a new partial-batch
    # size shows up under load
    with TraceAnnotation("serve.d2h"):
        ids, dists = np.asarray(res.ids)[:b], np.asarray(res.dists)[:b]
    return ids, dists, compute_s


class AnnsServer:
    """Dynamic-batching ANNS front.

    Two ways to fix the operating point:

    - **hand-picked** — pass ``params`` (or legacy ``ef``/``k``), the
      operator owns the recall/speed trade.
    - **SLO mode** — pass ``slo=RecallSLO(...)`` plus a swept
      ``frontier`` (:mod:`repro.anns.tune`): the server solves max-QPS
      s.t. the SLO *for the backend it actually holds* and serves at
      that pick, with ``ef`` re-snapped onto the backend's static ladder
      (:func:`repro.anns.api.search_ef_ladder` membership, else
      :func:`~repro.anns.api.round_ef`) so SLO serving never creates a
      jit retrace bucket the sweep didn't already compile.  An
      infeasible SLO raises at construction — a server that cannot hold
      its recall target must not come up quietly.  The resolved pick is
      kept on ``self.operating_point`` (expected recall/QPS telemetry).
    """

    def __init__(self, engine: Engine, *, max_batch: int = 64,
                 ef: int = 64, k: int = 10,
                 params: SearchParams | None = None,
                 slo=None, frontier=None):
        self.engine = engine
        self.max_batch = max_batch
        self.slo = slo
        self.operating_point = None
        if slo is not None:
            if params is not None:
                raise ValueError(
                    "pass either slo (frontier-driven params) or explicit "
                    "params, not both")
            if frontier is None:
                raise ValueError(
                    "slo mode needs a swept frontier (repro.anns.tune."
                    "sweep_frontier / ckpt.load_frontier) to choose from")
            self.operating_point = self._pick(slo, frontier)
            self.params = self.operating_point.params
        else:
            self.params = params or SearchParams(k=k, ef=ef)
        self.queue: list[AnnsRequest] = []
        self.served = 0
        self.drift_monitor = None
        self.compactor = None

    @property
    def backend(self):
        """The bare AnnsIndex behind this server (unwraps the Engine
        facade) — mutation and telemetry hooks talk to this."""
        return (self.engine.backend if isinstance(self.engine, Engine)
                else self.engine)

    def _snap_point(self, point):
        """``ef`` re-snapped onto the served backend's static ladder."""
        from repro.anns.tune import snap_point_for_backend

        return snap_point_for_backend(point, self.backend)

    def _pick(self, slo, frontier):
        """Constrained choice restricted to the served backend, ef
        re-snapped onto its static ladder."""
        from repro.anns.tune import choose

        point = choose(frontier, slo,
                       backend=getattr(self.backend, "name", None))
        return self._snap_point(point)

    def attach_drift_monitor(self, monitor) -> None:
        """Watch served telemetry with a
        :class:`repro.anns.tune.DriftMonitor` (fed via
        :meth:`observe_served`)."""
        self.drift_monitor = monitor
        if self.compactor is not None:
            self.compactor.attach_monitor(monitor)

    def attach_compactor(self, compactor) -> None:
        """Let tail-trigger drift verdicts schedule background
        compaction (:class:`repro.anns.stream.BackgroundCompactor`)
        instead of leaving the caller to run ``compact()`` inline.  The
        attached drift monitor registers for in-flight suppression, and
        — unless the compactor already has a warm spec — the post-swap
        search program is warmed at this server's batch shape and
        current params, so the first post-swap flush doesn't pay the
        recompile."""
        self.compactor = compactor
        if self.drift_monitor is not None:
            compactor.attach_monitor(self.drift_monitor)
        if compactor.warm is None:
            def _warm_spec():
                d = index_dim(self.engine)
                if d is None:
                    return []
                return [(np.zeros((self.max_batch, d), np.float32),
                         self.params)]
            compactor.warm = _warm_spec

    def observe_served(self, *, recall: float, latency_ms: float | None = None):
        """Fold one served window's measured telemetry into the attached
        drift monitor; the backend's live tail fraction rides along when
        the backend is mutable.  Returns the monitor's
        :class:`~repro.anns.tune.DriftVerdict` (None when no monitor).
        A ``tail_frac`` verdict schedules the attached background
        compactor (when one is attached) — the serving driver no longer
        calls ``compact()`` itself."""
        if self.drift_monitor is None:
            return None
        tail_fn = getattr(self.backend, "tail_fraction", None)
        tail = float(tail_fn()) if callable(tail_fn) else 0.0
        verdict = self.drift_monitor.observe(
            recall=recall, latency_ms=latency_ms, tail_fraction=tail)
        if self.compactor is not None:
            self.compactor.maybe_compact(verdict)
        return verdict

    def apply_operating_point(self, point) -> None:
        """Adopt a re-chosen operating point mid-session (post-retune):
        params snap onto the ladder, and the drift monitor — if any —
        rebases so stale EWMAs don't immediately re-trigger."""
        point = self._snap_point(point)
        self.operating_point = point
        self.params = point.params
        if self.drift_monitor is not None:
            self.drift_monitor.rebase(point)

    # legacy attribute views of the typed params
    @property
    def ef(self) -> int:
        return self.params.ef

    @property
    def k(self) -> int:
        return self.params.k

    def submit(self, query: np.ndarray, k: int | None = None):
        if k is None:
            k = self.params.k
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        if self.params.filter is not None:
            # typed fail-fast at submit time: an unfilterable backend
            # (no attribute columns / unknown attr) must not surface as
            # an opaque crash inside the jitted flush
            from repro.anns.filters import require_filterable
            require_filterable(self.params.filter,
                               getattr(self.backend, "attributes", None))
        self.queue.append(AnnsRequest(validate_query(
            query, index_dim(self.engine)), k))

    def _index_size(self) -> int | None:
        return index_size(self.engine)

    def flush(self) -> list[AnnsResponse]:
        """Serve up to max_batch queued requests in one jitted search.

        The batch is searched at the *largest* k any request asked for
        (bucketed onto the static ladder so heterogeneous-k traffic reuses
        compiled traces, and ladder-clamped to the live index size —
        :func:`batch_k_policy`), then each response is sliced down to its
        own ``r.k`` — a request may ask for more neighbors than the server
        default without getting silently truncated results.
        """
        if not self.queue:
            return []
        batch, self.queue = self.queue[: self.max_batch], self.queue[self.max_batch:]
        queries = np.stack([r.query for r in batch]).astype(np.float32)
        k_search = batch_k_policy(self.params.k,
                                  max(r.k for r in batch),
                                  self._index_size())
        ids, dists, _ = execute_search_batch(
            search_callable(self.engine), queries,
            self.params.replace(k=k_search), max_batch=self.max_batch)
        now = time.perf_counter()
        out = []
        for i, r in enumerate(batch):
            out.append(AnnsResponse(
                ids=ids[i, : r.k],
                dists=dists[i, : r.k],
                latency_ms=1e3 * (now - r.t_submit)))
        self.served += len(batch)
        return out

    def run(self, drain: bool = True) -> list[AnnsResponse]:
        out = []
        while self.queue:
            out.extend(self.flush())
            if not drain:
                break
        return out


class GenerateServer:
    """Static-batch text generation over the policy LM: one fixed (B, T)
    prompt batch prefilled together and decoded in lockstep for
    ``n_steps`` — requests neither join nor leave mid-flight, so a short
    completion waits for the longest one in its batch.  (This is *not*
    continuous batching; the real continuous batcher — requests
    coalesced into in-flight compiled buckets as capacity frees up —
    is the ANNS serving tier's
    :class:`repro.serve.scheduler.ContinuousBatcher`.)"""

    def __init__(self, cfg, params, rt, *, batch: int, max_seq: int):
        from repro.models import model as model_lib
        self.model = model_lib
        self.cfg, self.params, self.rt = cfg, params, rt
        self.batch, self.max_seq = batch, max_seq

    def generate(self, prompts: np.ndarray, n_steps: int,
                 temperature: float = 0.0, key=None):
        """prompts: (B, T) int32 -> (B, n_steps) greedy/sampled tokens."""
        m, cfg, rt = self.model, self.cfg, self.rt
        B, T = prompts.shape
        caches = m.init_cache(cfg, B, self.max_seq)
        logits, caches, clen = m.prefill(
            self.params, {"tokens": jnp.asarray(prompts)}, cfg, rt, caches)
        toks = []
        for i in range(n_steps):
            if temperature <= 0:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(
                    sub, logits / temperature, axis=-1).astype(jnp.int32)
            toks.append(nxt)
            logits, caches, clen = m.decode_step(
                self.params, {"tokens": nxt[:, None]}, cfg, rt, caches, clen)
        return np.stack([np.asarray(t) for t in toks], axis=1)
