"""Lockstep batched beam search over the flat graph.

One jitted ``lax.while_loop`` advances the whole query batch together —
the TPU analogue of the paper's search module, with its three RL-discovered
optimizations as knobs:

- ``gather_width`` (g): expand the g closest unexplored beam entries per
  step — dense (g*R)-wide neighbor gathers amortise HBM latency, playing
  the role of the paper's multi-level prefetching (§6.2 "batch processing
  with adaptive prefetching").
- multi-entry initialisation (§6.2 "multi-tier entry point selection").
- ``patience``: early termination on no-improvement rounds (§6.2
  "intelligent early termination with convergence detection").

The refinement module's quantized preliminary search (§2.3/§6.3) runs the
traversal on int8 dequantised distances and reranks the top
``rerank_factor * k`` in fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.anns.api import round_steps
from repro.anns.graph import GraphIndex

BIG = 3.0e38


def _qdist(q: jax.Array, vecs: jax.Array, metric: str, *,
           quantized: bool = False) -> jax.Array:
    """(B, d) x (B, C, d) -> (B, C) distances, smaller = closer.

    fp32 vectors score at ``Precision.HIGHEST``: on a TPU a float32 dot at
    default precision may run as a single bf16 pass, and the exact stages
    (fp32 scans, reranks, the delta tail) must rank as the CPU does.
    Dequantised int8 codes (``quantized=True``) are already approximate
    and keep the default precision."""
    precision = None if quantized else jax.lax.Precision.HIGHEST
    dots = jnp.einsum("bd,bcd->bc", q, vecs, precision=precision,
                      preferred_element_type=jnp.float32)
    if metric == "ip":
        return -dots
    qn = jnp.sum(q * q, axis=-1)[:, None]
    vn = jnp.sum(vecs.astype(jnp.float32) ** 2, axis=-1)
    return qn + vn - 2.0 * dots


@functools.partial(jax.jit, static_argnames=(
    "ef", "k", "gather_width", "patience", "max_steps", "metric",
    "quantized", "rerank", "n", "r", "record_trail"))
def _beam_search(
    neighbors, base, base_q, scales, entry_points, queries, *,
    ef: int, k: int, gather_width: int, patience: int, max_steps: int,
    metric: str, quantized: bool, rerank: int, n: int, r: int,
    record_trail: bool = False,
):
    B, d = queries.shape
    g = gather_width
    E = entry_points.shape[0]
    q32 = queries.astype(jnp.float32)

    # --- initialise beam with entry points ------------------------------
    init_ids = jnp.broadcast_to(entry_points[None, :], (B, E))
    if quantized:
        vecs0 = base_q[init_ids].astype(jnp.float32) * scales[init_ids][..., None]
    else:
        vecs0 = base[init_ids]
    d0 = _qdist(q32, vecs0, metric, quantized=quantized)

    pad = ef - E
    beam_ids = jnp.concatenate(
        [init_ids, jnp.zeros((B, pad), jnp.int32)], axis=1)
    beam_d = jnp.concatenate([d0, jnp.full((B, pad), BIG)], axis=1)
    order = jnp.argsort(beam_d, axis=1)
    beam_ids = jnp.take_along_axis(beam_ids, order, axis=1)
    beam_d = jnp.take_along_axis(beam_d, order, axis=1)
    explored = beam_d >= BIG            # padding counts as explored

    visited = jnp.zeros((B, n), bool)
    visited = visited.at[jnp.arange(B)[:, None], init_ids].set(True)

    state = dict(
        beam_ids=beam_ids, beam_d=beam_d, explored=explored, visited=visited,
        no_improve=jnp.zeros((B,), jnp.int32),
        active=jnp.ones((B,), bool),
        steps=jnp.zeros((), jnp.int32),
        expansions=jnp.zeros((), jnp.int32),
    )
    if record_trail:
        # the greedy path (entry -> ... -> target region): Vamana's prune
        # candidates; long-range hops live here, not in the final beam.
        state["trail"] = jnp.full((B, max_steps * g), -1, jnp.int32)

    def cond(s):
        return jnp.any(s["active"]) & (s["steps"] < max_steps)

    def body(s):
        beam_ids, beam_d = s["beam_ids"], s["beam_d"]
        explored, visited = s["explored"], s["visited"]

        # 1. pick g closest unexplored beam slots
        score = jnp.where(explored, BIG, beam_d)
        _, slots = jax.lax.top_k(-score, g)                  # (B, g)
        frontier_d = jnp.take_along_axis(score, slots, axis=1)
        has_work = frontier_d[:, 0] < BIG
        explored = explored.at[jnp.arange(B)[:, None], slots].set(True)
        exp_ids = jnp.take_along_axis(beam_ids, slots, axis=1)   # (B, g)

        # 2. gather neighbors, dedup within step + vs visited
        cand = neighbors[exp_ids].reshape(B, g * r)
        cand = jnp.sort(cand, axis=1)
        dup = jnp.concatenate(
            [jnp.zeros((B, 1), bool), cand[:, 1:] == cand[:, :-1]], axis=1)
        seen = jnp.take_along_axis(visited, cand, axis=1)
        fresh = (~dup) & (~seen)
        visited = visited.at[jnp.arange(B)[:, None], cand].set(True)

        # 3. distances (quantized prefilter or fp32)
        if quantized:
            vecs = base_q[cand].astype(jnp.float32) * scales[cand][..., None]
        else:
            vecs = base[cand]
        dc = _qdist(q32, vecs, metric, quantized=quantized)
        dc = jnp.where(fresh, dc, BIG)

        # 4. merge into beam
        all_ids = jnp.concatenate([beam_ids, cand], axis=1)
        all_d = jnp.concatenate([beam_d, dc], axis=1)
        all_exp = jnp.concatenate(
            [explored, jnp.zeros((B, g * r), bool)], axis=1)
        _, keep = jax.lax.top_k(-all_d, ef)
        nb_ids = jnp.take_along_axis(all_ids, keep, axis=1)
        nb_d = jnp.take_along_axis(all_d, keep, axis=1)
        nb_exp = jnp.take_along_axis(all_exp, keep, axis=1)

        # 5. convergence detection (paper §6.2)
        improved = nb_d[:, k - 1] < beam_d[:, k - 1]
        no_improve = jnp.where(improved, 0, s["no_improve"] + 1)

        # 6. classic HNSW stop + patience
        next_score = jnp.where(nb_exp, BIG, nb_d)
        best_unexplored = jnp.min(next_score, axis=1)
        active = (best_unexplored < nb_d[:, ef - 1]) & has_work
        if patience > 0:
            active &= no_improve <= patience

        upd = s["active"]
        out = dict(
            beam_ids=jnp.where(upd[:, None], nb_ids, beam_ids),
            beam_d=jnp.where(upd[:, None], nb_d, beam_d),
            explored=jnp.where(upd[:, None], nb_exp, explored),
            visited=jnp.where(upd[:, None], visited, s["visited"]),
            no_improve=jnp.where(upd, no_improve, s["no_improve"]),
            active=s["active"] & active,
            steps=s["steps"] + 1,
            expansions=s["expansions"] + jnp.sum(upd),
        )
        if record_trail:
            marked = jnp.where(upd[:, None], exp_ids, -1)
            out["trail"] = jax.lax.dynamic_update_slice(
                s["trail"], marked, (0, s["steps"] * g))
        return out

    final = jax.lax.while_loop(cond, body, state)
    beam_ids, beam_d = final["beam_ids"], final["beam_d"]

    if record_trail:
        return beam_ids, beam_d, final["trail"]

    if quantized and rerank > 0:
        # fp32 rerank of the quantized-order top rerank*k
        m = min(rerank * k, ef)
        top_ids = beam_ids[:, :m]
        dr = _qdist(q32, base[top_ids], metric)
        _, order = jax.lax.top_k(-dr, k)
        out_ids = jnp.take_along_axis(top_ids, order, axis=1)
        out_d = jnp.take_along_axis(dr, order, axis=1)
    else:
        out_ids = beam_ids[:, :k]
        out_d = beam_d[:, :k]
    return out_ids, out_d, final["steps"], final["expansions"]


def search(index: GraphIndex, queries: jax.Array, *, ef: int, k: int,
           gather_width: int = 1, patience: int = 0,
           quantized: bool = False, rerank: int = 2,
           max_steps: int | None = None):
    """Public batched k-NN search. Returns (ids (B,k), dists, steps, expansions)."""
    ef = max(ef, k, index.entry_points.shape[0])
    if max_steps is None:
        # bucket the derived step cap onto a static ladder: max_steps is a
        # static argname of the jitted search, and the while_loop exits
        # early via the active mask, so a rounded-up cap changes nothing
        # for converged searches but collapses jit traces across sweeps.
        max_steps = round_steps(4 * ef // max(1, gather_width) + 16)
    quantized = quantized and index.base_q is not None
    return _beam_search(
        index.neighbors, index.base, index.base_q, index.scales,
        index.entry_points, queries,
        ef=ef, k=k, gather_width=gather_width, patience=patience,
        max_steps=max_steps, metric=index.metric, quantized=quantized,
        rerank=rerank, n=index.n, r=index.degree)
