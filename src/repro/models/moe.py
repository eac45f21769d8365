"""Token-choice top-k MoE with capacity-based scatter dispatch.

Two execution paths:

- **local** (no mesh): plain scatter/gather dispatch; used by CPU smoke
  tests and single-device runs.
- **EP over the TP axis** (``shard_map``): activations are replicated over
  the ``model`` axis under tensor parallelism, so each model shard owns
  ``E / tp`` experts, dispatches *all* tokens routed to its local experts,
  and the partial outputs are ``psum``ed over the model axis — the same
  reduction a TP FFN already pays.  No all-to-all is needed in this regime
  (tokens are not sharded over the expert axis); this is the fused TP+EP
  scheme described in DESIGN.md §4.

Dispatch avoids the MaxText-style one-hot einsum (O(T * E * C) memory):
position-within-expert comes from a cumsum over the one-hot assignment
matrix (O(T * k * E) int32, transient) and tokens are scattered into an
(E, C, d) buffer with OOB drop semantics for capacity overflow.  Expert
FLOPs are therefore ``capacity_factor x`` the active FLOPs — the roofline
"useful compute" ratio in EXPERIMENTS.md accounts for this.

Shared experts (deepseek) are mathematically fused into one wider dense
gated FFN (sum of gated experts == concatenated gate/in columns + stacked
out rows) and handled by the caller as a dense FFN.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import Params, pdtype, _act


def init_moe(key: jax.Array, cfg: ModelConfig) -> Params:
    dt = pdtype(cfg)
    d, ff, e = cfg.d_model, cfg.moe_d_ff, cfg.moe_num_experts
    ks = jax.random.split(key, 4)
    return {
        "router": (jax.random.normal(ks[0], (d, e)) * d ** -0.5).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e, d, ff)) * d ** -0.5).astype(dt),
        "w_in":   (jax.random.normal(ks[2], (e, d, ff)) * d ** -0.5).astype(dt),
        "w_out":  (jax.random.normal(ks[3], (e, ff, d)) * ff ** -0.5).astype(dt),
    }


def _capacity(tokens: int, k: int, e: int, factor: float) -> int:
    c = int(tokens * k * factor / e) + 1
    return max(8, ((c + 7) // 8) * 8)


def _route(router: jax.Array, x: jax.Array, k: int):
    """x: (T, d) -> (weights (T,k) fp32, ids (T,k) int32, aux_loss scalar)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    # load-balancing aux loss (Switch-style): E * sum_e f_e * p_e
    e = router.shape[1]
    me = jnp.mean(probs, axis=0)
    f = jnp.mean(jax.nn.one_hot(ids[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(me * f)
    return w, ids, aux


def _dispatch_compute_combine(
    p: Params, x: jax.Array, w: jax.Array, ids: jax.Array,
    cfg: ModelConfig, capacity: int, e_start: int, e_local: int,
) -> jax.Array:
    """Dispatch tokens routed to experts [e_start, e_start+e_local) and
    return the weighted partial output (T, d).  Expert weight tensors in
    ``p`` are the *local* slices (e_local, ...)."""
    T, d = x.shape
    k = ids.shape[1]
    flat_ids = ids.reshape(-1)                         # (T*k,)
    local = flat_ids - e_start                          # local expert index
    in_range = (local >= 0) & (local < e_local)
    local_c = jnp.where(in_range, local, 0)

    # position within expert: rank of this assignment among same-expert ones
    oh = jax.nn.one_hot(local_c, e_local, dtype=jnp.int32) * in_range[:, None]
    pos = jnp.cumsum(oh, axis=0) - 1
    pos = jnp.sum(pos * oh, axis=-1)                    # (T*k,)
    pos = jnp.where(in_range, pos, capacity)            # OOB => dropped

    token_idx = jnp.arange(T * k, dtype=jnp.int32) // k
    buf = jnp.zeros((e_local, capacity, d), x.dtype)
    buf = buf.at[local_c, pos].set(x[token_idx], mode="drop")

    h = _act(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"]), cfg)
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["w_in"])
    y = jnp.einsum("ecf,efd->ecd", h, p["w_out"])       # (e_local, C, d)

    gathered = y.at[local_c, pos].get(mode="fill", fill_value=0)   # (T*k, d)
    wf = (w.reshape(-1) * in_range).astype(y.dtype)
    out = jnp.zeros((T, d), y.dtype).at[token_idx].add(gathered * wf[:, None])
    return out


def apply_moe(
    p: Params,
    x: jax.Array,                       # (B, S, d)
    cfg: ModelConfig,
    *,
    mesh=None,
    ep_axis: str = "model",
    dp_axes=("pod", "data"),
    capacity_factor: float = 1.25,
) -> tuple[jax.Array, jax.Array]:
    """Returns (out (B,S,d), aux_loss scalar fp32)."""
    B, S, d = x.shape
    e, k = cfg.moe_num_experts, cfg.moe_top_k
    xf = x.reshape(B * S, d)

    if mesh is None or ep_axis not in mesh.axis_names:
        w, ids, aux = _route(p["router"], xf, k)
        cap = _capacity(B * S, k, e, capacity_factor)
        out = _dispatch_compute_combine(p, xf, w, ids, cfg, cap, 0, e)
        return out.reshape(B, S, d).astype(x.dtype), aux

    tp = mesh.shape[ep_axis]
    assert e % tp == 0, (cfg.name, e, tp)
    e_local = e // tp
    dp_axes = tuple(a for a in dp_axes if a in mesh.axis_names)

    def shard_fn(router, wg, wi, wo, xl):
        # xl: (B_local*S, d) — batch sharded over dp axes, replicated over model
        Tl = xl.shape[0]
        w, ids, aux = _route(router, xl, k)
        midx = jax.lax.axis_index(ep_axis)
        cap = _capacity(Tl, k, e, capacity_factor)  # per-expert capacity (local experts)
        pl = {"w_gate": wg, "w_in": wi, "w_out": wo}
        out = _dispatch_compute_combine(pl, xl, w, ids, cfg, cap, midx * e_local, e_local)
        out = jax.lax.psum(out, ep_axis)
        aux = jax.lax.pmean(aux, ep_axis)
        return out, aux

    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    if dp_axes and (B * S) % dp_size == 0:
        batch_spec = P(dp_axes if len(dp_axes) > 1 else dp_axes[0], None)
    else:
        batch_spec = P(None, None)   # tiny decode batches: replicate tokens
    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(), P(ep_axis, None, None), P(ep_axis, None, None),
                  P(ep_axis, None, None), batch_spec),
        out_specs=(batch_spec, P()),
        check_vma=False,
    )
    out, aux = fn(p["router"], p["w_gate"], p["w_in"], p["w_out"], xf)
    return out.reshape(B, S, d).astype(x.dtype), aux
