"""Flash-decode over a sequence-sharded KV cache (shard_map combine).

Long-context decode keeps the KV cache sharded over the model axis along
*sequence* (see ``repro.dist.sharding.cache_shardings``): each device
owns a contiguous slice of cache positions.  One decode step is then

1. every device writes the new K/V into its slice iff the write slot
   falls inside it (a positional ``where`` — no gather),
2. every device scores the query against only its resident positions and
   keeps flash-style partial-softmax stats (running max ``m``, normalizer
   ``l``, unnormalised accumulator ``acc``),
3. one ``pmax`` + two ``psum`` over the model axis combine the partials
   exactly — the same online-softmax algebra the chunked attention scan
   uses, so results match the unsharded ``decode_attend`` bit-for-near
   (fp32 reductions reassociate across devices).

The query and output stay replicated over the model axis; only cache
slices and score partials are device-local, so the per-step wire cost is
O(B * Hq * D) regardless of context length — the point of the layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -2.0 ** 30  # matches repro.models.attention masking


def _decode_update_and_attend(q, k_new, v_new, k_cache, v_cache,
                              slot, valid, *, q_scale, softcap,
                              axis: str | None):
    """Core decode step over (a slice of) the cache.  With ``axis`` set
    this runs inside shard_map on a sequence slice and combines partial
    softmax stats over that mesh axis; with ``axis=None`` it is the plain
    single-device decode (the oracle the combine must match)."""
    B, S_loc, Hk, D = k_cache.shape
    Hq = q.shape[2]
    G = Hq // Hk

    off = 0
    if axis is not None:
        off = jax.lax.axis_index(axis) * S_loc
    pos = off + jnp.arange(S_loc, dtype=jnp.int32)          # global positions

    hit = (pos == slot)[None, :, None, None]
    nk = jnp.where(hit, k_new.astype(k_cache.dtype), k_cache)
    nv = jnp.where(hit, v_new.astype(v_cache.dtype), v_cache)

    qg = q.reshape(B, Hk, G, D)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, nk,
                   preferred_element_type=jnp.float32) * q_scale
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    mask = (pos < valid)[None, None, None, :]
    s = jnp.where(mask, s, NEG_INF)

    m_loc = jnp.max(s, axis=-1)                              # (B,Hk,G)
    m = m_loc if axis is None else jax.lax.pmax(m_loc, axis)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(mask, p, 0.0)
    l_loc = jnp.sum(p, axis=-1)
    acc_loc = jnp.einsum("bhgs,bshd->bhgd", p.astype(nv.dtype), nv,
                         preferred_element_type=jnp.float32)
    if axis is None:
        l, acc = l_loc, acc_loc
    else:
        l = jax.lax.psum(l_loc, axis)
        acc = jax.lax.psum(acc_loc, axis)
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return o.reshape(B, 1, Hq, D).astype(q.dtype), nk, nv


def seq_sharded_decode(q, k, v, cache, cache_len, *, window: int,
                       q_scale: float, softcap: float = 0.0,
                       mesh=None, dp_axes=()):
    """Drop-in for the decode branch of ``apply_attention``: update the
    cache at the write slot and attend over the valid prefix, with the
    cache sequence axis sharded over the mesh's model axis.

    Falls back to the unsharded math when the sequence length does not
    divide the model axis (the result is identical either way).
    """
    size = cache["k"].shape[1]
    slot = jnp.where(window > 0, cache_len % size,
                     jnp.minimum(cache_len, size - 1)).astype(jnp.int32)
    valid = jnp.minimum(cache_len + 1, size).astype(jnp.int32)

    n_model = mesh.shape["model"] if (
        mesh is not None and "model" in mesh.axis_names) else 1
    if n_model <= 1 or size % n_model != 0:
        o, nk, nv = _decode_update_and_attend(
            q, k, v, cache["k"], cache["v"], slot, valid,
            q_scale=q_scale, softcap=softcap, axis=None)
        return o, {"k": nk, "v": nv}

    rep = P(None, None, None, None)          # replicated over every axis
    seq = P(None, "model", None, None)       # cache layout
    fn = jax.shard_map(
        lambda q_, k_, v_, kc, vc, s_, n_: _decode_update_and_attend(
            q_, k_, v_, kc, vc, s_, n_, q_scale=q_scale, softcap=softcap,
            axis="model"),
        mesh=mesh,
        in_specs=(rep, rep, rep, seq, seq, P(), P()),
        out_specs=(rep, seq, seq),
        check_vma=False)
    o, nk, nv = fn(q, k, v, cache["k"], cache["v"], slot, valid)
    return o, {"k": nk, "v": nv}
