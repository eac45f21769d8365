"""Base vectors, held-out queries and exact ground truth, made on the
device from ``--seed`` by the benchmark's own code.

The mixture: ``clusters`` equal clusters, each a Gaussian on a random
``intrinsic_dim``-dimensional affine subspace (its centre drawn with
standard deviation ``centre_scale`` per coordinate, its basis scaled so the
in-subspace spread is ``spread`` per coordinate) plus isotropic noise of
``noise`` per coordinate.  Queries are fresh draws from the same mixture,
so they are in distribution, as a held-out query set is.

Ground truth is brute force in jnp at ``Precision.HIGHEST`` (a float32 dot
at default precision runs as one bf16 pass on a TPU), never through the
system's kernels.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST


def jax_key(seed: int, salt: int = 0):
    """A PRNG key from any whole number: ``jax.random.key`` keeps only 32
    bits of a Python int, so the high bits are folded in."""
    s = int(seed) % 2**64
    key = jax.random.key(s % 2**32)
    return jax.random.fold_in(jax.random.fold_in(key, s >> 32), salt)


def host_rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**64, salt])


@functools.partial(jax.jit, static_argnames=(
    "n", "d", "n_query", "clusters", "r", "centre_scale", "spread", "noise"))
def _mixture(key, *, n, d, n_query, clusters, r, centre_scale, spread,
             noise):
    k_mu, k_a, k_base, k_qc, k_q = jax.random.split(key, 5)
    mu = centre_scale * jax.random.normal(k_mu, (clusters, d), jnp.float32)
    basis = (spread / np.sqrt(r)) * jax.random.normal(
        k_a, (clusters, r, d), jnp.float32)
    per = n // clusters

    def one_cluster(c):
        kz, ke = jax.random.split(jax.random.fold_in(k_base, c))
        z = jax.random.normal(kz, (per, r), jnp.float32)
        eps = jax.random.normal(ke, (per, d), jnp.float32)
        return (mu[c] + jnp.dot(z, basis[c], precision=_HI)
                + noise * eps)

    base = jax.lax.map(one_cluster, jnp.arange(clusters)).reshape(n, d)
    cq = jax.random.randint(k_qc, (n_query,), 0, clusters)
    kz, ke = jax.random.split(k_q)
    z = jax.random.normal(kz, (n_query, r), jnp.float32)
    eps = jax.random.normal(ke, (n_query, d), jnp.float32)
    queries = (mu[cq] + jnp.einsum("qr,qrd->qd", z, basis[cq], precision=_HI)
               + noise * eps)
    return base, queries


@functools.partial(jax.jit, static_argnames=("k", "block"))
def _knn_chunk(base, base_sq, q, *, k, block=1024):
    """Exact top-k in two stages: the k best of each block of columns,
    then the k best of those (each true neighbour is among its block's k
    best), which is far cheaper on a TPU than one top-k over 10^6."""
    d = (jnp.sum(q * q, axis=1)[:, None] + base_sq[None, :]
         - 2.0 * jnp.dot(q, base.T, precision=_HI))
    b, n = d.shape
    nb = -(-n // block)
    d = jnp.pad(d, ((0, 0), (0, nb * block - n)), constant_values=jnp.inf)
    v, i = jax.lax.top_k(-d.reshape(b, nb, block), k)
    i = (i + (jnp.arange(nb) * block)[None, :, None]).reshape(b, nb * k)
    return jnp.take_along_axis(i, jax.lax.top_k(v.reshape(b, nb * k), k)[1],
                               axis=1)


def exact_knn(base, queries, k: int, chunk: int = 128) -> np.ndarray:
    """(nq, k) exact l2 nearest ids, brute force on the device."""
    base_sq = jnp.sum(base * base, axis=1)
    out = []
    nq = queries.shape[0]
    for lo in range(0, nq, chunk):
        q = queries[lo:lo + chunk]
        if q.shape[0] < chunk:          # one compiled shape per run
            q = jnp.pad(q, ((0, chunk - q.shape[0]), (0, 0)))
        out.append(_knn_chunk(base, base_sq, q, k=k))
    return np.asarray(jnp.concatenate(out))[:nq].astype(np.int32)


@dataclass
class Data:
    base: np.ndarray        # (n, d) float32, host
    queries: np.ndarray     # (n_query, d) float32, host: the held-out pool
    gt: np.ndarray          # (n_query, k) exact nearest ids


def make(spec: dict, seed: int, k: int) -> Data:
    """The configuration's ``data`` block, generated from ``seed``."""
    if spec["metric"] != "l2":
        raise ValueError(f"generator supports l2 only, not {spec['metric']}")
    if spec["n"] % spec["clusters"]:
        raise ValueError("n must be a multiple of clusters")
    base, queries = _mixture(
        jax_key(seed), n=spec["n"], d=spec["d"], n_query=spec["n_query"],
        clusters=spec["clusters"], r=spec["intrinsic_dim"],
        centre_scale=spec["centre_scale"], spread=spec["spread"],
        noise=spec["noise"])
    gt = exact_knn(base, queries, k)
    out = Data(np.asarray(base), np.asarray(queries), gt)
    del base, queries
    return out
