"""Base vectors, held-out queries and exact ground truth, made on the
device from ``--seed`` by the benchmark's own code.

The mixture: ``clusters`` clusters, each a Gaussian on a random
``intrinsic_dim``-dimensional affine subspace (its centre drawn with
standard deviation ``centre_scale`` per coordinate, its basis scaled so the
in-subspace spread is ``spread`` per coordinate) plus isotropic noise of
``noise`` per coordinate.  Queries are fresh draws from the same mixture,
so they are in distribution, as a held-out query set is.

The keys of a configuration's ``data`` block:

- ``n``, ``d``, ``n_query``: base rows (any whole number), dimensions,
  held-out queries.  Each cluster holds ``n // clusters`` rows, and the
  first ``n % clusters`` clusters one more (:func:`cluster_sizes`); a
  query draws its cluster with probability proportional to its size.
- ``metric``: ``"l2"``, or ``"angular"`` (ANN-Benchmarks' name for
  cosine): the mixture as for l2, then every base row and every query
  scaled to unit L2 norm in float32.  Neighbours of a unit query are then
  ranked by ``-q.x``.  Any other value raises.  ANN-Benchmarks stores an
  angular set's raw vectors and leaves the normalization to the system;
  here the benchmark does it, as the program's own loader
  (``repro.anns.datasets.make_dataset``) does before its backends, which
  serve ``"ip"`` on unit rows, see them.  So no run times it, and the
  reference cannot tell a system that would skip it.
- ``clusters``, ``intrinsic_dim``, ``centre_scale``, ``spread``,
  ``noise``: the mixture, as above.

Where ``n`` is a multiple of ``clusters`` (every l2 block), the mixture
is made by :func:`_mixture`, the program that has always made it; other
``n`` take :func:`_uneven_mixture`.

Ground truth is brute force in jnp at ``Precision.HIGHEST`` (a float32 dot
at default precision runs as one bf16 pass on a TPU), never through the
system's kernels: the exact top-k of the l2 distance, or of ``-q.x`` for
an angular block.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
METRICS = ("l2", "angular")


def jax_key(seed: int, salt: int = 0):
    """A PRNG key from any whole number: ``jax.random.key`` keeps only 32
    bits of a Python int, so the high bits are folded in."""
    s = int(seed) % 2**64
    key = jax.random.key(s % 2**32)
    return jax.random.fold_in(jax.random.fold_in(key, s >> 32), salt)


def host_rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**64, salt])


def _centres(k_mu, k_a, *, clusters, d, r, centre_scale, spread):
    """Each cluster's centre and the basis of its subspace."""
    mu = centre_scale * jax.random.normal(k_mu, (clusters, d), jnp.float32)
    basis = (spread / np.sqrt(r)) * jax.random.normal(
        k_a, (clusters, r, d), jnp.float32)
    return mu, basis


def _queries(k_q, cq, mu, basis, *, noise):
    """One held-out query from each cluster ``cq[i]``."""
    (n_query,), (_, r, d) = cq.shape, basis.shape
    kz, ke = jax.random.split(k_q)
    z = jax.random.normal(kz, (n_query, r), jnp.float32)
    eps = jax.random.normal(ke, (n_query, d), jnp.float32)
    return (mu[cq] + jnp.einsum("qr,qrd->qd", z, basis[cq], precision=_HI)
            + noise * eps)


@functools.partial(jax.jit, static_argnames=(
    "n", "d", "n_query", "clusters", "r", "centre_scale", "spread", "noise"))
def _mixture(key, *, n, d, n_query, clusters, r, centre_scale, spread,
             noise):
    k_mu, k_a, k_base, k_qc, k_q = jax.random.split(key, 5)
    mu, basis = _centres(k_mu, k_a, clusters=clusters, d=d, r=r,
                         centre_scale=centre_scale, spread=spread)
    per = n // clusters

    def one_cluster(c):
        kz, ke = jax.random.split(jax.random.fold_in(k_base, c))
        z = jax.random.normal(kz, (per, r), jnp.float32)
        eps = jax.random.normal(ke, (per, d), jnp.float32)
        return (mu[c] + jnp.dot(z, basis[c], precision=_HI)
                + noise * eps)

    base = jax.lax.map(one_cluster, jnp.arange(clusters)).reshape(n, d)
    cq = jax.random.randint(k_qc, (n_query,), 0, clusters)
    return base, _queries(k_q, cq, mu, basis, noise=noise)


def cluster_sizes(n: int, clusters: int) -> np.ndarray:
    """Rows per cluster: ``n // clusters`` each, plus one for each of the
    first ``n % clusters`` clusters."""
    sizes = np.full(clusters, n // clusters, np.int64)
    sizes[:n % clusters] += 1
    return sizes


@functools.partial(jax.jit, static_argnames=(
    "sizes", "d", "n_query", "r", "centre_scale", "spread", "noise"))
def _uneven_mixture(key, *, sizes, d, n_query, r, centre_scale, spread,
                    noise):
    """The mixture with ``sizes[c]`` rows in cluster ``c``, in cluster
    order; each query's cluster is that of a row drawn uniformly."""
    clusters, n = len(sizes), sum(sizes)
    k_mu, k_a, k_base, k_qc, k_q = jax.random.split(key, 5)
    mu, basis = _centres(k_mu, k_a, clusters=clusters, d=d, r=r,
                         centre_scale=centre_scale, spread=spread)
    kz, ke = jax.random.split(k_base)
    z = jax.random.normal(kz, (n, r), jnp.float32)
    eps = jax.random.normal(ke, (n, d), jnp.float32)
    label = jnp.searchsorted(jnp.asarray(np.cumsum(sizes)), jnp.arange(n),
                             side="right")

    def add_cluster(c, acc):
        return jnp.where((label == c)[:, None],
                         mu[c] + jnp.dot(z, basis[c], precision=_HI), acc)

    base = jax.lax.fori_loop(0, clusters, add_cluster,
                             jnp.zeros((n, d), jnp.float32)) + noise * eps
    cq = label[jax.random.randint(k_qc, (n_query,), 0, n)]
    return base, _queries(k_q, cq, mu, basis, noise=noise)


@jax.jit
def _unit(x):
    """Rows scaled to unit L2 norm, in float32."""
    return x / jnp.linalg.norm(x, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("k", "block", "metric"))
def _knn_chunk(base, base_sq, q, *, k, block=1024, metric="l2"):
    """Exact top-k in two stages: the k best of each block of columns,
    then the k best of those (each true neighbour is among its block's k
    best), which is far cheaper on a TPU than one top-k over 10^6."""
    if metric == "angular":
        d = -jnp.dot(q, base.T, precision=_HI)
    else:
        d = (jnp.sum(q * q, axis=1)[:, None] + base_sq[None, :]
             - 2.0 * jnp.dot(q, base.T, precision=_HI))
    b, n = d.shape
    nb = -(-n // block)
    d = jnp.pad(d, ((0, 0), (0, nb * block - n)), constant_values=jnp.inf)
    v, i = jax.lax.top_k(-d.reshape(b, nb, block), k)
    i = (i + (jnp.arange(nb) * block)[None, :, None]).reshape(b, nb * k)
    return jnp.take_along_axis(i, jax.lax.top_k(v.reshape(b, nb * k), k)[1],
                               axis=1)


def exact_knn(base, queries, k: int, chunk: int = 128,
              metric: str = "l2") -> np.ndarray:
    """(nq, k) exact nearest ids, brute force on the device: by l2, or by
    ``-q.x`` for ``metric="angular"`` (unit rows)."""
    base_sq = None if metric == "angular" else jnp.sum(base * base, axis=1)
    out = []
    nq = queries.shape[0]
    for lo in range(0, nq, chunk):
        q = queries[lo:lo + chunk]
        if q.shape[0] < chunk:          # one compiled shape per run
            q = jnp.pad(q, ((0, chunk - q.shape[0]), (0, 0)))
        out.append(_knn_chunk(base, base_sq, q, k=k, metric=metric))
    return np.asarray(jnp.concatenate(out))[:nq].astype(np.int32)


@dataclass
class Data:
    base: np.ndarray        # (n, d) float32, host
    queries: np.ndarray     # (n_query, d) float32, host: the held-out pool
    gt: np.ndarray          # (n_query, k) exact nearest ids


def make(spec: dict, seed: int, k: int) -> Data:
    """The configuration's ``data`` block, generated from ``seed``."""
    metric = spec["metric"]
    if metric not in METRICS:
        raise ValueError(f"data metric must be one of {METRICS}, not "
                         f"{metric!r}")
    sizes = cluster_sizes(spec["n"], spec["clusters"])
    mixture = dict(d=spec["d"], n_query=spec["n_query"],
                   r=spec["intrinsic_dim"], centre_scale=spec["centre_scale"],
                   spread=spec["spread"], noise=spec["noise"])
    if np.all(sizes == sizes[0]):
        base, queries = _mixture(jax_key(seed), n=spec["n"],
                                 clusters=spec["clusters"], **mixture)
    else:
        base, queries = _uneven_mixture(
            jax_key(seed), sizes=tuple(int(c) for c in sizes), **mixture)
    if metric == "angular":
        base, queries = _unit(base), _unit(queries)
    gt = exact_knn(base, queries, k, metric=metric)
    out = Data(np.asarray(base), np.asarray(queries), gt)
    del base, queries
    return out
