"""Production mesh construction.

A FUNCTION, not a module constant: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax init).

Topology: TPU v5e pods of 256 chips as a (data=16, model=16) torus slice;
multi-pod adds the leading "pod" axis over DCN.  DP gradient reduction runs
over ("pod", "data"); TP/EP collectives stay inside the pod's "model" axis
(ICI); nothing latency-sensitive crosses the DCN boundary.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis Auto: the partitioner places
    what the code's shardings leave open.  jax's default is Explicit
    axes, under which a gather such as the embedding lookup refuses an
    operand sharded along the gathered axis."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 4):
    """Small mesh for CPU tests (requires forced host device count)."""
    return auto_mesh((n_data, n_model), ("data", "model"))


def make_shard_mesh(n_shards: int):
    """1-D ``("shard",)`` mesh for the sharded ANNS backend: each device
    owns one slice of the stacked cell-major layout — including its own
    fp32 rerank slice ``base_f``, so per-device memory is O(N/S * d)
    (``repro.anns.ivf.sharding.place_on_mesh``).  CPU tests force host
    devices via ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    return auto_mesh((n_shards,), ("shard",))


def make_tuned_mesh(tp: int = 16, *, multi_pod: bool = False):
    """Same physical 256/512-chip grid, with the 16-wide model dimension
    logically split into ("replica", "model") = (16//tp, tp).

    Small models don't amortise TP=16 (a 2048-wide layer leaves 128
    columns/shard and pays an activation all-reduce per matmul); remapping
    part of the model axis to data parallelism trades those activation
    collectives for a slightly larger gradient reduction.  This is the
    "TP-degree" knob of the §Perf hillclimb — physical topology unchanged.
    """
    assert 16 % tp == 0
    if multi_pod:
        return auto_mesh((2, 16, 16 // tp, tp),
                         ("pod", "data", "replica", "model"))
    return auto_mesh((16, 16 // tp, tp), ("data", "replica", "model"))
