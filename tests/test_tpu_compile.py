"""Compile rehearsals: the serving path's Pallas kernels and the IVF
search program, compiled for a described TPU v5e (no chip attached) at
deployment shapes.

Interpret mode cannot show what Mosaic refuses (an unsupported primitive,
a misaligned block, too much VMEM); these compiles can, at no chip time.
Each asserts ``tpu_custom_call`` in the compiled program, i.e. the kernel
was lowered for the chip and not run through the interpreter.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and pytest-xdist imports
this file in every worker.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cellscan import ops as cellscan_ops
from repro.kernels.distance import ops as distance_ops
from repro.kernels.topk import ops as topk_ops

#: SIFT-128 at 10^6 vectors through the ``ivf`` backend, one serving batch
N, D, NLIST, CELL_PAD, B, NPROBE, K = 1_000_000, 128, 1024, 2048, 64, 16, 10


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def chip_kernels(one_chip):
    """Lower the kernels for the chip, not the interpreter, with no
    persistent cache (a compile for a described chip cannot be read back).
    Jit caches are cleared on both sides: the interpret choice is made at
    trace time and a cached CPU trace would otherwise be reused."""
    mp = pytest.MonkeyPatch()
    for mod in (cellscan_ops, distance_ops, topk_ops):
        mp.setattr(mod, "interpret_default", lambda: False)
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jax.clear_caches()
    yield one_chip
    mp.undo()
    jax.config.update("jax_enable_compilation_cache", cache_on)
    jax.clear_caches()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("nq,nx,k", [(64, 8192, 10), (64, 8192, 100),
                                     (64, 4096, 256)])
def test_topk_compiles(chip_kernels, nq, nx, k):
    d = _spec((nq, nx), jnp.float32, chip_kernels)
    fn = jax.jit(lambda x: topk_ops.topk_smallest(x, k))
    _assert_kernel(fn.lower(d).compile())


@pytest.mark.parametrize("exact", [True, False], ids=["fp32", "default"])
@pytest.mark.parametrize("dim", [128, 960])
def test_distance_compiles_at_brute_force_chunk(chip_kernels, dim, exact):
    """At the anchor's float32 contract precision and at Mosaic's
    default (the coarse probe's)."""
    from repro.anns.backends.brute_force import EXACT, BruteForceBackend
    q = _spec((B, dim), jnp.float32, chip_kernels)
    x = _spec((BruteForceBackend.chunk, dim), jnp.float32, chip_kernels)
    precision = EXACT if exact else None
    fn = jax.jit(lambda a, b: distance_ops.pairwise_distance(
        a, b, precision=precision))
    _assert_kernel(fn.lower(q, x).compile())


def _compile_ivf_search(s, *, d: int, nlist: int, b: int):
    """``_ivf_search`` at 10^6 vectors, one batch of ``b`` queries.  The
    cell blocks take their most chunks: every cell wastes under one."""
    from repro.anns.backends.ivf import _ivf_search
    chunk = cellscan_ops.chunk_rows(d, CELL_PAD)
    n_chunks = -(-N // chunk) + nlist
    blocks = cellscan_ops.CellBlocks(
        codes=_spec((n_chunks, d, chunk), jnp.int8, s),
        rows=_spec((n_chunks, 2, chunk), jnp.float32, s),
        first=_spec((nlist,), jnp.int32, s),
        live=_spec((nlist,), jnp.int32, s))
    args = (_spec((nlist, d), jnp.float32, s),          # centroids
            _spec((nlist, CELL_PAD), jnp.int32, s),     # cells
            _spec((N,), jnp.int32, s),                  # ids
            _spec((N, d), jnp.float32, s),              # base
            blocks,
            _spec((b, d), jnp.float32, s))              # queries
    compiled = _ivf_search.lower(*args, nprobe=NPROBE, k=K, m=2 * K,
                                 metric="l2", quantized=True).compile()
    _assert_kernel(compiled)
    assert "%cell_scan_blocks" in compiled.as_text()
    mem = compiled.memory_analysis()
    return mem.argument_size_in_bytes + mem.temp_size_in_bytes


@pytest.mark.parametrize("d,nlist", [(D, 1113), (960, 1383)])
def test_cell_blocks_compile_at_1m(chip_kernels, d, nlist):
    """The build's two programs for the scan's blocks: the chunked copy
    (its shape follows the base alone) and the alignment kernel (one per
    layout), at 10^6 vectors; each cell takes at most two chunks."""
    from repro.kernels.cellscan.cellscan import align_blocks
    s = chip_kernels
    chunk = cellscan_ops.chunk_rows(d, CELL_PAD)
    m = N // chunk + 2
    cellscan_ops._chunked.lower(_spec((N, d), jnp.int8, s),
                                _spec((N,), jnp.float32, s),
                                chunk=chunk, dp=d).compile()
    blocks = 2 * nlist
    compiled = align_blocks.lower(
        _spec((blocks,), jnp.int32, s), _spec((blocks,), jnp.int32, s),
        _spec((m, d, chunk), jnp.int8, s),
        _spec((m, 2, chunk), jnp.float32, s)).compile()
    _assert_kernel(compiled)


def test_ivf_search_compiles_at_sift_1m(chip_kernels):
    # index + one batch's scan must fit one 16 GB chip with room to spare
    assert _compile_ivf_search(chip_kernels, d=D, nlist=NLIST, b=B) < 4e9


def test_ivf_search_compiles_at_gist_1m(chip_kernels):
    """GIST-960: 1,383 cells after the ``max_cell`` splits, batch 32.
    The float32 base (3.84 GB) is an argument and, relaid out for the
    rerank in every batch, a temporary as well; the scan adds no
    per-batch block of dequantised rows."""
    assert _compile_ivf_search(chip_kernels, d=960, nlist=1383, b=32) < 10e9


@pytest.fixture(scope="module")
def four_chips(topo):
    from repro.launch.mesh import auto_mesh
    return auto_mesh((4,), ("shard",), devices=topo.devices)


def _placed_args(mesh, *, stream: bool):
    """Shapes of a 1M-vector index in 4 cell shards, as placed by
    ``place_on_mesh``: per-shard leaves split on ``"shard"``, routing
    state replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    S, C, cmax, npad, cap = 4, 1100, 300, 260_000, 256

    def spec(shape, dtype, *axes):
        return _spec(shape, dtype, NamedSharding(mesh, P(*axes)))
    sh3, sh2 = ("shard", None, None), ("shard", None)
    args = [spec((C, D), jnp.float32), spec((C,), jnp.int32),
            spec((C,), jnp.int32), spec((S, cmax, CELL_PAD), jnp.int32, *sh3),
            spec((S,), jnp.int32, "shard"), spec((S, npad, D), jnp.int8, *sh3),
            spec((S, npad), jnp.float32, *sh2),
            spec((S, npad, D), jnp.float32, *sh3)]
    if stream:
        args += [spec((S, npad), jnp.bool_, *sh2),
                 spec((S, cap, D), jnp.float32, *sh3),
                 spec((S, cap), jnp.bool_, *sh2),
                 spec((N + S * cap,), jnp.int32)]
    else:
        args += [spec((N,), jnp.int32)]
    return args + [spec((B, D), jnp.float32)]


@pytest.mark.parametrize("stream", [False, True], ids=["sharded",
                                                       "stream_sharded"])
def test_placed_sharded_search_compiles_on_four_chips(chip_kernels,
                                                      four_chips, stream):
    """The mesh-placed search at 1M over a 2x2 v5e: its Pallas kernels
    must sit inside the shard_map — the partitioner cannot split a TPU
    custom call."""
    if stream:
        from repro.anns.stream.search import make_placed_stream_search
        fn = make_placed_stream_search(four_chips)
    else:
        from repro.anns.backends.sharded import _make_placed_search
        fn = _make_placed_search(four_chips)
    compiled = fn.lower(*_placed_args(four_chips, stream=stream),
                        nprobe=NPROBE, k=K, m=2 * K, metric="l2",
                        quantized=True).compile()
    _assert_kernel(compiled)
    assert "all-gather" in compiled.as_text()
