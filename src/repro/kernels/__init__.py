"""Pallas TPU kernels for the perf-critical hot spots.

Layout per kernel: ``<name>.py`` (pl.pallas_call + BlockSpec), ``ops.py``
(jit'd public wrapper, auto-selects interpret mode off-TPU), ``ref.py``
(pure-jnp oracle used by the allclose test sweeps).

Kernels:
- ``distance`` — batched L2/IP distance matrix (MXU matmul-form, the ANNS
  inner loop: beam expansion scoring).
- ``topk``     — k-smallest selection over distance rows (beam/result set
  maintenance).
- ``qdist``    — int8 symmetric-quantized asymmetric distance (refinement
  module's preliminary search).
- ``cellscan`` — the IVF int8 cell scan: each probed cell's codes read as
  contiguous chunks through scalar-prefetched block tables.
- ``flash``    — causal flash attention forward (policy-LM serving path;
  window + logit-softcap support).
"""
