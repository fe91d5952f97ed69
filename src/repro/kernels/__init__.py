"""Pallas TPU kernels — the Engine's accelerator backends.

The GEMM surface lives in :mod:`repro.core.engine`; this package provides
the kernel bodies the registered "pallas" and "interpret" backends execute
(the registry entry, not this package, is the dispatch point — third-party
backends register alongside these without touching kernel code).

* redmule_matmul.py -- the paper's engine: X-stationary / W-streamed tiled
  GEMM with a VMEM scratch accumulator (store-once Z), the bias+activation
  epilogue fused into the store step, and a leading batch grid dimension
  for batched operands.  ops.py wraps it (padding, tile choice, epilogue
  plumbing); ref.py holds the pure-jnp oracles.
* flash_attention.py -- RedMulE-tiled attention (Q-stationary, K/V streamed,
  online-softmax accumulator) for long-context prefill.
* chunked_linear_attention.py -- VMEM-resident-state chunked recurrence
  (mLSTM / SSD), the store-once rule applied to linear attention.
"""
