"""Pallas TPU kernels for the perf-critical compute layers.

* hotspot/        — paper benchmark 1 (regular stencil), HP/HPC variants
* spmm/           — paper benchmark 2 (irregular), block-ELL MXU + gather
* ssd_scan/        — Mamba2 chunked-scan kernel (state VMEM-resident)
* flash_attention — production attention (replaces the XLA online-softmax
                    path whose score-block HBM traffic dominates §Roofline)

All kernels: pl.pallas_call + explicit BlockSpec VMEM tiling, ops.py jit'd
wrapper, ref.py pure-jnp oracle.  Kernels run compiled on a TPU and in the Pallas
interpreter on the CPU (:mod:`.platform`), where the tests validate them.
"""
