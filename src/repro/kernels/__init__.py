"""Pallas TPU kernels for the perf-critical compute layers.

  intersect/  — IoU Sketch L-way bitmap intersection + popcount (the
                paper's query-combine hot spot, §II-C/§IV-A)
  attention/  — flash attention (blockwise online softmax)
  rwkv/       — RWKV-6 wkv recurrence with data-dependent decay
  ssm/        — Mamba selective (diagonal) state-space scan

Each package ships the Pallas kernel (pl.pallas_call + explicit BlockSpec
VMEM tiling), a jit'd `ops.py` wrapper, and a pure-jnp `ref.py` oracle.
The intersect kernels (the served search path) run compiled on a TPU
and in the Pallas interpreter on the CPU — decided once, from the
default backend, in `intersect/ops.py`. The model-shell kernels take an
explicit `interpret=` flag; on real TPUs the models flip
`kernel_impl="pallas"`.
"""
