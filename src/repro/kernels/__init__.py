"""Pallas TPU kernels for the perf-critical compute layers.

<name>.py      pl.pallas_call + explicit BlockSpec VMEM tiling
ops.py         jit'd public wrappers (compiled; interpret=True in tests)
ref.py         pure-jnp oracles (the allclose ground truth in tests)

Kernels: nystrom_gram (tall-skinny CᵀC), woodbury (Cᵀv / Woodbury apply),
flash_attention (causal GQA forward), rmsnorm. The dry-run keeps the XLA
twins so HLO cost analysis sees real FLOPs.

The Nyström kernels are wired into the solver hot path through
``repro.core.backend.PallasBackend`` (``NystromIHVP(backend='pallas')``):
gram / Cᵀv / the fused Woodbury pass-2 stream the sketch-major (k, p)
buffer in place once per pass with the k-wide accumulator VMEM-resident.
"""
