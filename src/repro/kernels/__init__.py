"""Pallas TPU kernels (validated on CPU via interpret=True):

  flash_attention - online-softmax attention; GQA, causal/SWA, softcap
  rglru_scan      - RG-LRU linear recurrence (VMEM-resident sequential dim)
  pdhg_spmv       - blocked-ELL SpMV + fused PDHG iteration burst (the
                    core.solver backend="pallas" hot loop)
  ops             - jit'd public wrappers (layout, padding, block sizes,
                    and the only interpret-mode decision)
  ref             - pure-jnp oracles for allclose validation
"""
from . import flash_attention, ops, pdhg_spmv, ref, rglru_scan

__all__ = ["flash_attention", "ops", "pdhg_spmv", "ref", "rglru_scan"]
