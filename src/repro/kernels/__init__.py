"""Pallas TPU kernels (validated on CPU via interpret=True):

  flash_attention - online-softmax attention; GQA, causal/SWA, softcap
  rglru_scan      - RG-LRU linear recurrence (VMEM-resident sequential dim)
  pdhg_spmv       - blocked-ELL SpMV, the row-sharded PDHG's operator
                    pair (core.pdhg runs the update; no Pallas kernel)
  ops             - jit'd public wrappers (layout, padding, block sizes,
                    and the only interpret-mode decision)
  ref             - pure-jnp oracles for allclose validation
"""
from . import flash_attention, ops, pdhg_spmv, ref, rglru_scan

__all__ = ["flash_attention", "ops", "pdhg_spmv", "ref", "rglru_scan"]
