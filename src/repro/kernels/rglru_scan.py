"""Pallas TPU kernel for the RG-LRU linear recurrence.

h_t = a_t * h_{t-1} + b_t, elementwise over features.  Grid tiles the
(batch, feature) plane; each program streams its (S, bf) slab through
VMEM and runs the recurrence with a fori_loop carrying one (1, bf) row
— the sequential dimension stays on-chip, reads/writes to HBM are the
a/b inputs and h output only (memory-bound roofline: 3 tensors).

Feature blocks are 128-wide (lane-aligned); batch is the outer grid dim.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, h0_ref, o_ref, hlast_ref, *, seq: int):
    h0 = h0_ref[0]                                       # (bf,)

    def body(t, h):
        ht = a_ref[0, t] * h + b_ref[0, t]               # (bf,)
        o_ref[0, t] = ht
        return ht

    h = jax.lax.fori_loop(0, seq, body, h0)
    hlast_ref[0] = h


def rglru_scan(a, b, h0=None, *, bf: int = 128, interpret: bool):
    """a, b: (B, S, R) float32; h0: (B, R) initial state (zeros default).
    Returns (h (B,S,R), h_last (B,R))."""
    B, S, R = a.shape
    bf = min(bf, R)
    assert R % bf == 0, (R, bf)
    if h0 is None:
        h0 = jnp.zeros((B, R), a.dtype)
    grid = (B, R // bf)
    kernel = functools.partial(_kernel, seq=S)
    h, hlast = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, S, bf), lambda bi, fi: (bi, 0, fi)),
            pl.BlockSpec((1, S, bf), lambda bi, fi: (bi, 0, fi)),
            pl.BlockSpec((1, bf), lambda bi, fi: (bi, fi)),
        ],
        out_specs=[
            pl.BlockSpec((1, S, bf), lambda bi, fi: (bi, 0, fi)),
            pl.BlockSpec((1, bf), lambda bi, fi: (bi, fi)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, S, R), a.dtype),
                   jax.ShapeDtypeStruct((B, R), a.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(a, b, h0)
    return h, hlast
