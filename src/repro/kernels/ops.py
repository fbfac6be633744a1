"""Jit'd public wrappers around the Pallas kernels.

This module alone decides whether a kernel runs in interpret mode: off
a TPU it always does (the kernel body executes as traced jax ops), on a
TPU never.  There is no fallback: a kernel that Mosaic refuses raises.
The wrappers handle GQA layout, head_dim padding to the 128-lane MXU
width, and block-size selection.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import flash_attention as fa
from . import rglru_scan as rs


def _interpret() -> bool:
    """Interpret mode everywhere but on a TPU (read at trace time)."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap"))
def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    softcap: float = 0.0):
    """q: (B,S,H,hd); k,v: (B,T,Hkv,hd) -> (B,S,H,hd)."""
    B, S, H, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    # pad head_dim to the 128-lane width
    pad = (-hd) % 128
    if pad:
        zq = [(0, 0)] * 3 + [(0, pad)]
        q, k, v = (jnp.pad(x, zq) for x in (q, k, v))
    hdp = hd + pad
    qb = q.transpose(0, 2, 1, 3).reshape(B * H, S, hdp)
    kb = k.transpose(0, 2, 1, 3).reshape(B * Hkv, T, hdp)
    vb = v.transpose(0, 2, 1, 3).reshape(B * Hkv, T, hdp)
    # scale uses the REAL head_dim (zero padding contributes nothing to
    # the dots, so only the softmax scale constant must be corrected)
    out = fa.flash_attention_bhsd(
        qb, kb, vb, causal=causal, window=int(window or 0),
        softcap=softcap, interpret=_interpret(), scale=1.0 / (hd ** 0.5),
        bq=min(512, S), bk=min(512, T))
    out = out.reshape(B, H, S, hdp).transpose(0, 2, 1, 3)
    return out[..., :hd]


@jax.jit
def rglru(a, b, h0=None):
    """Linear recurrence h_t = a*h + b.  a, b: (B,S,R)."""
    return rs.rglru_scan(a, b, h0, interpret=_interpret())
