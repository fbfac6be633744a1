"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -2.3819763e38


def attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                  softcap: float = 0.0):
    """q: (B,S,H,hd); k,v: (B,T,Hkv,hd).  Naive masked softmax attention."""
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, S, Hkv, G, hd).astype(jnp.float32)
    s = jnp.einsum("bskgd,btkd->bkgst", qg,
                   k.astype(jnp.float32)) / np.sqrt(hd)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    qi = jnp.arange(S)[:, None]
    ki = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((S, k.shape[1]), bool)
    if causal:
        mask &= qi >= ki
    if window:
        mask &= qi - ki < window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32))
    return out.reshape(B, S, Hq, hd).astype(q.dtype)


def rglru_ref(a, b):
    """Linear recurrence h_t = a_t * h_{t-1} + b_t over axis 1.
    a, b: (B, S, R) float32.  Returns h: (B, S, R)."""
    def combine(l, r):
        return (l[0] * r[0], r[0] * l[1] + r[1])
    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h
