"""Pallas TPU flash attention (forward): online-softmax, GQA, causal /
sliding-window masks, logit softcap.

Tiling: grid = (batch*heads, q_blocks, kv_blocks), kv innermost with
"arbitrary" semantics so the VMEM scratch accumulators (m, l, acc)
persist across kv steps for a fixed (bh, q) tile.  BlockSpecs keep one
(bq, hd) query tile, one (bk, hd) K and V tile, and the (bq, hd) output
tile in VMEM; K/V tiles for GQA map q-head -> kv-head via the index_map
(h // group), so the expanded KV never materializes in HBM.

MXU alignment: bq/bk default 512/512 and head_dim is padded to a
multiple of 128 by the wrapper (ops.flash_attention).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, bq: int, bk: int, n_kv: int, causal: bool,
            window: int, softcap: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                     # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                     # (bk, hd)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    q_idx = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_idx = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= q_idx >= k_idx
    if window > 0:
        mask &= (q_idx - k_idx) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                  # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                               # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == n_kv - 1)
    def _done():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True, window: int = 0,
                         softcap: float = 0.0, bq: int = 512, bk: int = 512,
                         scale: float | None = None, interpret: bool):
    """q: (BH, S, hd); k, v: (BH_kv, T, hd) with BH = BH_kv * group.
    Returns (BH, S, hd)."""
    BH, S, hd = q.shape
    BHkv, T, _ = k.shape
    group = BH // BHkv
    bq = min(bq, S)
    bk = min(bk, T)
    assert S % bq == 0 and T % bk == 0, (S, bq, T, bk)
    n_kv = T // bk
    grid = (BH, S // bq, n_kv)
    if scale is None:
        scale = 1.0 / np.sqrt(hd)

    kernel = functools.partial(
        _kernel, scale=scale, bq=bq, bk=bk, n_kv=n_kv, causal=causal,
        window=window, softcap=softcap)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, qi, ki: (bh // group, ki, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, qi, ki: (bh // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max m
            pltpu.VMEM((bq, 1), jnp.float32),     # running denom l
            pltpu.VMEM((bq, hd), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
