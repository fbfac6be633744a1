"""Jit'd public wrappers around the Pallas kernels.

This module alone decides whether a kernel runs in interpret mode: off
a TPU it always does (the kernel body executes as traced jax ops), on a
TPU never.  There is no fallback: a kernel that Mosaic refuses raises.
The single-device PDHG burst is one such kernel today; Mosaic rejects
its `jnp.take` gather ("Only 2D gather is supported", see
docs/KERNELS.md).  The wrappers handle GQA layout, head_dim padding to
the 128-lane MXU width, and block-size selection.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import flash_attention as fa
from . import pdhg_spmv as ps
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


# ---------------------------------------------------------------------------
# PDHG over a blocked-ELL operator (the core.solver backend="pallas" path)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("row_meta", "col_meta", "iters",
                                             "precision"))
def pdhg_burst(c, tau, xmax, q, sig, ub, keep_n, keep_m,
               row_idx, row_val, col_idx, col_val, x0, y0, *,
               row_meta: tuple, col_meta: tuple, iters: int,
               precision: str = "fp32"):
    """One fused `iters`-iteration PDHG burst (kernels.pdhg_spmv).

    Arrays are storage-padded (x side n_pad, y side m_pad); returns
    (x, y, worst) with `worst` the terminal per-row residual vector
    computed in-kernel.  `keep_n`/`keep_m` freeze coordinates (True =
    hold), matching core.solver's adaptive batch semantics.
    `precision="bf16"` stores the iterates in bfloat16 between
    iterations (fp32 arithmetic and residuals — see pdhg_update_burst);
    the default "fp32" trace is unchanged."""
    return ps.pdhg_burst(c, tau, xmax, q, sig, ub, keep_n, keep_m,
                         row_idx, row_val, col_idx, col_val, x0, y0,
                         row_meta=row_meta, col_meta=col_meta, iters=iters,
                         interpret=_interpret(), precision=precision)


@functools.partial(jax.jit, static_argnames=("row_meta", "col_meta",
                                             "num_inst", "chunk",
                                             "max_chunks", "precision"))
def pdhg_adaptive(c, tau, xmax, q, sig, ub, row_idx, row_val, col_idx,
                  col_val, x0, y0, tols, inst_n, inst_m, *,
                  num_inst: int, row_meta: tuple, col_meta: tuple,
                  chunk: int, max_chunks: int, precision: str = "fp32"):
    """Adaptive PDHG over a block-stacked instance batch, Pallas bursts.

    The exact semantics of core.solver._pdhg_run_adaptive — `chunk`-
    iteration bursts inside one jitted lax.while_loop, per-instance
    residuals checked after every burst, converged instances frozen —
    but each burst is one fused Pallas kernel and the residual vector
    comes back from the kernel itself (no extra SpMV per check).

    `inst_n`/`inst_m` map storage coordinates to instance ids, with
    padded slots mapped to the dump segment `num_inst`.  Returns
    (x, y, per-instance residuals, per-instance chunks used)."""
    interpret = _interpret()

    def burst(x, y, frozen):
        frozen_ext = jnp.concatenate(
            [frozen, jnp.ones((1,), bool)])          # padded slots frozen
        return ps.pdhg_burst(
            c, tau, xmax, q, sig, ub, frozen_ext[inst_n], frozen_ext[inst_m],
            row_idx, row_val, col_idx, col_val, x, y,
            row_meta=row_meta, col_meta=col_meta, iters=chunk,
            interpret=interpret, precision=precision)

    def residuals(worst):
        return jax.ops.segment_max(worst, inst_m,
                                   num_segments=num_inst + 1)[:num_inst]

    def cond(state):
        _, _, _, k, frozen, _ = state
        return (k < max_chunks) & ~frozen.all()

    def step(state):
        x, y, _, k, frozen, used = state
        x, y, worst = burst(x, y, frozen)
        frozen_new = frozen | (residuals(worst) <= tols)
        used = jnp.where(frozen, used, k + 1)
        return x, y, worst, k + 1, frozen_new, used

    m_pad = y0.shape[0]
    state0 = (x0, y0, jnp.zeros(m_pad, x0.dtype), 0,
              jnp.zeros(num_inst, dtype=bool),
              jnp.zeros(num_inst, dtype=jnp.int32))
    x, y, worst, _, _, used = jax.lax.while_loop(cond, step, state0)
    return x, y, residuals(worst), used


@functools.lru_cache(maxsize=64)
def _sharded_burst_fn(mesh, axis: str, row_meta: tuple, col_meta: tuple,
                      iters: int, precision: str):
    """Build (and cache) the jitted shard_map program for one static
    configuration — mesh, layout meta, burst length, precision.  Cached
    on those statics so repeated bursts (the solver's restart ladder)
    reuse one compiled executable instead of re-tracing per call."""
    from jax.sharding import PartitionSpec as P

    rep, shd = P(), P(axis)

    def inner(c, tau, xmax, q, sig, ub, keep_n, keep_m,
              row_idx, row_val, col_idx, col_val, x0, y0):
        return ps.pdhg_update_burst_sharded(
            x0, y0, c, tau, xmax, q, sig, ub, keep_n, keep_m,
            row_idx, row_val, col_idx, col_val, row_meta=row_meta,
            col_meta=col_meta, iters=iters, axis=axis, precision=precision)

    fn = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(rep, rep, rep, shd, shd, shd, rep, shd,
                  shd, shd, shd, shd, rep, shd),
        out_specs=(rep, shd, shd), check_vma=False)
    return jax.jit(fn)


def pdhg_burst_sharded(mesh, c, tau, xmax, q, sig, ub, keep_n, keep_m,
                       row_idx, row_val, col_idx, col_val, x0, y0, *,
                       row_meta: tuple, col_meta: tuple, iters: int,
                       precision: str = "fp32"):
    """One fused PDHG burst over a row-block-sharded operator.

    `mesh` is a 1-D jax.sharding.Mesh (see runtime.sharding.solver_mesh)
    whose single axis partitions the [eq; ub] rows; the operand layout
    is kernels.pdhg_spmv.ell_pack_sharded's: x-side arrays replicated
    (length n_pad), y-side arrays and the per-shard ELL tables flat with
    a leading extent divisible by the mesh size (shard-major).  Each
    device runs the shared update body on its row slice; K^T.y is the
    one psum per iteration (kernels.pdhg_spmv.pdhg_update_burst_sharded).
    Returns (x, y, worst) in the same global layout as pdhg_burst.

    This path never engages for mesh size 1 — core.solver routes
    shards=1 to the single-device pallas burst, keeping that trajectory
    bit-for-bit untouched."""
    fn = _sharded_burst_fn(mesh, mesh.axis_names[0], row_meta, col_meta,
                           iters, precision)
    return fn(c, tau, xmax, q, sig, ub, keep_n, keep_m,
              row_idx, row_val, col_idx, col_val, x0, y0)
