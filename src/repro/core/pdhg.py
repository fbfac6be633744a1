"""One PDHG iteration, over any operator pair.

Diagonally preconditioned PDHG (Pock & Chambolle 2011) for the routing
LP of core.solver,

    min c.x  s.t.  K_eq x = b,  K_ub x <= h,  0 <= x <= xmax,

with the equality rows first.  This module owns the update: the step
sizes and masks (`preconditioners`, from the COO arrays), one burst of
iterations (`burst`) and the terminal residual (`residual`).  K enters
only as a pair of functions (Kx, KTy), and three operators supply one:

  * COO (`coo_pair`): one scalar gather and scatter-add per nonzero.
    The CPU runs it.
  * dense tiles (core.tile_spmv.operator_pair): block-sparse 8 x 128
    tiles.  A TPU runs it.
  * blocked ELL (kernels.pdhg_spmv.ell_pair), row-sharded across a mesh:
    each device holds a block of rows, and K^T.y is the psum of the
    devices' parts (`burst_sharded`).

Every caller runs the same iterates from the same inputs, so two
operators differ only by the floating-point order of their sums.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def coo_pair(row, col, val, m: int, n: int):
    """(Kx, KTy) as one scalar gather and scatter-add per nonzero."""
    def Kx(x):
        with jax.named_scope("pdhg/Kx"):
            return jnp.zeros(m).at[row].add(val * x[col])

    def KTy(y):
        with jax.named_scope("pdhg/KTy"):
            return jnp.zeros(n).at[col].add(val * y[row])

    return Kx, KTy


def preconditioners(row, col, val, b, h, m: int, n: int, m_eq: int):
    """(q, tau, sig, ub): the stacked right-hand side [b; h], the
    diagonal step sizes tau_j = 1/sum_i |K_ij| and sig_i = 1/sum_j
    |K_ij|, and the mask of inequality rows."""
    q = jnp.concatenate([b, h])
    abs_val = jnp.abs(val)
    col_sum = jnp.zeros(n).at[col].add(abs_val)
    row_sum = jnp.zeros(m).at[row].add(abs_val)
    tau = 1.0 / jnp.maximum(col_sum, 1e-12)
    sig = 1.0 / jnp.maximum(row_sum, 1e-12)
    return q, tau, sig, jnp.arange(m) >= m_eq


def burst(Kx, KTy, c, tau, sig, q, xmax, ub, x, y, iters,
          keep_n=None, keep_m=None):
    """`iters` PDHG iterations from (x, y); returns (x, y).

    `keep_n` / `keep_m`, where given, hold the coordinates they mark
    (the adaptive loop's frozen instances and padding)."""
    def body(_, state):
        x, y = state
        x_new = jnp.clip(x - tau * (c + KTy(y)), 0.0, xmax)
        if keep_n is not None:
            x_new = jnp.where(keep_n, x, x_new)
        x_bar = 2.0 * x_new - x
        y_new = y + sig * (Kx(x_bar) - q)
        y_new = jnp.where(ub, jnp.maximum(y_new, 0.0), y_new)
        if keep_m is not None:
            y_new = jnp.where(keep_m, y, y_new)
        return x_new, y_new

    return jax.lax.fori_loop(0, iters, body, (x, y))


def residual(Kx, q, ub, x):
    """Each row's primal residual: |K_eq x - b| on equality rows,
    max(K_ub x - h, 0) on inequality rows."""
    r = Kx(x) - q
    return jnp.where(ub, jnp.maximum(r, 0.0), jnp.abs(r))


@functools.lru_cache(maxsize=64)
def _sharded_burst_fn(mesh, axis: str, row_meta: tuple, col_meta: tuple,
                      iters: int):
    """The jitted shard_map program of one burst, cached on its statics
    (mesh, layout, length) so a restart ladder traces it once.

    x-side arrays (c, tau, xmax, x0) are replicated; y-side arrays (q,
    sig, ub, y0) and the ELL tables are split by rows.  Each device
    computes its rows of K.x from the replicated x, and its part of
    K^T.y from its rows of y; the psum of the parts is the one
    collective of an iteration.  x is the same on every device, as a
    function of replicated inputs and psum outputs."""
    from jax.sharding import PartitionSpec as P

    from repro.kernels import pdhg_spmv

    rep, shd = P(), P(axis)

    def inner(c, tau, xmax, q, sig, ub, row_idx, row_val, col_idx,
              col_val, x0, y0):
        Kx, KTy_part = pdhg_spmv.ell_pair(row_idx, row_val, col_idx,
                                          col_val, row_meta, col_meta)

        def KTy(y):
            return jax.lax.psum(KTy_part(y), axis)

        x, y = burst(Kx, KTy, c, tau, sig, q, xmax, ub, x0, y0, iters)
        return x, y, residual(Kx, q, ub, x)

    fn = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(rep, rep, rep, shd, shd, shd, shd, shd, shd, shd, rep,
                  shd),
        out_specs=(rep, shd, shd), check_vma=False)
    return jax.jit(fn)


def burst_sharded(mesh, c, tau, xmax, q, sig, ub, row_idx, row_val,
                  col_idx, col_val, x0, y0, *, row_meta: tuple,
                  col_meta: tuple, iters: int):
    """One burst over the row-sharded blocked-ELL operator
    (kernels.pdhg_spmv.ell_pack_sharded) on a 1-D `mesh`
    (runtime.sharding.solver_mesh).  Returns (x, y, per-row residual)
    in the pack's padded layout."""
    fn = _sharded_burst_fn(mesh, mesh.axis_names[0], row_meta, col_meta,
                           iters)
    return fn(c, tau, xmax, q, sig, ub, row_idx, row_val, col_idx,
              col_val, x0, y0)
