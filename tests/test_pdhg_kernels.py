"""Blocked-ELL PDHG kernels vs oracles, and backend equivalence.

Three layers of pinning, innermost first:

  1. layout — `ell_pack` reconstructs the dense operator exactly,
     including ragged tail blocks, empty rows, and per-block widths;
  2. kernel — the Pallas burst (interpret=True on CPU) matches the
     pure-jnp `ref.pdhg_ell_burst_ref` oracle to ~1 ulp, and tracks
     the XLA COO kernel's trajectory to fp tolerance;
  3. solver — `solve_fast(..., backend="pallas")` reproduces the
     "xla" backend's exact paper-model metrics within 1e-4 relative on
     small instances of all six topologies.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import solver, timeslot, topology, traffic
from repro.kernels import ops, pdhg_spmv, ref


def _random_coo(rng, m, n, nnz, *, wide_rows=0):
    """Random COO with optional very-wide rows (ELL worst case)."""
    row = rng.integers(0, m, nnz)
    col = rng.integers(0, n, nnz)
    if wide_rows:
        # concentrate extra entries on a few rows to force per-block
        # width divergence (the reason the layout is *blocked* ELL)
        extra = rng.integers(0, n, wide_rows * 40)
        row = np.concatenate([row, np.repeat(rng.integers(0, m, wide_rows),
                                             40)])
        col = np.concatenate([col, extra])
    val = rng.normal(size=len(row))
    return row, col, val


def _dense(row, col, val, m, n):
    K = np.zeros((m, n))
    np.add.at(K, (row, col), val)
    return K


@pytest.mark.parametrize("m,n,nnz,bm,align,wide", [
    (37, 29, 240, 8, 8, 0),        # ragged tail block (37 % 8 != 0)
    (16, 16, 60, 8, 8, 2),         # wide rows force unequal block widths
    (5, 3, 9, 8, 8, 0),            # single (padded) block each side
    (64, 40, 300, 16, 32, 1),      # non-default block/alignment
    (12, 12, 0, 8, 8, 0),          # empty operator
])
def test_ell_pack_reconstructs_dense(m, n, nnz, bm, align, wide):
    rng = np.random.default_rng(m * 1000 + n)
    row, col, val = _random_coo(rng, m, n, nnz, wide_rows=wide)
    op = pdhg_spmv.ell_pack(row, col, val, m, n, bm=bm, align=align)
    K = _dense(row, col, val, m, n).astype(np.float32)

    # rows direction: gathering a one-hot x reproduces column j of K
    dense_rows = np.zeros((op.m_pad, n), np.float32)
    for b, (off, w) in enumerate(zip(op.rows.offsets, op.rows.widths)):
        idx = op.rows.idx[off:off + bm * w].reshape(bm, w)
        vals = op.rows.val[off:off + bm * w].reshape(bm, w)
        for i in range(bm):
            np.add.at(dense_rows[b * bm + i], idx[i], vals[i])
    np.testing.assert_allclose(dense_rows[:m], K, atol=1e-6)
    assert np.all(dense_rows[m:] == 0.0)

    dense_cols = np.zeros((op.n_pad, m), np.float32)
    for b, (off, w) in enumerate(zip(op.cols.offsets, op.cols.widths)):
        idx = op.cols.idx[off:off + bm * w].reshape(bm, w)
        vals = op.cols.val[off:off + bm * w].reshape(bm, w)
        for i in range(bm):
            np.add.at(dense_cols[b * bm + i], idx[i], vals[i])
    np.testing.assert_allclose(dense_cols[:n], K.T, atol=1e-6)
    assert np.all(dense_cols[n:] == 0.0)

    # block invariants: widths aligned, offsets contiguous
    for blocks in (op.rows, op.cols):
        assert all(w % align == 0 and w >= align for w in blocks.widths)
        off = 0
        for o, w in zip(blocks.offsets, blocks.widths):
            assert o == off
            off += blocks.bm * w
        assert len(blocks.idx) == len(blocks.val) == off


def test_ell_spmv_matches_dense():
    rng = np.random.default_rng(7)
    m, n = 45, 31
    row, col, val = _random_coo(rng, m, n, 400, wide_rows=3)
    op = pdhg_spmv.ell_pack(row, col, val, m, n)
    K = _dense(row, col, val, m, n).astype(np.float32)
    x = rng.normal(size=n).astype(np.float32)
    y = rng.normal(size=m).astype(np.float32)
    kx = np.asarray(ref.ell_spmv(np.pad(x, (0, op.n_pad - n)), op.rows))
    kty = np.asarray(ref.ell_spmv(np.pad(y, (0, op.m_pad - m)), op.cols))
    np.testing.assert_allclose(kx[:m], K @ x, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(kty[:n], K.T @ y, atol=1e-4, rtol=1e-5)
    assert np.all(kx[m:] == 0.0) and np.all(kty[n:] == 0.0)


def _burst_args(rng, m, n, nnz, m_eq, *, frozen_frac=0.0, bm=8, align=8):
    row, col, val = _random_coo(rng, m, n, nnz, wide_rows=2)
    op = pdhg_spmv.ell_pack(row, col, val, m, n, bm=bm, align=align)

    def padn(a, cv=0.0):
        return jnp.asarray(np.pad(np.asarray(a, np.float32),
                                  (0, op.n_pad - n), constant_values=cv))

    def padm(a, cv=0.0):
        return jnp.asarray(np.pad(np.asarray(a, np.float32),
                                  (0, op.m_pad - m), constant_values=cv))

    col_sum = np.zeros(n)
    np.add.at(col_sum, col, np.abs(val))
    row_sum = np.zeros(m)
    np.add.at(row_sum, row, np.abs(val))
    keep_n = np.zeros(op.n_pad, bool)
    keep_m = np.zeros(op.m_pad, bool)
    if frozen_frac:
        keep_n[:n] = rng.random(n) < frozen_frac
        keep_m[:m] = rng.random(m) < frozen_frac
    args = (padn(rng.normal(size=n)),                        # c
            padn(1.0 / np.maximum(col_sum, 1e-12)),          # tau
            padn(rng.uniform(0.5, 4.0, n)),                  # xmax
            padm(rng.normal(size=m)),                        # q
            padm(1.0 / np.maximum(row_sum, 1e-12)),          # sig
            jnp.asarray(np.pad(np.arange(m) >= m_eq, (0, op.m_pad - m),
                               constant_values=True)),       # ub mask
            jnp.asarray(keep_n), jnp.asarray(keep_m),
            jnp.asarray(op.rows.idx), jnp.asarray(op.rows.val),
            jnp.asarray(op.cols.idx), jnp.asarray(op.cols.val),
            jnp.zeros(op.n_pad), jnp.zeros(op.m_pad))
    return op, args


@pytest.mark.parametrize("m,n,m_eq,frozen", [
    (41, 33, 20, 0.0),          # ragged blocks both sides
    (40, 32, 16, 0.4),          # freeze masks active
    (9, 6, 4, 0.0),             # single block each side
])
def test_pdhg_burst_matches_ref_oracle(m, n, m_eq, frozen):
    rng = np.random.default_rng(m + n)
    op, args = _burst_args(rng, m, n, 8 * m, m_eq, frozen_frac=frozen)
    kw = dict(row_meta=op.rows.meta, col_meta=op.cols.meta, iters=60)
    xk, yk, wk = ops.pdhg_burst(*args, **kw)
    xr, yr, wr = ref.pdhg_ell_burst_ref(*args, **kw)
    # same traced ops either side; only XLA fusion decisions may differ
    # between the two compiled programs, so agreement is ~1 ulp
    np.testing.assert_allclose(np.asarray(xk), np.asarray(xr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(wk), np.asarray(wr), atol=1e-6)
    # padded slots stayed pinned at zero through the whole burst
    assert np.all(np.asarray(xk)[n:] == 0.0)
    assert np.all(np.asarray(yk)[m:] == 0.0)
    assert np.all(np.asarray(wk)[m:] == 0.0)


def test_pdhg_burst_tracks_xla_kernel():
    """Both lowerings run the same update on a real routing LP — only
    the SpMV reduction order differs, so short trajectories agree to fp
    tolerance (long ones drift at fp-noise scale, which is why backend
    equivalence is asserted on metrics, not iterates)."""
    topo = topology.build("pon3")
    pat = traffic.pattern("uniform", n_map=3, n_reduce=2, total_gbits=6.0)
    cf = traffic.generate_batch(topo, pat, [0])[0]
    p = timeslot.ScheduleProblem(
        topo, cf, n_slots=timeslot.suggest_n_slots(topo, cf), path_slack=2)
    lp, _ = solver.build_routing_lp(p, "time")
    cscale = max(float(np.abs(lp.c).max(initial=0.0)), 1e-12)
    xmax = np.where(np.isfinite(lp.xmax), lp.xmax, 1e12)

    x_xla, y_xla, _, _ = solver._pdhg_kernel_state(
        jnp.asarray(lp.c / cscale), jnp.asarray(lp.row), jnp.asarray(lp.col),
        jnp.asarray(lp.val), jnp.asarray(lp.b), jnp.asarray(lp.h),
        jnp.asarray(xmax), jnp.zeros(lp.n), jnp.zeros(lp.m),
        lp.m, lp.n, lp.m_eq, 200)

    op, vecs, ell = solver._pack_pallas(lp.c / cscale, lp.row, lp.col,
                                        lp.val, lp.b, lp.h, xmax, lp.m_eq)
    x_pl, y_pl, _ = ops.pdhg_burst(
        *vecs, jnp.zeros(op.n_pad, bool), jnp.zeros(op.m_pad, bool), *ell,
        jnp.zeros(op.n_pad), jnp.zeros(op.m_pad),
        row_meta=op.rows.meta, col_meta=op.cols.meta, iters=200)
    scale = float(jnp.abs(x_xla).max())
    np.testing.assert_allclose(np.asarray(x_pl)[:lp.n], np.asarray(x_xla),
                               atol=2e-4 * max(scale, 1.0))
    np.testing.assert_allclose(np.asarray(y_pl)[:lp.m], np.asarray(y_xla),
                               atol=2e-4)


def test_pdhg_adaptive_matches_xla_adaptive():
    """The fused Pallas adaptive loop freezes/stops like the XLA one on
    a block-stacked batch (same chunk schedule, same tolerances)."""
    topo = topology.build("bcube")
    pat = traffic.pattern("uniform", n_map=3, n_reduce=2, total_gbits=6.0)
    probs = [timeslot.ScheduleProblem(
                 topo, cf, n_slots=timeslot.suggest_n_slots(topo, cf),
                 path_slack=2)
             for cf in traffic.generate_batch(topo, pat, range(3))]
    lps = [solver.build_routing_lp(p, "time")[0] for p in probs]
    rx = solver.solve_lp_batch(lps, iters=2000, tol=2e-3)
    rp = solver.solve_lp_batch(lps, iters=2000, tol=2e-3, backend="pallas")
    for a, b in zip(rx, rp):
        assert b.primal_residual <= 2e-3
        # identical chunk schedule => identical iteration counts unless a
        # residual lands within fp noise of the tolerance boundary
        assert abs(a.iterations - b.iterations) <= 500
        np.testing.assert_allclose(b.x, a.x, atol=5e-3)


@pytest.mark.parametrize("topo_name", list(topology.BUILDERS))
def test_backend_equivalence_all_topologies(topo_name):
    """solve_fast(backend="pallas") reproduces the "xla" backend's exact
    paper-model metrics within 1e-4 relative on every architecture."""
    topo = topology.build(topo_name)
    pat = traffic.pattern("uniform", n_map=3, n_reduce=2, total_gbits=6.0)
    cf = traffic.generate_batch(topo, pat, [0])[0]
    p = timeslot.ScheduleProblem(
        topo, cf, n_slots=timeslot.suggest_n_slots(topo, cf), path_slack=2)
    for objective in ("energy", "time"):
        rx = solver.solve_fast(p, objective, iters=2000)
        rp = solver.solve_fast(p, objective, iters=2000, backend="pallas")
        assert rp.metrics.feasible
        assert rp.remaining_gbits < 1e-6
        np.testing.assert_allclose(rp.metrics.energy_j, rx.metrics.energy_j,
                                   rtol=1e-4)
        np.testing.assert_allclose(rp.metrics.completion_s,
                                   rx.metrics.completion_s, rtol=1e-4)


def test_unknown_backend_rejected():
    topo = topology.build("pon3")
    pat = traffic.pattern("uniform", n_map=2, n_reduce=2, total_gbits=4.0)
    cf = traffic.generate_batch(topo, pat, [0])[0]
    p = timeslot.ScheduleProblem(topo, cf, n_slots=4)
    with pytest.raises(ValueError, match="unknown solver backend"):
        solver.solve_fast(p, "energy", backend="triton")
