"""The one PDHG update (core.pdhg) over its three operator pairs.

Three layers of pinning, innermost first:

  1. layout — the blocked-ELL pack (`ell_pack_sharded`, one shard)
     reconstructs the dense operator exactly, including ragged tail
     blocks, empty rows, and per-block widths, and its pair applies it;
  2. update — the shared burst over the ELL pair follows the COO pair's
     trajectory to float32 summation order, freeze masks included, and
     frozen coordinates hold bit for bit under every operator;
  3. solver — `solve_fast` with the dense tiles reproduces the COO
     operator's exact paper-model metrics within 1e-4 relative on small
     instances of every topology family.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pdhg, solver, tile_spmv, timeslot, topology, traffic
from repro.kernels import pdhg_spmv


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


def _ell_pair(op):
    """(Kx, KTy) over a one-shard pack, on its padded vectors."""
    return pdhg_spmv.ell_pair(
        *(jnp.asarray(a) for a in (op.row_idx, op.row_val, op.col_idx,
                                   op.col_val)), op.row_meta, op.col_meta)


def _unpack(idx, val, meta, n_rows, n_cols):
    """Dense (n_rows, n_cols) matrix of one blocked-ELL direction."""
    offsets, widths, bm, _ = meta
    dense = np.zeros((n_rows, n_cols), np.float32)
    for b, (off, w) in enumerate(zip(offsets, widths)):
        blk_i = idx[off:off + bm * w].reshape(bm, w)
        blk_v = val[off:off + bm * w].reshape(bm, w)
        for i in range(bm):
            np.add.at(dense[b * bm + i], blk_i[i], blk_v[i])
    return dense


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
    op = pdhg_spmv.ell_pack_sharded(row, col, val, m, n, shards=1, bm=bm,
                                    align=align)
    K = _dense(row, col, val, m, n).astype(np.float32)

    # rows direction: one stored row per constraint, gathering x
    dense_rows = _unpack(op.row_idx, op.row_val, op.row_meta, op.m_pad, n)
    np.testing.assert_allclose(dense_rows[:m], K, atol=1e-6)
    assert np.all(dense_rows[m:] == 0.0)
    # cols direction: one stored row per variable, gathering y
    dense_cols = _unpack(op.col_idx, op.col_val, op.col_meta, op.n_pad,
                         op.m_pad)
    np.testing.assert_allclose(dense_cols[:n, :m], K.T, atol=1e-6)
    assert np.all(dense_cols[n:] == 0.0)

    # block invariants: widths aligned, offsets contiguous
    for idx, meta in ((op.row_idx, op.row_meta), (op.col_idx, op.col_meta)):
        offsets, widths, bm_, _ = meta
        assert all(w % align == 0 and w >= align for w in widths)
        off = 0
        for o, w in zip(offsets, widths):
            assert o == off
            off += bm_ * w
        assert len(idx) == off


def test_ell_spmv_matches_dense():
    rng = np.random.default_rng(7)
    m, n = 45, 31
    row, col, val = _random_coo(rng, m, n, 400, wide_rows=3)
    op = pdhg_spmv.ell_pack_sharded(row, col, val, m, n, shards=1)
    Kx, KTy = _ell_pair(op)
    K = _dense(row, col, val, m, n).astype(np.float32)
    x = rng.normal(size=n).astype(np.float32)
    y = rng.normal(size=m).astype(np.float32)
    kx = np.asarray(Kx(jnp.asarray(np.pad(x, (0, op.n_pad - n)))))
    kty = np.asarray(KTy(jnp.asarray(np.pad(y, (0, op.m_pad - m)))))
    np.testing.assert_allclose(kx[:m], K @ x, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(kty[:n], K.T @ y, atol=1e-4, rtol=1e-5)
    assert np.all(kx[m:] == 0.0) and np.all(kty[n:] == 0.0)


def _random_lp(rng, m, n, m_eq, nnz):
    """A random LP in the solver's stacked form (c normalized, xmax
    finite)."""
    row, col, val = _random_coo(rng, m, n, nnz, wide_rows=2)
    return solver.StructuredLP(
        c=rng.uniform(0.1, 1.0, n), row=row, col=col, val=val,
        b=rng.normal(size=m_eq), h=rng.normal(size=m - m_eq),
        xmax=rng.uniform(0.5, 4.0, n))


def _masks(rng, n, m, frozen):
    keep_n = rng.random(n) < frozen
    keep_m = rng.random(m) < frozen
    return keep_n, keep_m


@pytest.mark.parametrize("m,n,m_eq,frozen", [
    (41, 33, 20, 0.0),          # ragged blocks both sides
    (40, 32, 16, 0.4),          # freeze masks active
    (9, 6, 4, 0.0),             # single block each side
])
def test_pdhg_burst_matches_ref_oracle(m, n, m_eq, frozen):
    """The shared burst over the blocked-ELL pair (the row-sharded
    solve's operator, one shard) follows the COO pair's trajectory:
    the same update, so only float32 summation order differs; frozen
    coordinates hold exactly and padded slots stay at zero."""
    rng = np.random.default_rng(m + n)
    lp = _random_lp(rng, m, n, m_eq, 8 * m)
    op, vecs, ell = solver._pack_sharded(lp, 1)
    c, tau, xmax, q, sig, ub = vecs
    keep_n, keep_m = _masks(rng, n, m, frozen)
    x0 = rng.uniform(0.0, 1.0, n).astype(np.float32)
    y0 = rng.normal(size=m).astype(np.float32)

    Kx, KTy = pdhg_spmv.ell_pair(*ell, op.row_meta, op.col_meta)
    xe, ye = pdhg.burst(
        Kx, KTy, c, tau, sig, q, xmax, ub,
        jnp.pad(x0, (0, op.n_pad - n)), jnp.pad(y0, (0, op.m_pad - m)), 60,
        keep_n=jnp.pad(keep_n, (0, op.n_pad - n)),
        keep_m=jnp.pad(keep_m, (0, op.m_pad - m)))
    we = pdhg.residual(Kx, q, ub, xe)
    xe, ye, we = (np.asarray(a) for a in (xe, ye, we))

    row, col, val, b, h = (jnp.asarray(a) for a in (lp.row, lp.col, lp.val,
                                                    lp.b, lp.h))
    qc, tauc, sigc, ubc = pdhg.preconditioners(row, col, val, b, h, m, n,
                                               m_eq)
    Kxc, KTyc = pdhg.coo_pair(row, col, val, m, n)
    xc, yc = pdhg.burst(Kxc, KTyc, jnp.asarray(lp.c), tauc, sigc, qc,
                        jnp.asarray(lp.xmax), ubc, jnp.asarray(x0),
                        jnp.asarray(y0), 60, keep_n=jnp.asarray(keep_n),
                        keep_m=jnp.asarray(keep_m))
    wc = np.asarray(pdhg.residual(Kxc, qc, ubc, xc))
    xc, yc = np.asarray(xc), np.asarray(yc)

    scale = max(float(np.abs(xc).max()), 1.0)
    np.testing.assert_allclose(xe[:n], xc, atol=1e-4 * scale)
    np.testing.assert_allclose(ye[:m], yc, atol=1e-4 * max(
        float(np.abs(yc).max()), 1.0))
    np.testing.assert_allclose(we[:m], wc, atol=1e-4 * max(
        float(np.abs(wc).max()), 1.0))
    # frozen coordinates held bit for bit on both pairs
    assert np.array_equal(xe[:n][keep_n], x0[keep_n])
    assert np.array_equal(ye[:m][keep_m], y0[keep_m])
    assert np.array_equal(xc[keep_n], x0[keep_n])
    # padded slots stayed pinned at zero through the whole burst
    assert np.all(xe[n:] == 0.0) and np.all(ye[m:] == 0.0)
    assert np.all(we[m:] == 0.0)


def _operator_pair(name, lp):
    """(Kx, KTy, x-length, y-length, to_pad) for one of the three
    operators over `lp`; `to_pad(v, pos)` places an unpadded vector."""
    if name == "coo":
        pair = pdhg.coo_pair(*(jnp.asarray(a) for a in (lp.row, lp.col,
                                                        lp.val)),
                             lp.m, lp.n)
        return (*pair, np.arange(lp.n), np.arange(lp.m), lp.n, lp.m)
    if name == "tiles":
        gp, st = solver._tile_layout([lp], solver.block_stack([lp]).lp,
                                     False)
        pair = tile_spmv.operator_pair(
            tuple(jnp.asarray(a) for a in st.arrays()),
            jnp.asarray(gp.val), gp.m, gp.n)
        return (*pair, st.pos_n, st.pos_m, gp.n, gp.m)
    op = pdhg_spmv.ell_pack_sharded(lp.row, lp.col, lp.val, lp.m, lp.n,
                                    shards=1)
    return (*_ell_pair(op), np.arange(lp.n), np.arange(lp.m), op.n_pad,
            op.m_pad)


@pytest.mark.parametrize("name", ["coo", "tiles", "ell"])
def test_frozen_coordinates_hold_bit_for_bit(name):
    """Under every operator, the coordinates a burst's freeze masks mark
    come out bit for bit as they went in, whatever the others do."""
    rng = np.random.default_rng(3)
    lp = _random_lp(rng, 90, 70, 40, 600)
    Kx, KTy, pos_n, pos_m, n_len, m_len = _operator_pair(name, lp)
    keep_n, keep_m = _masks(rng, lp.n, lp.m, 0.5)

    def place(v, pos, fill=0.0):
        out = np.full(n_len if pos is pos_n else m_len, fill, v.dtype)
        out[pos] = v
        return jnp.asarray(out)

    x0 = rng.uniform(0.0, 1.0, lp.n).astype(np.float32)
    y0 = rng.normal(size=lp.m).astype(np.float32)
    tau = rng.uniform(0.01, 0.2, lp.n).astype(np.float32)
    sig = rng.uniform(0.01, 0.2, lp.m).astype(np.float32)
    q = np.concatenate([lp.b, lp.h]).astype(np.float32)
    ub = np.arange(lp.m) >= lp.b.size
    x, y = pdhg.burst(
        Kx, KTy, place(lp.c.astype(np.float32), pos_n), place(tau, pos_n),
        place(sig, pos_m), place(q, pos_m),
        place(lp.xmax.astype(np.float32), pos_n), place(ub, pos_m, True),
        place(x0, pos_n), place(y0, pos_m), 40,
        keep_n=place(keep_n, pos_n, True), keep_m=place(keep_m, pos_m, True))
    x, y = np.asarray(x)[pos_n], np.asarray(y)[pos_m]
    assert np.array_equal(x[keep_n], x0[keep_n])
    assert np.array_equal(y[keep_m], y0[keep_m])
    # the free coordinates did move
    assert not np.array_equal(x[~keep_n], x0[~keep_n])
    assert not np.array_equal(y[~keep_m], y0[~keep_m])


@pytest.mark.parametrize("topo_name", list(topology.BUILDERS))
def test_backend_equivalence_all_topologies(topo_name, monkeypatch):
    """solve_fast with the dense tiles (as a TPU runs it) reproduces the
    COO operator's exact paper-model metrics within 1e-4 relative on
    every architecture."""
    topo = topology.build(topo_name)
    pat = traffic.pattern("uniform", n_map=3, n_reduce=2, total_gbits=6.0)
    cf = traffic.generate_batch(topo, pat, [0])[0]
    p = timeslot.ScheduleProblem(
        topo, cf, n_slots=timeslot.suggest_n_slots(topo, cf), path_slack=2)
    for objective in ("energy", "time"):
        monkeypatch.setattr(solver, "_tiled_spmv", lambda: False)
        rc = solver.solve_fast(p, objective, iters=2000)
        monkeypatch.setattr(solver, "_tiled_spmv", lambda: True)
        rt = solver.solve_fast(p, objective, iters=2000)
        assert rt.metrics.feasible
        assert rt.remaining_gbits < 1e-6
        np.testing.assert_allclose(rt.metrics.energy_j, rc.metrics.energy_j,
                                   rtol=1e-4)
        np.testing.assert_allclose(rt.metrics.completion_s,
                                   rc.metrics.completion_s, rtol=1e-4)


def test_shards_below_one_rejected():
    """A shard count below one is rejected before anything is solved."""
    topo = topology.build("pon3")
    pat = traffic.pattern("uniform", n_map=2, n_reduce=2, total_gbits=4.0)
    cf = traffic.generate_batch(topo, pat, [0])[0]
    p = timeslot.ScheduleProblem(topo, cf, n_slots=4)
    with pytest.raises(ValueError, match="shards must be >= 1"):
        solver.solve_fast(p, "energy", shards=0)
    lp, _ = solver.build_routing_lp(p, "energy")
    with pytest.raises(ValueError, match="shards must be >= 1"):
        solver.solve_lp_batch([lp], shards=0)
