"""The block-sparse tile operator (core.tile_spmv) against the COO
scatter it replaces on a TPU, and the stacked layout that keeps its
shapes independent of the order of a stack.

On a CPU the solver keeps the COO pair; these tests force the tile
operator where they need it (`solver._tiled_spmv`).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pdhg, solver, tile_spmv, timeslot, topology, traffic


def _routing_lps(name, n, objective="time", pattern="uniform", **kw):
    topo = topology.build(name, **kw)
    pat = traffic.pattern(pattern, n_map=3, n_reduce=2, total_gbits=6.0)
    return [solver.build_routing_lp(timeslot.ScheduleProblem(
                topo, cf, n_slots=timeslot.suggest_n_slots(topo, cf),
                path_slack=2), objective)[0]
            for cf in traffic.generate_batch(topo, pat, range(n))]


def _random_lp(rng, n, m_eq, m_ub, nnz, *, dups=0, empty=False):
    """A random LP in the solver's COO form; `dups` repeated (row, col)
    entries, and with `empty` a third of the rows and columns hold no
    entry."""
    m = m_eq + m_ub
    rows = rng.choice(m, max(m * 2 // 3, 1), replace=False) if empty \
        else np.arange(m)
    cols = rng.choice(n, max(n * 2 // 3, 1), replace=False) if empty \
        else np.arange(n)
    row = rng.choice(rows, nnz)
    col = rng.choice(cols, nnz)
    k = rng.integers(0, nnz, dups)
    row, col = np.concatenate([row, row[k]]), np.concatenate([col, col[k]])
    return solver.StructuredLP(
        c=rng.uniform(0.1, 1.0, n), row=row, col=col,
        val=rng.normal(size=len(row)), b=rng.uniform(0, 1, m_eq),
        h=rng.uniform(0, 1, m_ub), xmax=rng.uniform(1, 2, n))


def _case(name):
    rng = np.random.default_rng(7)
    if name == "random":
        return [_random_lp(rng, 300, 90, 140, 1500, dups=40),
                _random_lp(rng, 41, 7, 0, 60)], False
    if name == "random-empty-rows-cols":
        return [_random_lp(rng, 200, 150, 30, 400, empty=True)], False
    if name == "spine-leaf":
        return _routing_lps("spine-leaf", 1), False
    if name == "pon3":
        return _routing_lps("pon3", 1), False
    if name == "fat-tree-k4":
        return _routing_lps("fat-tree", 1, "energy", k=4), False
    if name == "bucket-padded-stack":
        # bucket padding adds zeros at (0, 0); the repeated entries
        # share slots with the originals
        return ([_random_lp(rng, 130, 60, 77, 700, dups=25)]
                + _routing_lps("pon3", 2, "energy", pattern="skew")), True
    raise KeyError(name)


@pytest.mark.parametrize("case", [
    "random", "random-empty-rows-cols", "spine-leaf", "pon3", "fat-tree-k4",
    "bucket-padded-stack"])
def test_tile_pair_matches_coo(case):
    """K.x and K^T.y through the tiles equal the COO scatter (and the
    dense float64 product) to float32 rounding."""
    lps, bucket = _case(case)
    g = solver.block_stack(lps).lp
    gp, st = solver._tile_layout(lps, g, bucket)
    if bucket:
        assert len(gp.val) > len(g.val) and not gp.val[len(g.val):].any()
    rng = np.random.default_rng(1)
    x = rng.normal(size=gp.n).astype(np.float32)
    y = rng.normal(size=gp.m).astype(np.float32)
    row, col, val = (jnp.asarray(a) for a in (gp.row, gp.col, gp.val))
    coo = pdhg.coo_pair(row, col, val, gp.m, gp.n)
    tiles = tile_spmv.operator_pair(
        tuple(jnp.asarray(a) for a in st.arrays()), val, gp.m, gp.n)
    K = np.zeros((gp.m, gp.n))
    np.add.at(K, (gp.row, gp.col), gp.val.astype(np.float32))
    for i, (v, dense) in enumerate(((x, K), (y, K.T))):
        want = dense @ v.astype(np.float64)
        scale = (np.abs(dense) @ np.abs(v)).max()
        got_tile = np.asarray(tiles[i](jnp.asarray(v)))
        got_coo = np.asarray(coo[i](jnp.asarray(v)))
        np.testing.assert_allclose(got_tile, got_coo, rtol=0,
                                   atol=4e-6 * scale)
        np.testing.assert_allclose(got_tile, want, rtol=0, atol=4e-6 * scale)


def _table(d: tile_spmv.Direction, val):
    t = np.zeros(d.tiles * tile_spmv.SLOTS)
    np.add.at(t, d.slot, np.pad(val, (0, len(d.slot) - len(val))))
    return t.reshape(d.tiles, tile_spmv.SUB, tile_spmv.LANES)


def test_instance_tiles_same_alone_and_stacked():
    """Stacked, each instance's tiles are its tiles alone: the same
    values, segments and blocks up to the instance's offsets."""
    lps = (_routing_lps("pon3", 2, pattern="skew")
           + _routing_lps("spine-leaf", 1))
    _, st = solver._tile_layout(lps, solver.block_stack(lps).lp, False)
    plans = [solver._tile_plan_cached(lp) for lp in lps]
    te = np.cumsum([0] + [p.kx_eq for p in plans])
    tu = te[-1] + np.cumsum([0] + [p.kx.tiles - p.kx_eq for p in plans])
    tt = np.cumsum([0] + [p.kty.tiles for p in plans])
    val = np.concatenate([lp.val for lp in lps])
    kx, kty = _table(st.kx, val), _table(st.kty, val)
    for i, lp in enumerate(lps):
        _, alone = solver._tile_layout([lp], solver.block_stack([lp]).lp,
                                       False)
        a_kx, a_kty = _table(alone.kx, lp.val), _table(alone.kty, lp.val)
        e, t_kx, t_kty = (plans[i].kx_eq, plans[i].kx.tiles,
                          plans[i].kty.tiles)
        # tiles past the plan's are the capacity's padding: empty
        assert not a_kx[t_kx:].any() and not a_kty[t_kty:].any()
        parts = [(st.kx, kx, slice(te[i], te[i] + e), alone.kx, a_kx,
                  slice(0, e)),
                 (st.kx, kx, slice(tu[i], tu[i + 1]), alone.kx, a_kx,
                  slice(e, t_kx)),
                 (st.kty, kty, slice(tt[i], tt[i + 1]), alone.kty, a_kty,
                  slice(0, t_kty))]
        for sd, stab, s, ad, atab, a in parts:
            np.testing.assert_array_equal(stab[s], atab[a])
            # one offset a field, but K^T.y's segments are rows: one
            # offset for the equality rows, one for the inequality rows
            eq = ad.seg[a] < plans[i].m_eq_a // tile_spmv.SUB
            for f in ("seg", "blk"):
                shift = getattr(sd, f)[s] - getattr(ad, f)[a]
                groups = (eq, ~eq) if sd is st.kty and f == "seg" else (
                    slice(None),)
                for grp in groups:
                    assert len(np.unique(shift[grp])) <= 1


def _dispatches(monkeypatch, lps, **kw):
    """Solve with the tile operator forced; every _pdhg_run_adaptive
    call's argument shapes and static arguments, and the results."""
    monkeypatch.setattr(solver, "_tiled_spmv", lambda: True)
    calls = []
    orig = solver._pdhg_run_adaptive

    def spy(*a):
        calls.append(tuple(
            tuple(np.shape(leaf) for leaf in x) if isinstance(x, tuple)
            else np.shape(x) if hasattr(x, "shape") else x for x in a))
        return orig(*a)
    monkeypatch.setattr(solver, "_pdhg_run_adaptive", spy)
    return calls, solver.solve_lp_batch(lps, **kw)


def test_stacked_shapes_do_not_depend_on_order(monkeypatch):
    """Three orders of one set of instances: every tensor of the
    dispatch has the same shape (tile counts included), and each
    instance's iterate is the same."""
    lps = (_routing_lps("pon3", 2, pattern="skew")
           + _routing_lps("spine-leaf", 2, pattern="skew"))
    orders = [[0, 1, 2, 3], [3, 1, 0, 2], [2, 3, 1, 0]]
    shapes, xs = [], []
    for order in orders:
        calls, res = _dispatches(monkeypatch, [lps[i] for i in order],
                                 iters=1000, max_restarts=0,
                                 tol=float("inf"))
        assert len(calls) == 1
        shapes.append(calls[0])
        xs.append({i: r.x for i, r in zip(order, res)})
    assert shapes[0][-1] is not None          # the tile operator ran
    assert shapes[1] == shapes[0] and shapes[2] == shapes[0]
    for i in range(len(lps)):
        for got in xs[1:]:
            np.testing.assert_array_equal(got[i], xs[0][i])


def test_adaptive_tile_trajectory_matches_coo(monkeypatch):
    """The fused adaptive PDHG with the tile operator follows the COO
    trajectory: the same chunks per instance, and iterates equal to
    float32 reduction order (the tolerance of
    test_batch_lp_matches_solve_lp, plus as much relative to |x|)."""
    from test_solver_batch import make_problems

    lps = [solver.build_routing_lp(p, "time")[0]
           for p in make_problems(n=3, pattern="skew")]
    coo = solver.solve_lp_batch(lps, iters=1500, max_restarts=0)
    solver.reset_dispatch_stats()
    _, tiled = _dispatches(monkeypatch, lps, iters=1500, max_restarts=0)
    stats = solver.dispatch_stats()
    assert stats.tiled_dispatches == stats.dispatches > 0
    assert 0 < stats.nnz_iters_useful < stats.tile_slots_run
    for a, b in zip(coo, tiled):
        assert b.iterations == a.iterations
        np.testing.assert_allclose(b.x, a.x, rtol=1e-6, atol=1e-6)
        assert b.primal_residual == pytest.approx(a.primal_residual,
                                                  rel=1e-3, abs=1e-9)


def test_cpu_keeps_coo_operator():
    """On a CPU every dispatch applies K as COO scatters."""
    assert not solver._tiled_spmv()
    solver.reset_dispatch_stats()
    solver.solve_lp_batch(_routing_lps("pon3", 2), iters=1000,
                          max_restarts=0)
    stats = solver.dispatch_stats()
    assert stats.dispatches > 0
    assert stats.tiled_dispatches == stats.tile_slots_run == 0


@pytest.mark.parametrize("kind", ["solve_lp", "batch-adaptive",
                                  "batch-fixed"])
def test_every_dispatch_kind_uses_tiles(kind, monkeypatch):
    """With _tiled_spmv true, every kind of dispatch applies K as tiles
    (one _tile_layout a dispatch, counted tiled) and lands where the
    COO operator does, to float32 summation order."""
    lps = _routing_lps("pon3", 2, pattern="skew")

    def solve():
        if kind == "solve_lp":
            return [solver.solve_lp(lp, iters=800, max_restarts=1)
                    for lp in lps]
        return solver.solve_lp_batch(lps, iters=800, max_restarts=1,
                                     adaptive=kind == "batch-adaptive")

    coo = solve()
    monkeypatch.setattr(solver, "_tiled_spmv", lambda: True)
    layouts = []
    orig = solver._tile_layout

    def spy(*a, **kw):
        layouts.append(len(a[0]))
        return orig(*a, **kw)
    monkeypatch.setattr(solver, "_tile_layout", spy)
    solver.reset_dispatch_stats()
    tiled = solve()
    assert layouts and sum(layouts) >= len(lps)
    stats = solver.dispatch_stats()
    if kind != "solve_lp":
        assert stats.tiled_dispatches == stats.dispatches == len(layouts)
    for a, b in zip(coo, tiled):
        assert b.iterations == a.iterations
        np.testing.assert_allclose(b.x, a.x, rtol=1e-5, atol=1e-5)


def test_tile_plan_cached_per_pattern():
    """Plans are cached per sparsity pattern: a re-solve of the same LP
    hits, a new pattern misses."""
    lps = _routing_lps("pon3", 2, pattern="skew")
    solver.reset_build_caches()
    for lp in (lps[0], lps[0], lps[1]):
        solver._tile_plan_cached(lp)
    stats = solver.build_cache_stats()
    assert (stats.tile_hits, stats.tile_misses) == (1, 2)


def test_tile_capacity_pads_and_never_shrinks():
    """Tile counts get 1/16 headroom on a shape bucket, and a dispatch
    of the same dims never gets fewer tiles than before."""
    solver.reset_build_caches()
    dims = (1024, 512, 768, 4096)
    first = solver._tile_capacity(dims, (640, 700))
    assert first == (solver._bucket(680), solver._bucket(743))
    assert solver._tile_capacity(dims, (600, first[1])) == first
    grown = solver._tile_capacity(dims, (first[0] + 1, 10))
    assert grown[0] > first[0] and grown[1] == first[1]
    assert solver._tile_capacity((1024, 512, 768, 8192), (10, 10)) == (
        32, 32)
