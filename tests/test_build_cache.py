"""Structure-cache equivalence suite for the vectorized build path.

PR 5 rewrote `build_routing_lp` as vectorized index arithmetic with a
cross-solve ProblemStructure cache and shape-bucketed PDHG dispatches;
the tile operator added a tile-plan cache.  These tests pin the three invariants
that make that refactor safe:

  1. the vectorized assembly reproduces the historical loop builder
     (`solver._build_routing_lp_loops`) **bit-for-bit** — arrays, row
     numbering, COO entry order, and row-identity keys — on every
     topology, both objectives, including degraded, epoch-merged and
     zero-flow instances;
  2. cache hits are invisible: solving with a hot structure/tile-plan
     cache returns bit-identical metrics to a cold build, under both
     operators, and an arrival-trace re-solve with unchanged structure
     performs zero LP rebuilds (the counters in
     `solver.build_cache_stats()` assert it);
  3. shape bucketing is value-neutral: bucketed solves match unbucketed
     within the golden 1e-4 envelope (on CPU they are bitwise equal).
"""
import re

import numpy as np
import pytest

from repro.core import (arrivals, failures, solver, timeslot, topology,
                        traffic)

SMALL = dict(n_map=3, n_reduce=2, total_gbits=6.0)
LP_FIELDS = ("c", "row", "col", "val", "b", "h", "xmax")


def _problem(topo_name: str, seed: int = 0, pattern: str = "uniform",
             **kw) -> timeslot.ScheduleProblem:
    topo = topology.build(topo_name)
    cf = traffic.generate(topo, traffic.pattern(pattern, **SMALL), seed)
    return timeslot.ScheduleProblem(
        topo, cf, n_slots=timeslot.suggest_n_slots(topo, cf),
        path_slack=2, **kw)


def _degraded(topo_name: str, seed: int = 0) -> timeslot.ScheduleProblem:
    p = _problem(topo_name, seed)
    return failures.degrade_problem(p, failures.sample(p.topo, "link1", seed))


def _merged(topo_name: str) -> timeslot.ScheduleProblem:
    """An epoch-merged problem: two co-flow sets concatenated, the way
    the rolling-horizon driver merges carried residuals + arrivals."""
    topo = topology.build(topo_name)
    pat = traffic.pattern("uniform", **SMALL)
    cf = traffic.concat_coflows([traffic.generate(topo, pat, 0),
                                 traffic.generate(topo, pat, 1)],
                                topo.n_vertices)
    return timeslot.ScheduleProblem(
        topo, cf, n_slots=timeslot.suggest_n_slots(topo, cf), path_slack=2)


def _assert_lp_equal(a, b, label=""):
    for name in LP_FIELDS:
        va, vb = getattr(a, name), getattr(b, name)
        assert va.shape == vb.shape, (label, name, va.shape, vb.shape)
        np.testing.assert_array_equal(va, vb, err_msg=f"{label} {name}")


def _assert_index_equal(a, b, label=""):
    for name in ("kf", "ke", "kw"):
        np.testing.assert_array_equal(getattr(a, name), getattr(b, name),
                                      err_msg=f"{label} {name}")
    assert (a.n_inj, a.n_theta) == (b.n_inj, b.n_theta), label
    assert a.eq_keys == b.eq_keys, label
    assert a.ub_keys == b.ub_keys, label


def _metrics_tuple(r):
    m = r.metrics
    return (m.energy_j, m.completion_s, m.fairness_term, m.feasible,
            m.max_violation, float(m.served.sum()), r.remaining_gbits)


# ---------------------------------------------------------------------------
# 1. vectorized assembly == historical loop builder, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("objective", ["energy", "time"])
@pytest.mark.parametrize("topo_name", sorted(topology.BUILDERS))
def test_vectorized_matches_loop_builder(topo_name, objective):
    p = _problem(topo_name)
    lp_v, idx_v = solver.build_routing_lp(p, objective, cache=False)
    lp_l, idx_l = solver._build_routing_lp_loops(p, objective)
    _assert_lp_equal(lp_v, lp_l, f"{topo_name}/{objective}")
    _assert_index_equal(idx_v, idx_l, f"{topo_name}/{objective}")


@pytest.mark.parametrize("objective", ["energy", "time"])
def test_vectorized_matches_loops_degraded_and_merged(objective):
    for label, p in [("degraded", _degraded("spine-leaf")),
                     ("degraded-pon", _degraded("pon3")),
                     ("merged", _merged("spine-leaf"))]:
        lp_v, idx_v = solver.build_routing_lp(p, objective, cache=False)
        lp_l, idx_l = solver._build_routing_lp_loops(p, objective)
        _assert_lp_equal(lp_v, lp_l, label)
        _assert_index_equal(idx_v, idx_l, label)


@pytest.mark.parametrize("objective", ["energy", "time"])
def test_vectorized_matches_loops_zero_flow(objective):
    topo = topology.build("spine-leaf")
    p = timeslot.ScheduleProblem(topo, traffic.empty_coflow(topo.n_vertices),
                                 n_slots=2)
    lp_v, _ = solver.build_routing_lp(p, objective, cache=False)
    lp_l, _ = solver._build_routing_lp_loops(p, objective)
    _assert_lp_equal(lp_v, lp_l, "zero-flow")


def test_admissible_matches_loops():
    for topo_name in sorted(topology.BUILDERS):
        p = _problem(topo_name)
        for a, b in zip(solver._admissible(p), solver._admissible_loops(p)):
            np.testing.assert_array_equal(a, b, err_msg=topo_name)


# ---------------------------------------------------------------------------
# 2. cache hits are invisible (and counted)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("objective", ["energy", "time"])
def test_structure_cache_hit_is_bitwise(objective):
    p = _problem("pon3")
    solver.reset_build_caches()
    lp_cold, idx_cold = solver.build_routing_lp(p, objective)
    stats = solver.build_cache_stats()
    assert (stats.structure_misses, stats.structure_hits) == (1, 0)
    lp_hot, idx_hot = solver.build_routing_lp(p, objective)
    stats = solver.build_cache_stats()
    assert (stats.structure_misses, stats.structure_hits) == (1, 1)
    _assert_lp_equal(lp_cold, lp_hot)
    _assert_index_equal(idx_cold, idx_hot)
    # the sparsity pattern is genuinely shared, not rebuilt
    assert lp_hot.row is lp_cold.row and lp_hot.col is lp_cold.col


def test_structure_cache_keying():
    p = _problem("spine-leaf")
    solver.reset_build_caches()
    solver.build_routing_lp(p, "energy")
    # value-only changes reuse the structure: brown-out (scaled caps,
    # same cap>0 pattern) and a doubled horizon both hit ...
    brown = failures.degrade_problem(
        p, failures.FailureScenario("brown", cap_scale=0.5))
    lp_b, _ = solver.build_routing_lp(brown, "energy")
    wide = timeslot.rehorizon(p, 2 * p.n_slots)
    lp_w, _ = solver.build_routing_lp(wide, "energy")
    stats = solver.build_cache_stats()
    assert stats.structure_hits == 2 and stats.structure_misses == 1
    # ... with refreshed values
    lp_p, _ = solver.build_routing_lp(p, "energy")
    assert not np.array_equal(lp_b.h, lp_p.h)
    assert not np.array_equal(lp_w.h, lp_p.h)
    # structural changes miss: a link cut (cap>0 pattern shrinks) and
    # the other objective (theta column)
    cut = _degraded("spine-leaf")
    solver.build_routing_lp(cut, "energy")
    solver.build_routing_lp(p, "time")
    stats = solver.build_cache_stats()
    assert stats.structure_misses == 3


@pytest.mark.parametrize("topo_name", sorted(topology.BUILDERS))
def test_solve_fast_cached_equals_uncached(topo_name, operator):
    p = _problem(topo_name)
    solver.reset_build_caches()
    cold = solver.solve_fast(p, "energy", iters=200, tol=5e-3)
    assert solver.build_cache_stats().structure_hits == 0
    hot = solver.solve_fast(p, "energy", iters=200, tol=5e-3)
    assert solver.build_cache_stats().structure_hits >= 1
    assert _metrics_tuple(cold) == _metrics_tuple(hot)
    np.testing.assert_array_equal(cold.schedule, hot.schedule)


def test_solve_fast_cached_degraded_and_merged(operator):
    for p in (_degraded("spine-leaf"), _merged("spine-leaf")):
        solver.reset_build_caches()
        cold = solver.solve_fast(p, "time", iters=200, tol=5e-3)
        hot = solver.solve_fast(p, "time", iters=200, tol=5e-3)
        assert _metrics_tuple(cold) == _metrics_tuple(hot)
        np.testing.assert_array_equal(cold.schedule, hot.schedule)


def test_solve_fast_warm_cached_equals_uncached(operator):
    """The epoch re-solve primitive: warm-started, epoch-merged flow
    indexing (flow_map), identical with cold and hot build caches."""
    p1 = _problem("spine-leaf", seed=0)
    p2 = _merged("spine-leaf")
    flow_map = np.concatenate([np.arange(p1.coflow.n_flows),
                               np.full(p2.coflow.n_flows
                                       - p1.coflow.n_flows, -1)])

    def run():
        r1 = solver.solve_fast(p1, "energy", iters=200, tol=5e-3)
        return solver.solve_fast_warm(p2, "energy", warm=r1,
                                      flow_map=flow_map, iters=200,
                                      tol=5e-3)

    solver.reset_build_caches()
    cold = run()
    hot = run()
    assert cold.warm_started and hot.warm_started
    assert _metrics_tuple(cold) == _metrics_tuple(hot)
    np.testing.assert_array_equal(cold.schedule, hot.schedule)


def test_arrival_resolve_is_zero_rebuild():
    """Re-solving an unchanged arrival trace performs zero LP rebuilds:
    every epoch's structure (and, on a TPU, its tile plan) is already
    cached, so only value refreshes run."""
    topo = topology.build("spine-leaf")
    pat = traffic.pattern("uniform", **SMALL)
    spec = arrivals.ArrivalSpec(family="poisson", n_coflows=3,
                                mean_interarrival_s=1.0)
    trace = arrivals.generate_trace(topo, pat, spec, seed=0)

    solver.reset_build_caches()
    first = arrivals.run_online(topo, trace, "energy", iters=300, tol=5e-3)
    snap = solver.build_cache_stats().snapshot()
    assert snap.structure_misses > 0
    second = arrivals.run_online(topo, trace, "energy", iters=300, tol=5e-3)
    stats = solver.build_cache_stats()
    assert stats.structure_misses == snap.structure_misses, \
        "re-solving an unchanged trace must not rebuild any LP structure"
    assert stats.tile_misses == snap.tile_misses, \
        "re-solving an unchanged trace must not re-plan any tile layout"
    assert stats.structure_hits > snap.structure_hits
    assert second.total_energy_j == first.total_energy_j
    assert second.makespan_s == first.makespan_s


def test_tile_plan_cache_zero_replan(monkeypatch):
    """With the tile operator, a dispatch plans the tiles only on the
    first solve of a structure; the second solve refills the cached
    plan (zero re-plans)."""
    monkeypatch.setattr(solver, "_tiled_spmv", lambda: True)
    p = _problem("spine-leaf")
    solver.reset_build_caches()
    solver.solve_fast(p, "energy", iters=200, tol=5e-3)
    snap = solver.build_cache_stats().snapshot()
    assert snap.tile_misses > 0
    solver.solve_fast(p, "energy", iters=200, tol=5e-3)
    stats = solver.build_cache_stats()
    assert stats.tile_misses == snap.tile_misses
    assert stats.tile_hits > snap.tile_hits


# ---------------------------------------------------------------------------
# 3. shape bucketing is value-neutral
# ---------------------------------------------------------------------------

def test_bucket_grid_properties():
    for x in list(range(1, 70)) + [100, 333, 1024, 5000, 123457]:
        b = solver._bucket(x)
        assert b >= x
        assert b == solver._bucket(b), "buckets are fixed points"
        if x > 32:
            assert b <= x * 1.15, (x, b)


@pytest.mark.parametrize("objective", ["energy", "time"])
def test_bucketed_batch_matches_unbucketed(objective):
    topo = topology.build("pon3")
    pat = traffic.pattern("uniform", **SMALL)
    probs = [timeslot.ScheduleProblem(
                 topo, cf, n_slots=timeslot.suggest_n_slots(topo, cf),
                 path_slack=2)
             for cf in traffic.generate_batch(topo, pat, range(3))]
    on = solver.solve_fast_batch(probs, objective, iters=400, tol=2e-3,
                                 bucket=True)
    off = solver.solve_fast_batch(probs, objective, iters=400, tol=2e-3,
                                  bucket=False)
    for a, b in zip(on, off):
        np.testing.assert_allclose(a.metrics.energy_j, b.metrics.energy_j,
                                   rtol=1e-4)
        np.testing.assert_allclose(a.metrics.completion_s,
                                   b.metrics.completion_s, rtol=1e-4)
        np.testing.assert_allclose(a.lp_x, b.lp_x, rtol=1e-4, atol=1e-7)
        assert a.iterations == b.iterations


def test_bucketed_warm_matches_unbucketed():
    p = _problem("spine-leaf")
    warm = solver.solve_fast(p, "energy", iters=300, tol=5e-3)
    wide = timeslot.rehorizon(p, 2 * p.n_slots)
    on = solver.solve_fast_warm(wide, "energy", warm=warm, iters=300,
                                tol=5e-3, bucket=True)
    off = solver.solve_fast_warm(wide, "energy", warm=warm, iters=300,
                                 tol=5e-3, bucket=False)
    assert on.warm_started and off.warm_started
    np.testing.assert_allclose(on.metrics.energy_j, off.metrics.energy_j,
                               rtol=1e-4)
    assert on.iterations == off.iterations


# ---------------------------------------------------------------------------
# sweep --profile and the benchmark trend gate
# ---------------------------------------------------------------------------

def test_sweep_profile_prints_build_solve_split():
    from repro.sweep import runner
    spec = runner.SweepSpec(topos=("spine-leaf",), objectives=("energy",),
                            patterns=("uniform",), seeds=(0, 1),
                            total_gbits=8.0, n_map=4, n_reduce=3,
                            iters=600, oracle_check=0, profile=True)
    lines: list[str] = []
    records, _ = runner.run_sweep(spec, log=lines.append)
    assert len(records) == 2
    prof = [ln for ln in lines if "profile" in ln]
    assert any("problem generation" in ln for ln in prof)
    cell = [ln for ln in prof if "spine-leaf/uniform/min-energy:" in ln]
    assert len(cell) == 1
    # the split comes from the program's spans (repro.trace): build,
    # PDHG host and device work, the pack split three ways, and certify
    ms = {k: float(v) for k, v in re.findall(r"(\w+) +([\d.]+) ms",
                                            cell[0])}
    assert set(ms) == {"build", "pdhg", "pack", "certify", "other", "total"}
    parts = dict(re.findall(r"(\w+) ([\d.]+)[,)]", cell[0]))
    assert set(parts) >= {"stack", "run", "unstack", "decompose", "slots",
                          "evaluate", "paths", "states"}
    assert ms["build"] > 0 and ms["pdhg"] > 0 and ms["pack"] > 0
    # the decomposition peels at least one path a flow (12 a problem)
    assert int(parts["paths"]) >= 24
    assert int(parts["states"]) >= int(parts["paths"])
    assert float(parts["run"]) > 0 and float(parts["slots"]) > 0
    assert ms["build"] + ms["pdhg"] + ms["pack"] + ms["certify"] \
        <= ms["total"] + 0.1
    # every schedule is evaluated: one accounting read each, of its
    # nonzero entries out of its dense cells
    calls, entries, cells = map(int, re.search(
        r"accounting (\d+) calls, (\d+)/(\d+) entries", cell[0]).groups())
    assert calls >= 2 and 0 < entries < cells
    assert "structure" in cell[0]
    # the recording ends with the sweep
    from repro import trace
    assert trace.active() is None


def test_bench_trend_tool_modes():
    import importlib.util
    import pathlib
    spec = importlib.util.spec_from_file_location(
        "check_bench_trend",
        pathlib.Path(__file__).resolve().parent.parent / "tools"
        / "check_bench_trend.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    def doc(loop, batch, args):
        return {"benches": {"sweep_bench": {"args": args, "records": [
            {"name": "sweep/a/loop", "wall_ms": loop},
            {"name": "sweep/a/batch", "wall_ms": batch},
            {"name": "sweep/aggregate/xla", "wall_ms": batch},
        ]}}}

    base = doc(100.0, 50.0, {"seeds": 8, "iters": 1500})
    # absolute mode (same args): direct aggregate wall comparison
    assert mod.check_sweep(
        base, doc(100.0, 50.0, {"seeds": 8, "iters": 1500}), 0.2) == 0
    assert mod.check_sweep(
        base, doc(100.0, 70.0, {"seeds": 8, "iters": 1500}), 0.2) == 1
    # normalized mode (different seeds, same budget): batch/loop ratio
    # comparison, insensitive to machine speed and benchmark scale
    assert mod.check_sweep(
        base, doc(200.0, 100.0, {"seeds": 4, "iters": 1500}), 0.2) == 0
    assert mod.check_sweep(
        base, doc(200.0, 140.0, {"seeds": 4, "iters": 1500}), 0.2) == 1
    # a different iteration budget shifts the ratio for reasons other
    # than batch-path health: reported and skipped, never gated
    assert mod.check_sweep(
        base, doc(200.0, 140.0, {"seeds": 4, "iters": 600}), 0.2) == 0
    # nothing comparable: reported, not failed
    assert mod.check_sweep({}, doc(1.0, 1.0, {}), 0.2) == 0


# ---------------------------------------------------------------------------
# rehorizon: the retry-ladder fast copy
# ---------------------------------------------------------------------------

def test_rehorizon_matches_full_construction():
    p = _problem("dcell")
    q = timeslot.rehorizon(p, 2 * p.n_slots)
    full = timeslot.ScheduleProblem(p.topo, p.coflow,
                                    n_slots=2 * p.n_slots, rho=p.rho,
                                    path_slack=p.path_slack)
    assert q.n_slots == full.n_slots
    np.testing.assert_array_equal(q.flow_edge_mask, full.flow_edge_mask)
    np.testing.assert_array_equal(q.edge_w_ok, full.edge_w_ok)
    # derived arrays are shared with the source problem, not rebuilt
    assert q.flow_edge_mask is p.flow_edge_mask
    # changing path_slack genuinely rebuilds
    q2 = timeslot.rehorizon(p, 2 * p.n_slots, path_slack=None)
    assert q2.path_slack is None
    assert q2.flow_edge_mask is not p.flow_edge_mask
    # and the solved metrics agree with the from-scratch problem
    ra = solver.solve_fast(q, "energy", iters=300, tol=5e-3)
    rb = solver.solve_fast(full, "energy", iters=300, tol=5e-3)
    assert _metrics_tuple(ra) == _metrics_tuple(rb)
