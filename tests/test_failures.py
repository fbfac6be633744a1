"""Failure engine: schema preservation, determinism, warm-started
re-solves, and the sweep's --failures axis."""
import numpy as np
import pytest

from repro.core import failures, solver, timeslot, topology, traffic

PRESETS = ["link1", "link3", "switch", "device", "degrade50", "brownout"]


def small_problem(name="spine-leaf", seed=2, total=8.0):
    t = topology.build(name)
    cf = traffic.shuffle_traffic(t, total, n_map=4, n_reduce=3, seed=seed)
    return timeslot.ScheduleProblem(
        t, cf, n_slots=timeslot.suggest_n_slots(t, cf), path_slack=2)


# ---------------------------------------------------------------------------
# degraded topologies stay schema-valid
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("preset", PRESETS)
@pytest.mark.parametrize("name", ["fat-tree", "spine-leaf", "bcube", "dcell",
                                  "pon3", "pon5"])
def test_degraded_schema_valid(name, preset):
    topo = topology.build(name)
    d = failures.apply(topo, failures.sample(topo, preset, 0))
    # same devices/edges/wavelengths — only capacities may shrink
    assert d.devices is topo.devices or d.devices == topo.devices
    np.testing.assert_array_equal(d.edges, topo.edges)
    assert d.cap.shape == topo.cap.shape
    assert (d.cap >= 0.0).all()
    assert (d.cap <= topo.cap + 1e-12).all()
    ratio = failures.degradation_ratio(topo, d)
    assert 0.0 <= ratio <= 1.0
    if preset != "none":
        assert ratio > 0.0, preset
    if name != "pon3":   # pon3's AWGR paths are intentionally one-way
        d.validate()


def test_device_outage_zeroes_incident_edges():
    topo = topology.build("spine-leaf")
    scen = failures.fail_device(topo, "spine0")
    dev = next(i for i, dd in enumerate(topo.devices) if dd.name == "spine0")
    d = failures.apply(topo, scen)
    incident = (topo.edges[:, 0] == dev) | (topo.edges[:, 1] == dev)
    assert (d.cap[incident] == 0.0).all()
    np.testing.assert_array_equal(d.cap[~incident], topo.cap[~incident])


def test_link_cut_closed_under_reversal():
    topo = topology.build("bcube")
    scen = failures.sample(topo, "link1", 7)
    dead = set(scen.cut_edges)
    for e in list(dead):
        u, v = topo.edges[e]
        rev = np.flatnonzero((topo.edges[:, 0] == v)
                             & (topo.edges[:, 1] == u))
        assert set(rev.tolist()) <= dead, "reverse direction survived"


# ---------------------------------------------------------------------------
# seeded ensembles are deterministic
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("preset", PRESETS)
def test_ensemble_deterministic(preset):
    topo = topology.build("bcube")
    a = failures.ensemble(topo, preset, range(4))
    b = failures.ensemble(topo, preset, range(4))
    assert a == b
    caps = [failures.apply(topo, s).cap for s in a]
    for s, cap in zip(b, caps):
        np.testing.assert_array_equal(failures.apply(topo, s).cap, cap)


def test_sample_varies_with_seed():
    topo = topology.build("fat-tree")
    scens = {failures.sample(topo, "link1", s).cut_edges for s in range(16)}
    assert len(scens) > 1, "all seeds drew the same link"


# ---------------------------------------------------------------------------
# degraded problems + warm-started re-solves
# ---------------------------------------------------------------------------

def test_degrade_problem_zeroes_unroutable_flows():
    p = small_problem("spine-leaf")
    # cut one server's only access link: its flows become unroutable
    srv = int(p.coflow.src[0])
    e = int(np.flatnonzero(p.topo.edges[:, 0] == srv)[0])
    dst = int(p.topo.edges[e, 1])
    groups = failures.link_groups(p.topo)
    gid = next(i for i, g in enumerate(groups)
               if set(np.unique(p.topo.edges[list(g)])) == {srv, dst})
    dp = failures.degrade_problem(p, failures.cut_links(p.topo, [gid]))
    touched = (p.coflow.src == srv) | (p.coflow.dst == srv)
    assert (dp.coflow.size[touched] == 0.0).all()
    assert np.array_equal(dp.coflow.size[~touched], p.coflow.size[~touched])
    # the degraded instance still solves and stays exactly feasible
    r = solver.solve_fast(dp, "energy", iters=2000)
    assert r.metrics.feasible
    assert r.metrics.served.sum() < p.coflow.total_gbits


@pytest.mark.parametrize("objective", ["energy", "time"])
def test_warm_resolve_matches_cold(objective):
    """Warm-started incremental re-solve lands on a schedule equivalent to
    a cold solve of the same degraded instance (both exactly feasible,
    same delivered Gbits, primary metric within a small LP-multiplicity
    band)."""
    p = small_problem("spine-leaf")
    healthy = solver.solve_fast(p, objective, iters=2000)
    dp = failures.degrade_problem(p, failures.sample(p.topo, "link1", 0))
    cold = solver.solve_fast(dp, objective, iters=2000)
    warm = solver.resolve_incremental(dp, objective, healthy, iters=2000)
    assert cold.metrics.feasible and warm.metrics.feasible
    assert warm.metrics.served.sum() == pytest.approx(
        cold.metrics.served.sum(), rel=1e-6)
    key = "energy_j" if objective == "energy" else "completion_s"
    assert getattr(warm.metrics, key) == pytest.approx(
        getattr(cold.metrics, key), rel=0.05)


def test_ensemble_warm_equals_cold_metrics():
    p = small_problem("bcube")
    healthy = solver.solve_fast(p, "energy", iters=2000)
    dprobs = [failures.degrade_problem(p, failures.sample(p.topo, "link1", s))
              for s in range(3)]
    cold = solver.solve_fast_ensemble(dprobs, "energy", iters=2000)
    warm = solver.solve_fast_ensemble(dprobs, "energy", warm=[healthy] * 3,
                                      iters=2000)
    for c, w in zip(cold, warm):
        assert c.metrics.feasible and w.metrics.feasible
        assert w.metrics.served.sum() == pytest.approx(
            c.metrics.served.sum(), rel=1e-6)
        assert w.metrics.energy_j == pytest.approx(c.metrics.energy_j,
                                                   rel=0.05)


def test_noop_scenario_projection_is_lossless():
    """Projecting onto an identical (undegraded) instance must preserve the
    decomposed routing volumes and duals exactly."""
    p = small_problem("spine-leaf")
    healthy = solver.solve_fast(p, "energy", iters=2000)
    lp, idx = solver.build_routing_lp(p, "energy")
    x0, y0 = solver.project_warm_start(healthy, p, lp, idx)
    np.testing.assert_allclose(y0, healthy.lp_y, atol=1e-12)
    served = sum(pp.volume for pp in healthy.paths)
    assert x0[:len(idx.kf)].sum() > 0
    # injection totals match the decomposed volumes per flow
    F, W = p.coflow.n_flows, p.topo.n_wavelengths
    inj = x0[len(idx.kf):len(idx.kf) + F * W].reshape(F, W).sum(axis=1)
    per_flow = np.zeros(F)
    for pp in healthy.paths:
        per_flow[pp.flow] += pp.volume
    np.testing.assert_allclose(inj, np.minimum(per_flow, p.coflow.size),
                               atol=1e-9)
    assert served == pytest.approx(inj.sum(), abs=1e-9)


# ---------------------------------------------------------------------------
# sweep integration
# ---------------------------------------------------------------------------

def test_sweep_failures_axis(tmp_path):
    from repro.sweep import SweepSpec, run_sweep, write_csv, write_markdown
    spec = SweepSpec(topos=("spine-leaf",), objectives=("energy",),
                     patterns=("uniform",), seeds=(0, 1),
                     failures=("link1",), total_gbits=6.0, n_map=4,
                     n_reduce=3, iters=1200, oracle_check=0)
    records, problems = run_sweep(spec)
    assert len(records) == len(problems) == 4          # 2 healthy + 2 degraded
    degraded = [r for r in records if r.failure == "link1"]
    assert len(degraded) == 2
    for r in degraded:
        assert r.feasible
        assert 0.0 < r.degradation_ratio < 1.0
        assert 0.0 < r.survivability <= 1.0 + 1e-9
    csv_path = write_csv(records, tmp_path / "r.csv")
    md = write_markdown(records, tmp_path / "r.md").read_text()
    assert "failure" in csv_path.read_text().splitlines()[0]
    assert "Degraded fabrics" in md


@pytest.mark.parametrize("bad", ["meteor", "none"])
def test_sweep_rejects_unknown_failure(bad):
    """Unknown presets and the no-op "none" (whose records would be
    misfiled as healthy rows) are both rejected up front."""
    from repro.sweep import SweepSpec
    spec = SweepSpec(topos=("spine-leaf",), failures=(bad,))
    with pytest.raises(ValueError, match="failure preset"):
        spec.validate()


# ---------------------------------------------------------------------------
# repair: exact inverse of apply (the chaos engine's core invariant)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("preset", PRESETS)
def test_repair_round_trip_bit_identical(preset):
    topo = topology.build("spine-leaf")
    scen = failures.sample(topo, preset, seed=3)
    degraded = failures.apply(topo, scen)
    assert degraded.cap.sum() < topo.cap.sum()
    restored = failures.repair(degraded, scen, topo)
    # bit-identical, not approximately equal: same capacity bytes, so
    # the repaired fabric hits the same solver structure-cache entry
    assert restored.cap.tobytes() == topo.cap.tobytes()
    assert restored.name == topo.name
    cf = traffic.shuffle_traffic(topo, 8.0, n_map=4, n_reduce=3, seed=2)
    n = timeslot.suggest_n_slots(topo, cf)
    p_h = timeslot.ScheduleProblem(topo, cf, n_slots=n, path_slack=2)
    p_r = timeslot.ScheduleProblem(restored, cf, n_slots=n, path_slack=2)
    assert solver._structure_key(p_h, "energy") \
        == solver._structure_key(p_r, "energy")


def test_repair_rejects_wrong_degraded_state():
    topo = topology.build("spine-leaf")
    scen = failures.sample(topo, "link1", seed=3)
    other = failures.apply(topo, failures.sample(topo, "switch", seed=5))
    with pytest.raises(ValueError, match="not apply"):
        failures.repair(other, scen, topo)


def test_affected_rows_is_the_support_of_apply():
    topo = topology.build("spine-leaf")
    for preset in PRESETS:
        scen = failures.sample(topo, preset, seed=1)
        rows = failures.affected_rows(topo, scen)
        changed = np.any(failures.apply(topo, scen).cap != topo.cap,
                         axis=tuple(range(1, topo.cap.ndim)))
        # every changed row is inside the declared support
        assert not np.any(changed & ~rows), preset


def test_compose_matches_sequential_application_pattern():
    """Applying the composition of two cut scenarios zeroes exactly the
    union of their supports (the replay invariant FabricState relies
    on: active-set composition over the pristine topology)."""
    topo = topology.build("spine-leaf")
    a = failures.sample(topo, "link1", seed=0)
    b = failures.sample(topo, "switch", seed=1)
    both = failures.apply(topo, failures.compose([a, b]))
    rows = failures.affected_rows(topo, a) | failures.affected_rows(topo, b)
    assert np.all(both.cap[rows] == 0.0)
    assert np.array_equal(both.cap[~rows], topo.cap[~rows])


# ---------------------------------------------------------------------------
# the failure path's spans and counters, and stranded flows
# ---------------------------------------------------------------------------

def _fat_tree_problem(k=4, seed=0, n_map=4, n_reduce=2, total=8.0):
    t = topology.build("fat-tree", k=k)
    cf = traffic.shuffle_traffic(t, total, n_map=n_map, n_reduce=n_reduce,
                                 seed=seed)
    return timeslot.ScheduleProblem(t, cf, n_slots=timeslot.suggest_n_slots(
        t, cf), path_slack=0)


def _agg_failures(p):
    """One scenario per aggregation switch: none strands a flow."""
    return [failures.fail_device(p.topo, i)
            for i, d in enumerate(p.topo.devices) if d.name.startswith("agg")]


def test_failure_spans_once_per_member():
    from repro import trace

    p = _fat_tree_problem()
    healthy = solver.solve_fast_batch([p], "energy", iters=4000)[0]
    scens = _agg_failures(p)[:3]
    with trace.recording() as rec:
        dprobs = [failures.degrade_problem(p, s, n_slots=p.n_slots)
                  for s in scens]
        solver.solve_fast_ensemble(dprobs, "energy", warm=[healthy] * 3,
                                   iters=4000)
    s = rec.seconds()
    assert len(s["failure.degrade"]) == len(s["warm.project"]) == 3
    assert all(v >= 0.0 for v in s["failure.degrade"] + s["warm.project"])


def test_ensemble_stats_count_members_and_iterations():
    p = _fat_tree_problem()
    healthy = solver.solve_fast_batch([p], "energy", iters=4000)[0]
    dprobs = [failures.degrade_problem(p, s, n_slots=p.n_slots)
              for s in _agg_failures(p)[:3]]
    solver.reset_ensemble_stats()
    warm = solver.solve_fast_ensemble(dprobs, "energy", warm=[healthy] * 3,
                                      iters=4000)
    cold = solver.solve_fast_ensemble(dprobs[:2], "energy", iters=4000)
    st = solver.ensemble_stats().snapshot()
    assert (st.members, st.warm_members) == (5, 3)
    assert st.warm_iters == sum(r.iterations for r in warm) > 0
    assert sum(r.iterations for r in cold) > 0
    # the projection places every Gbit of the healthy paths: kept on a
    # surviving path or re-routed, never more than the demand
    shipped = st.kept_gbits + st.rerouted_gbits
    assert 0.0 < shipped <= 3 * p.coflow.total_gbits + 1e-9
    solver.reset_ensemble_stats()
    assert solver.ensemble_stats() == solver.EnsembleStats()


def test_warm_members_run_fewer_iterations_than_cold():
    """Fat-tree k=4: re-plans after each aggregation switch's failure,
    warm-started from the healthy plan, converge in fewer PDHG
    iterations than the same members started from zero."""
    p = _fat_tree_problem()
    healthy = solver.solve_fast_batch([p], "energy", iters=16000,
                                      tol=2e-3)[0]
    dprobs = [failures.degrade_problem(p, s, n_slots=p.n_slots)
              for s in _agg_failures(p)]
    warm = solver.solve_fast_ensemble(dprobs, "energy", warm=[healthy] *
                                      len(dprobs), iters=16000, tol=2e-3)
    cold = solver.solve_fast_ensemble(dprobs, "energy", iters=16000,
                                      tol=2e-3)
    for w, c in zip(warm, cold):
        assert w.metrics.feasible and c.metrics.feasible
    assert sum(r.iterations for r in warm) < sum(r.iterations for r in cold)


def _old_mask(p):
    """The flow-edge mask as it was before stranded flows admitted no
    edge (`shortest + path_slack` compared with an infinite `shortest`)."""
    src, dst = p.coflow.src, p.coflow.dst
    mask = ~(p.e_dst[None, :] == src[:, None])
    mask &= ~(p.e_src[None, :] == dst[:, None])
    hs = timeslot.hop_rows(p.topo, src)
    hd = timeslot.hop_rows(p.topo, dst, to=True)
    through = hs[:, p.e_src] + 1 + hd[:, p.e_dst]
    shortest = hs[np.arange(p.coflow.n_flows), dst]
    return mask & (through <= (shortest + p.path_slack)[:, None])


@pytest.mark.parametrize("k", [8, 16])
def test_stranded_flows_admit_no_edge(k):
    """One edge switch hosting tasks fails: its flows are stranded and
    admit no edge, so the degraded LP is no larger than the healthy one,
    and every routable flow keeps its mask row."""
    p = _fat_tree_problem(k=k, seed=1, n_map=10, n_reduce=6, total=30.0)
    src = int(p.coflow.src[0])
    edge = int(p.topo.edges[p.topo.edges[:, 0] == src, 1][0])
    dp = failures.degrade_problem(p, failures.fail_device(p.topo, edge),
                                  n_slots=p.n_slots)
    stranded = dp.coflow.size == 0.0
    assert stranded.any() and not stranded.all()
    assert not dp.flow_edge_mask[stranded].any()
    old = _old_mask(dp)
    # the defect: nearly every edge, inf <= inf
    assert (old[stranded].sum(axis=1) > 0.9 * p.topo.n_edges).all()
    np.testing.assert_array_equal(dp.flow_edge_mask[~stranded],
                                  old[~stranded])
    lp_h, _ = solver.build_routing_lp(p, "energy")
    lp_d, _ = solver.build_routing_lp(dp, "energy")
    assert lp_d.n <= lp_h.n


def test_stranded_flows_keep_the_solve_finite():
    """A stranded flow's rows hold only its injections, fixed at 0: the
    solve stays finite, its demand row's dual stays 0, and every
    routable flow is served in full."""
    p = _fat_tree_problem(k=4, seed=1)
    src = int(p.coflow.src[0])
    edge = int(p.topo.edges[p.topo.edges[:, 0] == src, 1][0])
    dp = failures.degrade_problem(p, failures.fail_device(p.topo, edge),
                                  n_slots=p.n_slots)
    stranded = dp.coflow.size == 0.0
    assert stranded.any()
    lp, idx = solver.build_routing_lp(dp, "energy")
    res = solver.solve_lp_batch([lp], iters=4000)[0]
    assert np.isfinite(res.x).all() and np.isfinite(res.y).all()
    assert np.isfinite(res.primal_residual)
    assert np.isfinite(res.duality_gap_rel)
    demand_row = {k: i for i, k in enumerate(idx.eq_keys)}
    for f in np.flatnonzero(stranded):
        assert res.y[demand_row[("d", int(f))]] == 0.0
    r = solver.solve_fast_batch([dp], "energy", iters=4000)[0]
    assert r.metrics.feasible
    np.testing.assert_allclose(r.metrics.served[~stranded],
                               dp.coflow.size[~stranded], atol=1e-6)
