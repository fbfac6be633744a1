"""Batched PDHG solve: the instance-axis stack must reproduce per-instance
solves element-wise (block-diagonal PDHG decouples exactly), and the
sweep runner must emit exact paper-model metrics."""
import numpy as np
import pytest

from repro.core import solver, timeslot, topology, traffic


def make_problems(topo_name="spine-leaf", n=4, pattern="uniform", slack=None):
    topo = topology.build(topo_name)
    pat = traffic.pattern(pattern, n_map=4, n_reduce=3, total_gbits=8.0)
    probs = []
    for cf in traffic.generate_batch(topo, pat, range(n)):
        T = timeslot.suggest_n_slots(topo, cf)
        probs.append(timeslot.ScheduleProblem(topo, cf, n_slots=T,
                                              path_slack=slack))
    return probs


@pytest.mark.parametrize("objective", ["time", "energy"])
def test_batch_matches_per_instance(objective):
    """With the host restart ladder (adaptive=False) the batch reproduces
    per-instance solve_fast schedules element-wise."""
    probs = make_problems(n=4)
    batch = solver.solve_fast_batch(probs, objective, iters=2000,
                                    adaptive=False)
    for p, b in zip(probs, batch):
        s = solver.solve_fast(p, objective, iters=2000)
        np.testing.assert_allclose(b.schedule, s.schedule, atol=1e-5)
        assert b.metrics.energy_j == pytest.approx(s.metrics.energy_j, rel=1e-6)
        assert b.metrics.completion_s == pytest.approx(
            s.metrics.completion_s, rel=1e-6)
        assert b.metrics.feasible
        assert b.remaining_gbits < 1e-6


def test_batch_lp_matches_solve_lp():
    """Block-diagonal stacking reproduces each instance's own PDHG iterate."""
    probs = make_problems(n=3, pattern="skew")
    lps = [solver.build_routing_lp(p, "time")[0] for p in probs]
    batch = solver.solve_lp_batch(lps, iters=1500, max_restarts=0,
                                  adaptive=False)
    for lp, b in zip(lps, batch):
        single = solver.solve_lp(lp, iters=1500, max_restarts=0)
        np.testing.assert_allclose(b.x, single.x, atol=1e-6)
        assert b.primal_residual == pytest.approx(single.primal_residual,
                                                  rel=1e-3, abs=1e-9)


@pytest.mark.parametrize("objective", ["time", "energy"])
def test_adaptive_batch_converges_and_schedules_well(objective):
    """The fused adaptive solve (default) must hit the same tolerances and
    produce feasible, fully-shipped schedules whose exact metrics agree
    with the per-instance path."""
    probs = make_problems(n=4)
    batch = solver.solve_fast_batch(probs, objective, iters=2000, tol=2e-3)
    for p, b in zip(probs, batch):
        s = solver.solve_fast(p, objective, iters=2000, tol=2e-3)
        assert b.metrics.feasible
        assert b.remaining_gbits < 1e-6
        assert b.lp_primal_residual <= 2e-3
        # both converged to tolerance: exact metrics agree closely
        assert b.metrics.completion_s == pytest.approx(
            s.metrics.completion_s, rel=0.1)
        assert b.metrics.energy_j == pytest.approx(s.metrics.energy_j,
                                                   rel=0.1)


def test_batch_mixed_shapes():
    """Instances whose LPs differ in size (placement changes the admissible
    triple set) still stack and solve."""
    probs = make_problems("pon3", n=3, pattern="packed")
    sizes = {solver.build_routing_lp(p, "energy")[0].n for p in probs}
    results = solver.solve_fast_batch(probs, "energy", iters=2000)
    assert len(results) == 3
    for r in results:
        assert r.metrics.feasible
        assert r.remaining_gbits < 1e-6


def test_batch_requires_shared_topology():
    a = make_problems("spine-leaf", n=1)
    b = make_problems("bcube", n=1)
    with pytest.raises(ValueError):
        solver.solve_fast_batch(a + b, "energy")


def test_path_slack_keeps_feasibility():
    """Near-shortest route pruning must not break the fast path."""
    for name in ("fat-tree", "pon3", "pon5"):
        (p,) = make_problems(name, n=1, slack=2)
        full, = make_problems(name, n=1, slack=None)
        assert p.flow_edge_mask.sum() <= full.flow_edge_mask.sum()
        r = solver.solve_fast(p, "time", iters=2500)
        assert r.metrics.feasible, name
        assert r.remaining_gbits < 1e-6, name


def test_sweep_runner_records_exact_metrics():
    from repro.sweep import SweepSpec, run_sweep, write_csv, write_markdown
    spec = SweepSpec(topos=("spine-leaf",), objectives=("energy",),
                     patterns=("uniform",), seeds=(0, 1), total_gbits=8.0,
                     n_map=4, n_reduce=3, iters=1200, oracle_check=0)
    records, problems = run_sweep(spec)
    assert len(records) == 2
    # recorded numbers must be the exact core.timeslot.evaluate outputs of
    # the batched solve (deterministic, so re-solving reproduces them)
    again = solver.solve_fast_batch(problems, "energy", iters=spec.iters,
                                    tol=spec.tol)
    for rec, r in zip(records, again):
        assert rec.feasible
        assert rec.energy_j == pytest.approx(r.metrics.energy_j, rel=1e-9)
        assert rec.completion_s == pytest.approx(r.metrics.completion_s,
                                                 rel=1e-9)
    csv_p = write_csv(records, "/tmp/test_sweep/results.csv")
    md_p = write_markdown(records, "/tmp/test_sweep/results.md")
    assert csv_p.exists() and "spine-leaf" in md_p.read_text()
