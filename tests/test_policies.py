"""The policy zoo (core.policies) against the LP fast path.

Three layers of assurance:

  * every policy x all six topologies x both objectives produces a
    schedule that passes the shared feasibility verifier
    (core.verify.check_schedule — eqs. 19-22/39 residuals), drains the
    demand, and never beats the LP under the shared objective
    functional (gap_vs_lp >= 1.0 within tolerance);
  * determinism: policies are pure functions of the instance — two
    independently built copies of the same seeded problem produce
    byte-identical schedules;
  * a 4-server micro-instance small enough to brute-force: ECMP's
    hash choice is pinned against an independent reference and
    least-loaded's routing must achieve the exhaustive min-max
    bottleneck utilization over all candidate-path combinations.

The sweep integration test runs the real `--policy` axis end to end
(records, gap columns, report gap table).
"""
import functools
import itertools
import zlib

import numpy as np
import pytest

from repro.core import policies, solver, timeslot, topology, traffic, verify
from repro.core.traffic import CoflowSet
from repro.sweep.report import write_markdown
from repro.sweep.runner import SweepSpec, run_sweep

TOPOS = tuple(topology.BUILDERS)
OBJECTIVES = ("energy", "time")
GAP_TOL = 1e-4
PATTERN = dict(n_map=4, n_reduce=3, total_gbits=8.0)


def _build_problem(topo_name: str, seed: int = 0) -> timeslot.ScheduleProblem:
    topo = topology.build(topo_name)
    cf = traffic.generate(topo, traffic.pattern("uniform", **PATTERN), seed)
    return timeslot.ScheduleProblem(
        topo, cf, n_slots=timeslot.suggest_n_slots(topo, cf), path_slack=2)


@functools.lru_cache(maxsize=None)
def _problem(topo_name: str) -> timeslot.ScheduleProblem:
    return _build_problem(topo_name)


@functools.lru_cache(maxsize=None)
def _lp(topo_name: str, objective: str) -> solver.FastPathResult:
    return solver.solve_fast(_problem(topo_name), objective, iters=3000)


# ---------------------------------------------------------------------------
# The full grid: feasible, certified, never better than the LP
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("objective", OBJECTIVES)
@pytest.mark.parametrize("topo_name", TOPOS)
@pytest.mark.parametrize("pol_name", sorted(policies.POLICIES))
def test_policy_grid(pol_name, topo_name, objective):
    p = _problem(topo_name)
    r = policies.get(pol_name).solve(p, objective, iters=1500)
    assert r.certificate is not None
    r.certificate.assert_ok(f"{pol_name}/{topo_name}/min-{objective}")
    assert r.metrics.feasible
    assert r.remaining_gbits <= 1e-6, (pol_name, r.remaining_gbits)
    # the certificate and the paper-model evaluator measure the same
    # residuals — they must agree, not merely both pass
    np.testing.assert_allclose(r.certificate.max_residual,
                               r.metrics.max_violation, atol=1e-7)
    gap = policies.gap_vs_lp(objective, p, r.schedule,
                             p, _lp(topo_name, objective))
    assert gap >= 1.0 - GAP_TOL, (pol_name, topo_name, objective, gap)


@pytest.mark.parametrize("topo_name", TOPOS)
def test_lp_row_certificate(topo_name):
    """The sweep's own LP row certifies feasible on every topology —
    the verifier anchors the LP side of every gap the report prints."""
    p = _problem(topo_name)
    r = _lp(topo_name, "energy")
    cert = verify.check_schedule(p, r.schedule).assert_ok(topo_name)
    assert cert.max_residual <= cert.tol


# ---------------------------------------------------------------------------
# Determinism
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pol_name",
                         ["ecmp", "least-loaded", "scf", "fair"])
def test_policy_deterministic(pol_name):
    """Two independently built copies of the same seeded instance ->
    byte-identical schedules (no hidden RNG, no dict-order leaks)."""
    a = policies.get(pol_name).solve(_build_problem("spine-leaf", 3),
                                     "energy")
    b = policies.get(pol_name).solve(_build_problem("spine-leaf", 3),
                                     "energy")
    np.testing.assert_array_equal(a.schedule, b.schedule)


# ---------------------------------------------------------------------------
# Brute-force micro-reference (4 servers)
# ---------------------------------------------------------------------------

def _micro_problem() -> timeslot.ScheduleProblem:
    topo = topology.build("spine-leaf")
    s = np.asarray(topo.task_servers)[:4]
    cf = CoflowSet(np.array([s[0], s[0], s[1]]),
                   np.array([s[2], s[3], s[3]]),
                   np.array([4.0, 2.0, 3.0]), topo.n_vertices)
    return timeslot.ScheduleProblem(
        topo, cf, n_slots=timeslot.suggest_n_slots(topo, cf), path_slack=2)


def test_ecmp_micro_reference():
    """ECMP's pick is exactly the crc32 rank into the candidate list,
    and the picked path independently walks src -> dst."""
    p = _micro_problem()
    idx, sets = policies.path_sets(p)
    _, chosen = policies.get("ecmp").route(p, "energy")
    assert len(chosen) == p.coflow.n_flows
    for fp in chosen:
        f = fp.flow
        cand = sets[f]
        key = (f"{f}:{int(p.coflow.src[f])}:"
               f"{int(p.coflow.dst[f])}").encode()
        want = cand[zlib.crc32(key) % len(cand)]
        np.testing.assert_array_equal(fp.triples, want.triples)
        # walk the edge chain: contiguous src -> dst
        es = idx.ke[fp.triples]
        assert int(p.e_src[es[0]]) == int(p.coflow.src[f])
        assert int(p.e_dst[es[-1]]) == int(p.coflow.dst[f])
        np.testing.assert_array_equal(p.e_dst[es[:-1]], p.e_src[es[1:]])


def test_least_loaded_micro_bruteforce():
    """On the 4-server micro-instance the greedy routing achieves the
    exhaustive min-max bottleneck utilization over every combination of
    candidate paths."""
    p = _micro_problem()
    idx, sets = policies.path_sets(p)
    ke, kw = idx.ke, idx.kw
    cap = p.topo.cap

    def max_util(choice) -> float:
        load = np.zeros_like(cap)
        for fp in choice:
            np.add.at(load, (ke[fp.triples], kw[fp.triples]),
                      float(p.coflow.size[fp.flow]))
        pos = cap > 0.0
        return float((load[pos] / cap[pos]).max())

    best = min(max_util(c) for c in itertools.product(*sets))
    _, chosen = policies.get("least-loaded").route(p, "energy")
    assert len(chosen) == p.coflow.n_flows
    np.testing.assert_allclose(max_util(chosen), best, rtol=1e-9)


# ---------------------------------------------------------------------------
# Sweep integration: the --policy axis end to end
# ---------------------------------------------------------------------------

def test_sweep_policy_axis(tmp_path):
    spec = SweepSpec(topos=("spine-leaf",), objectives=("energy",),
                     patterns=("uniform",), seeds=(0, 1), iters=1200,
                     total_gbits=8.0, n_map=4, n_reduce=3,
                     oracle_check=0, policies=("ecmp", "scf"))
    records, problems = run_sweep(spec)
    assert len(records) == len(problems)
    pol_rows = [r for r in records if r.policy != "lp"]
    assert {r.policy for r in pol_rows} == {"ecmp", "scf"}
    assert len(pol_rows) == 4          # 2 policies x 2 seeds
    for r in pol_rows:
        assert r.feasible, (r.policy, r.max_violation)
        assert r.gap_vs_lp >= 1.0 - GAP_TOL, (r.policy, r.gap_vs_lp)
        assert r.remaining_gbits <= 1e-6
    assert all(r.gap_vs_lp == 1.0 for r in records if r.policy == "lp")
    md = write_markdown(records, tmp_path / "results.md").read_text()
    assert "Optimal-vs-practical gap" in md
    assert "| spine-leaf | ecmp | none |" in md


def test_sweep_gap_reference_tightened():
    """spine-leaf / min-completion / packed at the default iteration
    budget is the known hard cell: the batched LP stops ~3.7% above the
    optimum and its unconverged lp_lower_bound sits ABOVE it, so
    least-loaded (which finds the true optimum here) would record a
    meaningless 0.96 "win".  The runner must re-solve the reference at
    a higher budget and record the certified tie instead."""
    spec = SweepSpec(topos=("spine-leaf",), objectives=("completion",),
                     patterns=("packed",), seeds=(0,),
                     oracle_check=0, policies=("least-loaded",))
    records, _ = run_sweep(spec)
    (row,) = [r for r in records if r.policy == "least-loaded"]
    assert row.gap_vs_lp >= 1.0 - GAP_TOL, row.gap_vs_lp


def test_unknown_policy_rejected():
    with pytest.raises(KeyError):
        policies.get("valiant")
    with pytest.raises(ValueError):
        SweepSpec(policies=("valiant",)).validate()
