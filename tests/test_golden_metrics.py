"""Golden-metrics regression gate for the fast-path solver stack.

A tiny pinned grid (2 topologies x 2 objectives x 1 seed) with expected
exact paper-model Metrics committed under tests/golden/metrics.json.
Every cell is solved with solve_fast under BOTH operators (`operator`
fixture: COO as a CPU runs it, dense tiles as a TPU runs it) and
compared to the committed numbers at 1e-4 relative — solver refactors
(LP assembly, PDHG schedule, packing) cannot silently drift the
reproduced paper numbers.  The committed values come from the COO
operator; the tiles are held to the same envelope (they differ only in
float32 summation order, docs/SOLVER.md §7).

Regenerate after an *intentional* numbers change:

    PYTHONPATH=src python tests/test_golden_metrics.py --regen

and include the diff of tests/golden/metrics.json in the PR so the
drift is reviewable.
"""
import functools
import json
import pathlib

import numpy as np
import pytest

from repro import search, service
from repro.core import (arrivals, policies, solver, timeslot, topology,
                        traffic, verify)

GOLDEN_PATH = pathlib.Path(__file__).parent / "golden" / "metrics.json"
RTOL = 1e-4
# policy gaps divide by the per-operator LP solve, whose packed cost
# wiggles ~1e-3 between operators — looser envelope than the metrics
GAP_RTOL = 5e-3

# the pinned grid — small enough to solve tightly in seconds, spanning
# an electronic DCN and the AWGR PON cell plus both objectives
GRID = [(topo, obj)
        for topo in ("spine-leaf", "pon3")
        for obj in ("energy", "time")]
# the pinned policy-gap grid: the heuristic baselines on the same cells
POLICY_GRID = [(topo, obj, pol)
               for topo, obj in GRID
               for pol in ("ecmp", "least-loaded", "scf")]
SEED = 0
PATTERN = dict(n_map=4, n_reduce=3, total_gbits=8.0)

# the pinned two-tenant service run: an electronic-DCN tenant and a PON
# tenant sharing one scheduler (repro.service), seed 0 — service-loop
# refactors cannot silently shift the schedules it emits
SERVICE_KEY = "service/spine-leaf+pon3/seed0"

# the pinned placement-search runs (repro.search): one small SA run per
# GRID cell, seed 0 — search refactors (moves, cooling, seeding, the
# batched evaluator) cannot silently shift the optimized placements or
# their gains.  The budget is deliberately tiny; the committed
# results/placement run uses the real budget.
SEARCH_CFG = dict(method="sa", seed=0, generations=2, population=6,
                  iters=1500)
# the search cell whose pinned optimum ties a baseline to the last ulp
TIED_SEARCH = {("spine-leaf", "time")}


def _problem(topo_name: str) -> timeslot.ScheduleProblem:
    topo = topology.build(topo_name)
    cf = traffic.generate(topo, traffic.pattern("uniform", **PATTERN), SEED)
    return timeslot.ScheduleProblem(
        topo, cf, n_slots=timeslot.suggest_n_slots(topo, cf), path_slack=2)


def _solve(topo_name: str, objective: str) -> dict:
    p = _problem(topo_name)
    r = solver.solve_fast(p, objective)
    # every golden schedule carries a zero-violation feasibility
    # certificate (capacity / conservation / wavelength / demand
    # residuals, core.verify) — not just the evaluate() bit
    verify.check_schedule(p, r.schedule).assert_ok(
        f"{topo_name}/min-{objective}")
    m = r.metrics
    return {"energy_j": float(m.energy_j),
            "completion_s": float(m.completion_s),
            "fairness_term": float(m.fairness_term),
            "served_gbits": float(m.served.sum()),
            "feasible": bool(m.feasible)}


@functools.lru_cache(maxsize=None)
def _lp_for_gap(topo_name: str, objective: str, operator: str):
    """The LP solve a gap divides by, cached per operator (the name is
    the cache key; the operator fixture has set the solver up)."""
    p = _problem(topo_name)
    return p, solver.solve_fast(p, objective)


def _policy_gap(topo_name: str, objective: str, pol_name: str,
                operator: str) -> dict:
    p_lp, lp = _lp_for_gap(topo_name, objective, operator)
    p = _problem(topo_name)
    r = policies.get(pol_name).solve(p, objective)
    r.certificate.assert_ok(f"{pol_name}/{topo_name}/min-{objective}")
    m = r.metrics
    return {"gap_vs_lp": float(policies.gap_vs_lp(objective, p,
                                                  r.schedule, p_lp, lp)),
            "energy_j": float(m.energy_j),
            "completion_s": float(m.completion_s),
            "feasible": bool(m.feasible)}


def _service_run() -> dict:
    spec = arrivals.ArrivalSpec(n_coflows=2, mean_interarrival_s=2.0)
    pat = traffic.pattern("uniform", **PATTERN)
    tenants = [
        service.TenantSpec("dcn", topology.build("spine-leaf"), pat,
                           spec, seed=SEED, objective="energy"),
        service.TenantSpec("pon", topology.build("pon3"), pat,
                           spec, seed=SEED, objective="time"),
    ]
    res = service.run_service(
        tenants, service.ServiceConfig(iters=3000, tol=2e-3,
                                       verify_schedules=True))
    assert res.backlog_gbits == 0.0
    return {"total_energy_j": float(res.total_energy_j),
            "makespan_s": float(res.makespan_s),
            "tenant_energy_j": [float(t.energy_j) for t in res.tenants],
            "tenant_shipped_gbits": [float(t.shipped_gbits)
                                     for t in res.tenants],
            "tenant_makespan_s": [float(t.makespan_s)
                                  for t in res.tenants],
            "n_done": sum(r.status == "done" for r in res.requests),
            "arrived": res.counters.arrived,
            "admitted": res.counters.admitted}


def _search_run(topo_name: str, objective: str) -> dict:
    topo = topology.build(topo_name)
    pat = traffic.pattern("uniform", **PATTERN)
    obj = "time" if objective == "time" else "energy"
    res = search.optimize_placement(topo, pat, obj, **SEARCH_CFG)
    res.best.result.certificate.assert_ok(
        f"search/{topo_name}/min-{objective}")
    return {"best_score": float(res.best.score),
            "gain": float(res.gain),
            "baseline_best": res.baseline_best,
            "baselines": {k: float(c.score)
                          for k, c in res.baselines.items()},
            "best_mappers": res.best.placement.mappers.tolist(),
            "best_reducers": res.best.placement.reducers.tolist(),
            "evaluations": res.evaluations,
            "dispatches": res.dispatches}


def _golden() -> dict:
    with open(GOLDEN_PATH) as fh:
        return json.load(fh)


@pytest.mark.parametrize("topo_name,objective", GRID)
def test_golden_metrics(topo_name, objective, operator):
    want = _golden()[f"{topo_name}/min-{objective}/seed{SEED}"]
    got = _solve(topo_name, objective)
    assert got["feasible"] and want["feasible"]
    for key in ("energy_j", "completion_s", "fairness_term",
                "served_gbits"):
        np.testing.assert_allclose(
            got[key], want[key], rtol=RTOL, atol=1e-9,
            err_msg=f"{topo_name}/min-{objective}[{operator}] {key} drifted "
                    f"from tests/golden/metrics.json (regen only if the "
                    f"change is intentional)")


def test_golden_service_metrics(operator):
    """The two-tenant service pin: schedule quality of the coalescing
    loop (per-tenant energies, shipped volumes, completion times) must
    match the committed numbers under both operators."""
    want = _golden()[SERVICE_KEY]
    got = _service_run()
    # admission accounting is solver-independent: exact equality
    for key in ("n_done", "arrived", "admitted"):
        assert got[key] == want[key], key
    for key in ("total_energy_j", "makespan_s", "tenant_energy_j",
                "tenant_shipped_gbits", "tenant_makespan_s"):
        np.testing.assert_allclose(
            got[key], want[key], rtol=RTOL, atol=1e-9,
            err_msg=f"{SERVICE_KEY}[{operator}] {key} drifted from "
                    f"tests/golden/metrics.json (regen only if the "
                    f"change is intentional)")


@pytest.mark.parametrize("topo_name,objective,pol_name", POLICY_GRID)
def test_golden_policy_gaps(topo_name, objective, pol_name, operator):
    """The pinned optimal-vs-practical grid: each baseline policy's
    certified schedule and its gap over the LP cannot silently drift
    under either operator.  Gaps get the looser GAP_RTOL envelope (the
    LP denominator is operator-dependent); the policy's own metrics are
    pure numpy and held to the solver RTOL."""
    want = _golden()[f"policy/{topo_name}/min-{objective}/{pol_name}/"
                     f"seed{SEED}"]
    got = _policy_gap(topo_name, objective, pol_name, operator)
    assert got["feasible"] and want["feasible"]
    assert got["gap_vs_lp"] >= 1.0 - 1e-4
    np.testing.assert_allclose(
        got["gap_vs_lp"], want["gap_vs_lp"], rtol=GAP_RTOL,
        err_msg=f"policy/{topo_name}/min-{objective}/{pol_name}"
                f"[{operator}] gap drifted (regen only if intentional)")
    for key in ("energy_j", "completion_s"):
        np.testing.assert_allclose(
            got[key], want[key], rtol=RTOL, atol=1e-9,
            err_msg=f"policy/{topo_name}/min-{objective}/{pol_name}"
                    f"[{operator}] {key} drifted")


@pytest.mark.parametrize("topo_name,objective", GRID)
def test_golden_search_runs(topo_name, objective, operator):
    """The pinned SA placement-search runs: optimized placement, score,
    gain, and per-baseline scores must match the committed numbers under
    both operators.  The accept/reject trajectory depends on exact score
    comparisons, so the placement ids are pinned too, but for the one
    cell in TIED_SEARCH: there the optimum ties the `local` baseline,
    and which of the tied placements the search keeps is decided by a
    last-ulp difference of completion time (docs/PLACEMENT.md §6), so
    that cell pins score and gain, not ids."""
    want = _golden()[f"search/{topo_name}/min-{objective}/sa/seed{SEED}"]
    got = _search_run(topo_name, objective)
    tag = f"search/{topo_name}/min-{objective}[{operator}]"
    assert got["baseline_best"] == want["baseline_best"]
    assert got["evaluations"] == want["evaluations"]
    assert got["dispatches"] == want["dispatches"]
    if (topo_name, objective) not in TIED_SEARCH:
        assert got["best_mappers"] == want["best_mappers"], \
            f"{tag} optimized placement drifted (regen only if intentional)"
        assert got["best_reducers"] == want["best_reducers"]
    np.testing.assert_allclose(got["best_score"], want["best_score"],
                               rtol=RTOL)
    np.testing.assert_allclose(got["gain"], want["gain"], rtol=RTOL)
    assert got["gain"] >= 1.0 - 1e-12
    for k in search.BASELINES:
        np.testing.assert_allclose(
            got["baselines"][k], want["baselines"][k], rtol=RTOL,
            err_msg=f"{tag} baseline {k} drifted")


def _regen() -> None:
    doc = {f"{t}/min-{o}/seed{SEED}": _solve(t, o) for t, o in GRID}
    doc.update({f"policy/{t}/min-{o}/{pol}/seed{SEED}":
                _policy_gap(t, o, pol, "coo")
                for t, o, pol in POLICY_GRID})
    doc.update({f"search/{t}/min-{o}/sa/seed{SEED}": _search_run(t, o)
                for t, o in GRID})
    doc[SERVICE_KEY] = _service_run()
    GOLDEN_PATH.parent.mkdir(parents=True, exist_ok=True)
    GOLDEN_PATH.write_text(json.dumps(doc, indent=1, sort_keys=True) + "\n")
    print(f"wrote {GOLDEN_PATH}")
    for k, v in doc.items():
        if k == SERVICE_KEY:
            print(f"  {k}: E={v['total_energy_j']:.4f} J "
                  f"M={v['makespan_s']:.6f} s done={v['n_done']}")
        elif k.startswith("search/"):
            print(f"  {k}: best={v['best_score']:.6f} "
                  f"gain={v['gain']:.4f} vs {v['baseline_best']}")
        else:
            print(f"  {k}: E={v['energy_j']:.4f} J  "
                  f"M={v['completion_s']:.6f} s")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--regen", action="store_true",
                    help="rewrite tests/golden/metrics.json from the "
                         "current solver (COO operator, as on a CPU)")
    if ap.parse_args().regen:
        _regen()
    else:
        ap.error("pass --regen to rewrite the golden fixture")
