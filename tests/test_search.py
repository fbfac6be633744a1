"""Tests for the placement-search loop (repro.search).

Covers: move-set validity, per-seed determinism, the certified
incumbent-never-worse invariant, optimizer-vs-exhaustive agreement on a
brute-forceable 4-server micro-topology, and config/argument errors.
The pinned golden search runs live in test_golden_metrics.py.
"""
import dataclasses
import itertools
import math

import numpy as np
import pytest

from repro import search
from repro.core import timeslot, topology, traffic

# a tiny AWGR PON cell: 2 racks x 2 servers (+ OLT) solves in well
# under a second per dispatch, and its asymmetric rack costs make
# placement matter (unlike a symmetric fabric at full occupancy)
TINY_PON = dict(n_racks=2, servers_per_rack=2,
                lam=topology.awgr_lambda(3))
TINY_PAT = dict(n_map=2, n_reduce=1, total_gbits=4.0)
FAST = dict(generations=2, population=4, iters=800)


def _tiny_topo():
    return topology.build("pon3", **TINY_PON)


# ---------------------------------------------------------------- moves


@pytest.mark.parametrize("topo_name,kw", [
    ("bcube", dict(n=2)),
    ("pon3", TINY_PON),
    ("spine-leaf", dict(n_servers=4, n_leaf=2, n_spine=1)),
])
def test_moves_preserve_validity(topo_name, kw):
    """Chained random proposals always yield valid placements with the
    original mapper/reducer counts."""
    topo = topology.build(topo_name, **kw)
    pat = traffic.pattern("uniform", **TINY_PAT)
    rng = np.random.default_rng(7)
    pl = traffic.sample_placement(topo, pat, rng)
    for _ in range(60):
        pl = search.propose(pl, topo, rng)
        pl.validate(topo)
        assert pl.n_map == TINY_PAT["n_map"]
        assert pl.n_reduce == TINY_PAT["n_reduce"]


def test_each_move_kind_preserves_validity():
    topo = _tiny_topo()
    pat = traffic.pattern("uniform", **TINY_PAT)
    rng = np.random.default_rng(3)
    pl = traffic.sample_placement(topo, pat, rng)
    for kind in search.MOVES:
        moved = getattr(search, kind)(pl, topo, rng)
        moved.validate(topo)


# ----------------------------------------------------- search invariants


@pytest.mark.parametrize("method", search.METHODS)
def test_deterministic_per_seed(method):
    """Same (seed, method) twice -> bit-identical incumbent placement,
    score, and history."""
    topo = _tiny_topo()
    pat = traffic.pattern("uniform", **TINY_PAT)
    a = search.optimize_placement(topo, pat, "energy", method=method,
                                  seed=11, **FAST)
    b = search.optimize_placement(topo, pat, "energy", method=method,
                                  seed=11, **FAST)
    assert a.best.placement.key() == b.best.placement.key()
    assert a.best.score == b.best.score
    assert a.history == b.history
    assert a.gain == b.gain


@pytest.mark.parametrize("method", search.METHODS)
@pytest.mark.parametrize("objective", ["energy", "time"])
def test_incumbent_never_worse_and_certified(method, objective):
    """The returned incumbent is certified feasible and never worse than
    the best fixed baseline (gain >= 1); history is monotone."""
    topo = _tiny_topo()
    pat = traffic.pattern("uniform", **TINY_PAT)
    res = search.optimize_placement(topo, pat, objective, method=method,
                                    seed=0, **FAST)
    assert math.isfinite(res.best.score)
    assert res.best.result.certificate is not None
    res.best.result.certificate.assert_ok("search incumbent")
    base = min(c.score for c in res.baselines.values())
    assert res.best.score <= base + 1e-9
    assert res.gain >= 1.0 - 1e-12
    assert res.improved == (res.gain > 1.0)
    assert res.history == sorted(res.history, reverse=True)
    assert res.baseline_best in search.BASELINES
    assert res.evaluations > 0 and res.dispatches >= 1


def test_optimizer_matches_exhaustive_on_micro_topology():
    """bcube(n=2) with one mapper and one reducer has only 4*3 = 12
    placements: the optimizer must find the exhaustive optimum."""
    topo = topology.build("bcube", n=2)
    pat = traffic.pattern("uniform", n_map=1, n_reduce=1, total_gbits=3.0)
    # n_map=1 makes the pinned map-output vector deterministic ([total]),
    # so exhaustive scores are directly comparable to the optimizer's
    map_out = np.array([pat.total_gbits])
    cfg = search.SearchConfig(iters=1200)
    servers = topo.task_servers
    placements = [traffic.Placement(np.array([m]), np.array([r]))
                  for m, r in itertools.permutations(servers, 2)]
    assert len(placements) == 12
    n_slots = 2 * timeslot.suggest_n_slots(
        topo, traffic.generate_from_placement(topo, pat, placements[0],
                                              map_out=map_out))
    cands = search.evaluate_placements(topo, pat, placements, "energy",
                                       map_out=map_out, n_slots=n_slots,
                                       cfg=cfg)
    exhaustive = min(c.score for c in cands)
    assert math.isfinite(exhaustive)
    for method in search.METHODS:
        res = search.optimize_placement(
            topo, pat, "energy", method=method, seed=0, n_slots=n_slots,
            iters=1200, generations=4, population=6)
        np.testing.assert_allclose(
            res.best.score, exhaustive, rtol=1e-6,
            err_msg=f"{method} missed the exhaustive optimum")


def test_batched_evaluator_scores_match_metrics():
    """evaluate_placements scores are the exact paper metrics of the
    solved problems, +inf only for unfinished/infeasible members."""
    topo = _tiny_topo()
    pat = traffic.pattern("uniform", **TINY_PAT)
    rng = np.random.default_rng(0)
    pls = [traffic.sample_placement(topo, pat, rng) for _ in range(3)]
    map_out = traffic._map_outputs(pat, rng)
    n_slots = max(timeslot.suggest_n_slots(
        topo, traffic.generate_from_placement(topo, pat, pl,
                                              map_out=map_out))
        for pl in pls)
    cands = search.evaluate_placements(
        topo, pat, pls, "energy", map_out=map_out, n_slots=n_slots,
        cfg=search.SearchConfig(iters=1200))
    for c in cands:
        if math.isfinite(c.score):
            assert c.score == pytest.approx(float(c.result.metrics.energy_j))
        assert c.problem.n_slots == n_slots


# ------------------------------------------------------------- config


def test_unknown_method_raises():
    topo = _tiny_topo()
    pat = traffic.pattern("uniform", **TINY_PAT)
    with pytest.raises(ValueError, match="unknown method"):
        search.optimize_placement(topo, pat, method="hillclimb")


@pytest.mark.parametrize("bad", [
    dict(generations=-1),
    dict(population=0),
    dict(elite=99),
    dict(alpha=0.0),
    dict(t0_frac=0.0),
    dict(elite=-1),
])
def test_config_validation(bad):
    with pytest.raises(ValueError):
        search.SearchConfig(**bad).validate()


def test_overrides_win_over_cfg():
    topo = _tiny_topo()
    pat = traffic.pattern("uniform", **TINY_PAT)
    cfg = search.SearchConfig(generations=9, population=4, iters=800)
    res = search.optimize_placement(topo, pat, "energy", method="sa",
                                    cfg=cfg, generations=1)
    # 1 seed generation + 1 move generation
    assert len(res.history) == 2


# ------------------------------------------- sweep integration (axis)


def test_sweep_placement_axis(tmp_path):
    """--placement-search end to end: the runner appends one optimized
    row plus the three fixed-baseline rows per seed, tags them with the
    method and gain, and the report renders the gain table."""
    from repro.sweep.report import write_csv, write_markdown
    from repro.sweep.runner import SweepSpec, run_sweep
    spec = SweepSpec(topos=("pon3",), objectives=("energy",),
                     patterns=("uniform",), seeds=(0,), iters=800,
                     total_gbits=8.0, n_map=4, n_reduce=3,
                     oracle_check=0, placement_search=("sa",),
                     placement_generations=2, placement_population=4)
    records, problems = run_sweep(spec)
    assert len(records) == len(problems)
    rows = [r for r in records if r.placement_search != "none"]
    assert len(rows) == 4              # optimized + spread/packed/local
    assert {r.pattern for r in rows} == {"optimized", "spread",
                                         "packed", "local"}
    (opt,) = [r for r in rows if r.pattern == "optimized"]
    assert opt.placement_search == "sa"
    assert opt.feasible and opt.remaining_gbits <= 1e-6
    assert opt.placement_gain >= 1.0 - 1e-9    # incumbent never worse
    # the winning fixed baseline reads exactly 1.0 by construction
    assert any(math.isclose(r.placement_gain, 1.0, rel_tol=1e-12)
               for r in rows if r.pattern != "optimized")
    md = write_markdown(records, tmp_path / "results.md").read_text()
    assert "Placement search (joint placement + routing)" in md
    assert "| pon3 | sa |" in md
    csv_text = write_csv(records, tmp_path / "results.csv").read_text()
    assert "placement_gain" in csv_text.splitlines()[0]
    assert ",optimized," in csv_text


def test_sweep_unknown_search_method_rejected():
    from repro.sweep.runner import SweepSpec
    with pytest.raises(ValueError, match="placement-search"):
        SweepSpec(placement_search=("hillclimb",)).validate()
