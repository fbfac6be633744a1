"""The N-1 re-plan cell ("replan" mix) without a chip: a small fat-tree
cell whose check passes a sound run and refuses the control, a program
that re-plans on the healthy fabric and one that drops a routable flow;
failures chosen by the rule and LP shapes that each draw of a template
repeats; the configuration against fattree-k16's; the three readers."""
import pathlib
import sys
import time

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import calibrate  # noqa: E402
import harness  # noqa: E402
from spans import Spans  # noqa: E402

DATA = BENCH / "testdata" / "replan"
SEED = 2 ** 31 + 11
NUMBERS = ("lp_gap", "pdhg_obj_gap", "metric_gap", "cert_gbits",
           "dropped_gbits")


@pytest.fixture
def cell(monkeypatch):
    monkeypatch.setattr(harness, "SPEC", DATA / "BENCHMARK.json")
    monkeypatch.setattr(harness, "DATA", DATA)
    return "k4-n1-replan"


def test_replan_cell_sound_run_is_correct(cell):
    line = harness.run(cell, SEED, 0.5, False, time.perf_counter())
    assert line["correct"] and line["attempted"] > 0 and not line["failed"]
    assert line["metrics"]["schedules_per_s"]["value"] > 0
    assert list(line)[-1] == "checks"
    assert set(line["checks"]) == {"unanswered", *NUMBERS}
    traced = harness.run(cell, SEED + 1, 0.5, True, time.perf_counter())
    assert traced["correct"]
    for name in ("degrade_ms.replan", "project_ms.replan",
                 "warm_iters.replan"):
        assert traced["metrics"][name]["value"] > 0, name


def test_replan_cell_numbers_inside_limits_and_control_fails(cell):
    driver, limits, _ = harness.prepare(cell, SEED)
    driver.warm_up()
    for r in calibrate.readings(driver, [SEED, 7]):
        prog, ctrl = r["program"], r["control"]
        assert all(prog[k] <= limits[k]["limit"] for k in limits), prog
        assert not r["failed"]
        for k in ("lp_gap", "pdhg_obj_gap", "metric_gap"):
            assert ctrl[k] > limits[k]["limit"], (k, ctrl)


def test_replan_on_the_healthy_fabric_fails_cert_gbits(cell, monkeypatch):
    from repro.core import failures

    monkeypatch.setattr(failures, "apply", lambda topo, scen: topo)
    line = harness.run(cell, SEED, 0.5, False, time.perf_counter())
    assert not line["correct"]
    c = line["checks"]["cert_gbits"]
    assert c["value"] > c["limit"]


def test_zeroing_a_routable_flow_fails_dropped_gbits(cell, monkeypatch):
    from repro.core import failures

    orig = failures.routable_flows

    def drops_flow_0(p):
        ok = orig(p)
        ok[0] = False
        return ok

    monkeypatch.setattr(failures, "routable_flows", drops_flow_0)
    line = harness.run(cell, SEED, 0.5, False, time.perf_counter())
    assert not line["correct"]
    c = line["checks"]["dropped_gbits"]
    assert c["value"] > c["limit"] == 0


def _shapes(lps):
    return [(lp.n, lp.m, len(lp.val)) for lp in lps]


def test_each_draw_of_a_template_meets_the_same_lp_shapes(cell):
    """Every draw of a template fails, in each class, an element the
    healthy plan loads and of the class's tier, and its 4 re-plans have
    the LP shapes of every other draw of that template."""
    driver, _, _ = harness.prepare(cell, SEED)
    solver = driver.solver
    fab = driver.fabric
    seen = {}
    for key in range(4):
        driver.seed = SEED + 7 ** key
        pairs = driver.problems(1, key)
        healthy = [(p, rp, r, True) for (p, rp), r in
                   zip(pairs, driver.solve([p for p, _ in pairs], 2e-3))]
        for (p, rp, r, _), (j, _) in zip(healthy, driver.order(1, key)):
            load = r.schedule.sum(axis=(0, 2, 3))
            fails = driver.failures(rp, r.schedule)
            assert [f.name for f in fails] == driver.classes
            for f in fails:
                assert load[list(f.arcs)].sum() > 0, f
                if f.device is None:
                    ends = set(fab.edges[list(f.arcs)].ravel().tolist())
                    tiers = ({"edge-agg-link": (driver.edge_sw, driver.agg),
                              "agg-core-link": (driver.agg, driver.core)}
                             [f.name])
                    assert len(f.arcs) == 2
                    assert sorted(t[list(ends)].sum() for t in tiers) == \
                        [1, 1]
                else:
                    tier = {"agg-switch": driver.agg,
                            "core-switch": driver.core}[f.name]
                    assert tier[f.device]
            members = driver.degrade([(p, rp, r, True)], Spans())
            lps = [solver.build_routing_lp(dp, driver.objective)[0]
                   for dp, _, _ in members]
            seen.setdefault(j, []).append(_shapes(lps))
    assert sorted(seen) == [0, 1]
    for draws in seen.values():
        assert len(draws) == 4 and all(d == draws[0] for d in draws)


def test_config_is_fattree_k16_under_single_failures():
    cfg = harness.load_json(BENCH / "configs" / "fattree-k16-n1.json")
    base = harness.load_json(BENCH / "configs" / "fattree-k16.json")
    for key in ("fabric", "fingerprint", "shuffle", "schedule", "solver",
                "precision", "reduced", "why_reduced"):
        assert cfg[key] == base[key], key
    mix = harness.load_json(BENCH / "mixes" / "replan-energy-pods.json")
    pods = harness.load_json(BENCH / "mixes" / "sweep-energy-pods.json")
    assert mix["deck"] == pods["deck"] and mix["levels"] == pods["levels"]
    assert mix["failures"] == cfg["failures"]["classes"]
    spec = harness.load_json(harness.SPEC)
    w = harness.workload(spec, "k16-n1-replan")
    assert (w["config"], w["traffic"], w["chips"]) == (
        "fattree-k16-n1", "replan-energy-pods", 1)
    limits = harness.load_json(BENCH / "limits" / "k16-n1-replan.json")
    assert limits["dropped_gbits"]["limit"] == 0


def _reader(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py").read


def test_readers_read_nothing_where_the_program_has_nothing():
    """A program without the spans or the counters (as before they were
    added) leaves each metric out instead of failing."""
    obs = {"schedules": [None] * 20, "replans": 16, "spans": {}}
    for name in ("degrade_ms.replan", "project_ms.replan",
                 "warm_iters.replan"):
        assert _reader(name)(obs) is None, name
        assert _reader(name)(dict(obs, program_spans={})) is None, name
    spans = {"failure.degrade": [0.004] * 16, "warm.project": [0.008] * 20}
    obs = dict(obs, program_spans=spans)
    assert _reader("degrade_ms.replan")(obs) == pytest.approx(4.0)
    assert _reader("project_ms.replan")(obs) == pytest.approx(10.0)
    ens = {"members": 20, "warm_members": 16, "warm_iters": 40000}
    assert _reader("warm_iters.replan")(dict(obs, ensemble=ens)) == 2500.0
    assert _reader("warm_iters.replan")(
        dict(obs, ensemble=dict(ens, warm_members=0))) is None


def test_window_without_ensemble_counters(cell, monkeypatch):
    from repro.core import solver

    driver, _, _ = harness.prepare(cell, SEED)
    monkeypatch.delattr(solver, "ensemble_stats")
    obs = driver.window(0.0, Spans())
    assert "ensemble" not in obs and obs["replans"] == 8
    assert driver.check(obs)["dropped_gbits"] == 0.0
