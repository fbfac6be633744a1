"""The check that decides `correct`, at a size a test run holds (CPU).

The control (each layer's output replaced by the reference computed one
precision below the configuration's) must read above a limit where the
program reads within them; and a whole run, with the timed path broken
underneath, must come out not correct, once for each fault a sweep cell
can have: the LP, the PDHG iterate, the schedule or its reported numbers
altered where they are produced, a PDHG solve that hands back its
starting point, or half of a batch left unanswered.
Test cells and their limits live in bench/testdata."""
import dataclasses
import pathlib
import sys
import time

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import calibrate  # noqa: E402
import harness  # noqa: E402

DATA = BENCH / "testdata"
SEED = 2 ** 31 + 11


@pytest.fixture
def cells(monkeypatch):
    monkeypatch.setattr(harness, "SPEC", DATA / "BENCHMARK.json")
    monkeypatch.setattr(harness, "DATA", DATA)


@pytest.mark.parametrize("cell", ["k4-sweep", "pon3-sweep"])
def test_control_fails_where_the_program_passes(cells, cell):
    driver, limits, _ = harness.prepare(cell, SEED)
    driver.warm_up()
    for r in calibrate.readings(driver, [SEED, 7]):
        prog, ctrl = r["program"], r["control"]
        assert all(prog[k] <= limits[k]["limit"] for k in limits), prog
        failed = [k for k in limits if ctrl[k] > limits[k]["limit"]]
        assert failed, ctrl
        for k in ("lp_gap", "pdhg_obj_gap", "metric_gap"):
            assert ctrl[k] > limits[k]["limit"], (k, ctrl)


def _broken_run(monkeypatch, module, attr, alter):
    orig = getattr(module, attr)

    def broken(*args, **kwargs):
        return alter(orig(*args, **kwargs))

    monkeypatch.setattr(module, attr, broken)
    return harness.run("k4-sweep", SEED, 0.5, False, time.perf_counter())


def test_sound_run_is_correct(cells):
    line = harness.run("k4-sweep", SEED, 0.5, False, time.perf_counter())
    assert line["correct"] and line["attempted"] > 0 and not line["failed"]
    assert list(line)[-1] == "checks"
    assert line["metrics"]["schedules_per_s"]["value"] > 0


def test_fault_lp_altered(cells, monkeypatch):
    from repro.core import solver

    def alter(out):
        lp, idx = out
        h = lp.h.copy()
        h[0] *= 1.001
        return dataclasses.replace(lp, h=h), idx

    line = _broken_run(monkeypatch, solver, "build_routing_lp", alter)
    assert not line["correct"]
    assert line["checks"]["lp_gap"]["value"] > line["checks"]["lp_gap"]["limit"]


def test_fault_pdhg_iterate_altered(cells, monkeypatch):
    from repro.core import solver

    def alter(results):
        for r in results:
            r.x = r.x * 1.05
        return results

    line = _broken_run(monkeypatch, solver, "solve_lp_batch", alter)
    assert not line["correct"]
    c = line["checks"]["pdhg_obj_gap"]
    assert c["value"] > c["limit"]


def test_fault_pdhg_state_unchanged(cells, monkeypatch):
    from repro.core import solver

    def alter(results):
        for r in results:
            r.x = r.x * 0.0   # the solve hands back its zero start
        return results

    line = _broken_run(monkeypatch, solver, "solve_lp_batch", alter)
    assert not line["correct"]
    c = line["checks"]["pdhg_obj_gap"]
    assert c["value"] > c["limit"]


def test_fault_half_the_batch_left_out(cells, monkeypatch):
    from repro.core import solver

    line = _broken_run(monkeypatch, solver, "solve_fast_batch",
                       lambda results: results[:len(results) // 2])
    assert not line["correct"]
    assert line["failed"] >= line["attempted"] // 2 > 0
    c = line["checks"]["unanswered"]
    assert c["value"] > c["limit"]


def test_fault_reported_metrics_altered(cells, monkeypatch):
    from repro.core import solver

    def alter(m):
        m.energy_j *= 1.0001
        return m

    line = _broken_run(monkeypatch, solver, "evaluate", alter)
    assert not line["correct"]
    c = line["checks"]["metric_gap"]
    assert c["value"] > c["limit"]


def test_fault_schedule_altered(cells, monkeypatch):
    from repro.core import solver

    def alter(r):
        r.schedule = r.schedule.copy()
        r.schedule[0] *= 1.01  # flow 0 ships 1% more than its metrics say
        return r

    line = _broken_run(monkeypatch, solver, "_assemble_fast_result", alter)
    assert not line["correct"]
    c = line["checks"]["metric_gap"]
    assert c["value"] > c["limit"]
