"""Program spans (repro.trace) and the PDHG work counters of
core.solver.DispatchStats."""
import threading

import jax
import numpy as np
import pytest

from repro import trace
from repro.core import failures, solver, timeslot, topology, traffic

PACK = ("pack.decompose", "pack.slots", "pack.evaluate")
PDHG = ("pdhg.stack", "pdhg.run", "pdhg.unstack")


def _problems(n=2, topo_name="pon3", pattern="packed"):
    topo = topology.build(topo_name)
    pat = traffic.pattern(pattern, n_map=4, n_reduce=3, total_gbits=8.0)
    return [timeslot.ScheduleProblem(topo, cf,
                                     n_slots=timeslot.suggest_n_slots(topo, cf))
            for cf in traffic.generate_batch(topo, pat, range(n))]


def _leaf(name):
    with trace.span(name):
        pass


@pytest.fixture
def notes(monkeypatch):
    """Counts the TraceAnnotations built."""
    made = []
    real = jax.profiler.TraceAnnotation

    def counting(name, **kw):
        made.append(name)
        return real(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    return made


@pytest.mark.parametrize("what", ["span", "decorators", "solve", "replan"])
def test_off_stores_nothing_and_builds_no_annotation(notes, what):
    assert trace.active() is None
    if what == "span":
        with trace.span("a"):
            with trace.span("b"):
                pass
    elif what == "decorators":
        trace.batched(trace.spanned("f")(lambda: None))()
    elif what == "solve":
        solver.solve_fast_batch(_problems(1), "time", iters=500)
    else:
        # failures.degrade_problem and solver.project_warm_start
        p = _problems(1)[0]
        healthy = solver.solve_fast_batch([p], "time", iters=500)[0]
        dp = failures.degrade_problem(p, failures.sample(p.topo, "link1", 0),
                                      n_slots=p.n_slots)
        solver.solve_fast_ensemble([dp], "time", warm=[healthy], iters=500)
    assert notes == []
    with trace.recording() as rec:
        pass
    assert rec.records == []


def test_on_annotates_every_span(notes):
    with trace.recording() as rec:
        with trace.span("a"):
            trace.spanned("f")(lambda: None)()
    assert notes == ["a", "f"]
    assert [r.name for r in rec.records] == ["a", "f"]
    assert all(r.end_ns >= r.start_ns for r in rec.records)
    assert set(rec.seconds()) == {"a", "f"}


def test_nested_spans_record_their_parent():
    with trace.recording() as rec:
        with trace.span("a"):
            with trace.span("b"):
                with trace.span("c"):
                    pass
            with trace.span("d"):
                pass
        with trace.span("e"):
            pass
    parents = {r.name: r.parent for r in rec.records}
    assert parents == {"a": None, "b": 0, "c": 1, "d": 0, "e": None}


def test_span_on_another_thread_has_no_parent_there():
    with trace.recording() as rec:
        with trace.span("main"):
            t = threading.Thread(target=_leaf, args=("worker",))
            t.start()
            t.join(timeout=10)
            assert not t.is_alive()
    assert {r.name: r.parent for r in rec.records} == {"main": None,
                                                       "worker": None}


@pytest.mark.parametrize("nested_in", ["slots", "none"])
def test_total_counts_nested_spans_of_a_prefix_once(nested_in):
    with trace.recording() as rec:
        with trace.span("pack.slots"):
            if nested_in == "slots":
                with trace.span("pack.decompose"):
                    pass
        if nested_in == "none":
            with trace.span("pack.decompose"):
                pass
    s = rec.seconds()
    want = s["pack.slots"][0]
    if nested_in == "none":
        want += s["pack.decompose"][0]
    assert rec.total("pack.") == pytest.approx(want, abs=1e-9)
    assert rec.total("pack.decompose") == pytest.approx(
        s["pack.decompose"][0], abs=1e-9)
    assert rec.total("pack.", start=len(rec.records)) == 0.0


def test_batched_calls_share_one_id_and_nest_into_the_outer():
    inner = trace.batched(lambda: _leaf("x"))

    @trace.batched
    def outer():
        with trace.span("y"):
            inner()

    with trace.recording() as rec:
        outer()
        inner()
        _leaf("z")
    ids = {r.name + str(i): r.batch for i, r in enumerate(rec.records)}
    assert ids["y0"] == ids["x1"] is not None
    assert ids["x2"] not in (None, ids["y0"])
    assert ids["z3"] is None


def test_solve_fast_batch_spans(operator):
    probs = _problems(2)
    d0 = solver.dispatch_stats().snapshot()
    with trace.recording() as rec:
        solver.solve_fast_batch(probs, "time", iters=500)
        n_first = len(rec.records)
        solver.solve_fast_batch(probs[:1], "time", iters=500)
    levels = solver.dispatch_stats().dispatches - d0.dispatches
    s = rec.seconds()
    for name in ("lp.build",) + PACK:
        assert len(s[name]) == 3, name
    for name in PDHG:
        assert len(s[name]) == levels, name
    assert set(s) == {"lp.build", *PACK, *PDHG}
    # one id per solve_fast_batch call
    first = {r.batch for r in rec.records[:n_first]}
    second = {r.batch for r in rec.records[n_first:]}
    assert len(first) == len(second) == 1
    assert first != second and None not in first | second


def _lp(topo_name="pon3", seed=0):
    p = _problems(seed + 1, topo_name)[seed]
    lp, _ = solver.build_routing_lp(p, "time")
    return lp


@pytest.mark.parametrize("how", ["bucket-shaped", "unbucketed"])
def test_one_instance_on_its_own_shape_wastes_nothing(how):
    lp = _lp()
    if how == "bucket-shaped":
        lp, _ = solver._pad_for_buckets(lp)
        assert len(lp.val) == solver._bucket(len(lp.val))
    d0 = solver.dispatch_stats().snapshot()
    res = solver.solve_lp_batch([lp], iters=1000,
                                bucket=how == "bucket-shaped")
    d = solver.dispatch_stats()
    run = d.nnz_iters_run - d0.nnz_iters_run
    useful = d.nnz_iters_useful - d0.nnz_iters_useful
    assert useful == run == res[0].iterations * len(lp.val) > 0


def test_a_frozen_instance_makes_useful_work_lower(operator):
    a, b = _lp(seed=0), _lp(seed=1)
    solo = solver.solve_lp_batch([a], iters=1000)[0]
    d0 = solver.dispatch_stats().snapshot()
    res = solver.solve_lp_batch(
        [a, b], iters=1000,
        warm_starts=[(solo.x, solo.y), (np.zeros(b.n), np.zeros(b.m))])
    d = solver.dispatch_stats()
    run = d.nnz_iters_run - d0.nnz_iters_run
    useful = d.nnz_iters_useful - d0.nnz_iters_useful
    assert res[0].iterations < res[1].iterations
    assert useful == (res[0].iterations * len(a.val)
                      + res[1].iterations * len(b.val))
    assert 0 < useful < run
