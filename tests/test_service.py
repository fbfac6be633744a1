"""Scheduler service: deterministic replay, coalescing correctness, SLO
accounting, admission control under overload, and the opt-in soak.

Everything here drives repro.service.run_service through the injectable
virtual clock with the deterministic "iterations" cost model, so every
assertion — including byte-identical event logs — is exact, not
statistical."""
import dataclasses

import numpy as np
import pytest

from repro import service
from repro.core import arrivals, solver, timeslot, topology, traffic

TOPO = topology.build("spine-leaf")
LIGHT = traffic.pattern("uniform", n_map=4, n_reduce=3, total_gbits=6.0)
# heavy enough that flows span several windows and carry residuals
HEAVY = traffic.pattern("uniform", n_map=4, n_reduce=3, total_gbits=48.0)


def light_tenants(n=2, n_coflows=2):
    spec = arrivals.ArrivalSpec(n_coflows=n_coflows,
                                mean_interarrival_s=2.0)
    return [service.TenantSpec(f"t{k}", TOPO, LIGHT, spec, seed=k)
            for k in range(n)]


CFG = service.ServiceConfig(iters=1500, tol=2e-3)


# ---------------------------------------------------------------------------
# virtual clock + cost model
# ---------------------------------------------------------------------------

def test_clock_monotone():
    c = service.VirtualClock()
    assert c.now() == 0.0
    c.advance(1.5)
    c.advance_to(1.5)              # exact landing is fine
    assert c.now() == 1.5
    with pytest.raises(ValueError):
        c.advance(-0.1)
    with pytest.raises(ValueError):
        c.advance_to(1.0)          # rewinding is not


def test_cost_model():
    m = service.SolveCostModel(base_s=0.1, per_iteration_s=1e-3,
                               per_instance_s=0.01)
    assert m.cost_s(iterations=100, n_members=2, wall_s=99.0) \
        == pytest.approx(0.1 + 0.1 + 0.02)
    w = service.SolveCostModel(mode="measured")
    assert w.cost_s(iterations=100, n_members=2, wall_s=0.5) == 0.5
    with pytest.raises(ValueError):
        service.SolveCostModel(mode="wall")


def test_nearest_rank_percentiles():
    vals = [0.4, 0.1, 0.3, 0.2]
    assert service.nearest_rank(vals, 50.0) == 0.2
    assert service.nearest_rank(vals, 99.0) == 0.4
    assert service.nearest_rank(vals, 100.0) == 0.4
    assert np.isnan(service.nearest_rank([], 50.0))
    s = service.LatencyStats()
    for v in vals:
        s.add(v)
    assert (s.p50, s.p99, s.p999) == (0.2, 0.4, 0.4)
    with pytest.raises(ValueError):
        s.add(-1.0)


# ---------------------------------------------------------------------------
# deterministic replay (acceptance criterion, both operators)
# ---------------------------------------------------------------------------

def test_replay_byte_identical_event_log(operator):
    tenants = light_tenants()
    r1 = service.run_service(tenants, CFG)
    r2 = service.run_service(tenants, CFG)
    log = r1.event_log()
    assert log == r2.event_log()            # byte-identical replay
    assert len(log.splitlines()) == len(r1.events) > 0
    # schedule metrics replay exactly too, not just the log
    assert r1.total_energy_j == r2.total_energy_j
    assert r1.makespan_s == r2.makespan_s
    assert [t.energy_j for t in r1.tenants] \
        == [t.energy_j for t in r2.tenants]
    assert r1.latency.samples == r2.latency.samples
    assert r1.backlog_gbits == 0.0
    assert all(r.status == "done" for r in r1.requests)


def test_event_log_canonical_shape():
    r = service.run_service(light_tenants(), CFG)
    kinds = {"arrive", "admit", "shed", "defer", "dispatch", "sched",
             "retry", "exec", "done"}
    ts = []
    for ev in r.events:
        assert ev.kind in kinds
        assert ev.line.startswith(f"t={ev.t:.6f} {ev.kind} ")
        ts.append(ev.t)
    assert ts == sorted(ts)                 # monotone event timeline


# ---------------------------------------------------------------------------
# coalescing correctness: stacked dispatch == per-tenant solves
# ---------------------------------------------------------------------------

def test_coalesced_equals_serial_service_run():
    tenants = light_tenants(n=3)
    coal = service.run_service(tenants, CFG)
    serial = service.run_service(
        tenants, dataclasses.replace(CFG, coalesce=False,
                                     overlap_build=False))
    assert coal.counters.dispatches < serial.counters.dispatches
    for a, b in zip(coal.tenants, serial.tenants):
        assert a.energy_j == pytest.approx(b.energy_j, rel=1e-4)
        assert a.makespan_s == pytest.approx(b.makespan_s, rel=1e-4)
        assert a.n_done == b.n_done
    assert coal.total_energy_j == pytest.approx(serial.total_energy_j,
                                                rel=1e-4)
    # completion events agree per request, not just in aggregate
    done_c = {(r.tenant, r.coflow_id): r.t_done for r in coal.requests}
    done_s = {(r.tenant, r.coflow_id): r.t_done for r in serial.requests}
    assert done_c.keys() == done_s.keys()
    for k in done_c:
        assert done_c[k] == pytest.approx(done_s[k], rel=1e-4, abs=1e-6)


def test_group_solve_matches_solve_fast_warm():
    """The dispatch primitive itself: one stacked heterogeneous group
    reproduces each member's solo solve_fast_warm within fp noise."""
    probs = []
    for s in range(3):
        cf = traffic.generate(TOPO, LIGHT, s)
        probs.append(timeslot.ScheduleProblem(
            TOPO, cf, n_slots=timeslot.suggest_n_slots(TOPO, cf),
            path_slack=2))
    objs = ["energy", "time", "energy"]
    grp = solver.solve_fast_group(probs, objs, iters=1500, tol=2e-3)
    for p, o, g in zip(probs, objs, grp):
        solo = solver.solve_fast_warm(p, o, iters=1500, tol=2e-3)
        assert g.metrics.energy_j == pytest.approx(solo.metrics.energy_j,
                                                   rel=1e-4)
        assert g.metrics.completion_s == pytest.approx(
            solo.metrics.completion_s, rel=1e-4)
        assert not g.warm_started
    # warm pass: flow-mapped identity projection cuts iterations
    warm = solver.solve_fast_group(
        probs, objs, warm=list(grp),
        flow_maps=[np.arange(p.coflow.n_flows) for p in probs],
        iters=1500, tol=2e-3)
    assert all(g.warm_started for g in warm)
    assert sum(g.iterations for g in warm) \
        < sum(g.iterations for g in grp)
    # a shape-incompatible warm member degrades to cold, solo
    other = topology.build("pon3")
    cf = traffic.generate(other, LIGHT, 0)
    p_other = timeslot.ScheduleProblem(
        other, cf, n_slots=timeslot.suggest_n_slots(other, cf),
        path_slack=2)
    mixed = solver.solve_fast_group(
        [probs[0], p_other], ["energy", "energy"],
        warm=[grp[0], grp[1]], iters=1500, tol=2e-3)
    assert mixed[0].warm_started and not mixed[1].warm_started


# ---------------------------------------------------------------------------
# SLO accounting
# ---------------------------------------------------------------------------

def test_slo_accounting_exact():
    # one tenant, both co-flows at t=0 -> exactly one dispatch whose
    # deterministic cost IS every request's decision latency
    tenant = service.TenantSpec(
        "t0", TOPO, LIGHT, None, trace=arrivals.trace_at_t0(
            [traffic.generate(TOPO, LIGHT, s) for s in range(2)]))
    cost = service.SolveCostModel(base_s=0.5, per_iteration_s=0.0,
                                  per_instance_s=0.0)
    tight = dataclasses.replace(CFG, cost=cost, slo_p99_s=0.4)
    r = service.run_service([tenant], tight)
    assert r.latency.count == 2
    assert r.latency.p50 == r.latency.p99 == r.latency.p999 == 0.5
    assert r.counters.slo_breaches == 2     # 0.5 > 0.4 for both
    loose = dataclasses.replace(CFG, cost=cost, slo_p99_s=0.6)
    r2 = service.run_service([tenant], loose)
    assert r2.counters.slo_breaches == 0
    assert r2.latency.samples == r.latency.samples


def test_latency_includes_queueing_delay():
    # an arrival mid-window waits for the next boundary; its decision
    # latency must include that wait, not just the solve cost
    cf = traffic.generate(TOPO, LIGHT, 0)
    tenant = service.TenantSpec(
        "t0", TOPO, LIGHT, None,
        trace=[arrivals.Arrival(0.0, cf, 0),
               arrivals.Arrival(0.1, traffic.generate(TOPO, LIGHT, 1), 1)])
    cost = service.SolveCostModel(base_s=0.01, per_iteration_s=0.0,
                                  per_instance_s=0.0)
    r = service.run_service([tenant],
                            dataclasses.replace(CFG, cost=cost))
    lat = {rq.coflow_id: rq.latency_s for rq in r.requests}
    window_s = 4.0 * TOPO.slot_duration
    assert lat[0] == pytest.approx(0.01)
    # request 1 arrived at 0.1, admitted at the next boundary
    assert lat[1] >= window_s - 0.1
    assert r.counters.windows >= 2


# ---------------------------------------------------------------------------
# admission control under overload
# ---------------------------------------------------------------------------

def test_overload_sheds_past_queue_bound():
    flood = service.TenantSpec(
        "f", TOPO, LIGHT,
        arrivals.ArrivalSpec(family="burst", n_coflows=10, burst_size=10,
                             mean_interarrival_s=0.1), seed=3)
    cfg = dataclasses.replace(CFG, max_pending=4)
    r = service.run_service([flood], cfg)
    assert r.counters.arrived == 10
    assert r.counters.shed == 6             # queue bound 4, burst of 10
    assert r.counters.admitted == 4
    shed = [rq for rq in r.requests if rq.status == "shed"]
    assert len(shed) == 6
    assert all(np.isnan(rq.t_decision) for rq in shed)
    assert r.latency.count == 4             # shed requests never sampled
    assert sum(l.startswith("t=") and " shed " in l
               for l in r.event_log().splitlines()) == 6
    # shed demand is not backlog: admitted work still drains fully
    assert r.backlog_gbits == 0.0


def test_backlog_cap_defers_then_serves():
    flood = service.TenantSpec(
        "f", TOPO, LIGHT,
        arrivals.ArrivalSpec(family="burst", n_coflows=4, burst_size=4,
                             mean_interarrival_s=0.1), seed=3)
    cfg = dataclasses.replace(CFG, max_backlog_gbits=LIGHT.total_gbits)
    r = service.run_service([flood], cfg)
    # one co-flow per window fits the cap; the rest defer but are never
    # dropped — every request still completes
    assert r.counters.deferred > 0
    assert r.counters.shed == 0
    assert all(rq.status == "done" for rq in r.requests)
    assert r.backlog_gbits == 0.0
    # deferral shows up as queueing delay in the tail
    assert r.latency.max > r.latency.percentile(1.0)


# ---------------------------------------------------------------------------
# bucket-hit accounting
# ---------------------------------------------------------------------------

def test_repeat_run_hits_compiled_shapes():
    tenants = light_tenants()
    r1 = service.run_service(tenants, CFG)
    r2 = service.run_service(tenants, CFG)
    assert r1.counters.solver_dispatches == r2.counters.solver_dispatches
    # the second identical run lands every stacked dispatch on a shape
    # the first one already compiled
    assert r2.counters.bucket_hits == r2.counters.solver_dispatches


# ---------------------------------------------------------------------------
# opt-in soak: sustained overload, zero leaks, monotone clock
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_soak_10k_arrivals_no_leaks():
    tiny = traffic.pattern("uniform", n_map=1, n_reduce=1,
                           total_gbits=4.0)
    spec = arrivals.ArrivalSpec(n_coflows=2500,
                                mean_interarrival_s=0.02)
    tenants = [service.TenantSpec(f"t{k}", TOPO, tiny, spec, seed=k)
               for k in range(4)]
    cfg = dataclasses.replace(CFG, max_pending=16, iters=1000,
                              max_windows=512)
    r = service.run_service(tenants, cfg)
    assert r.counters.arrived == 10_000
    assert r.counters.shed > 0              # the overload really bit
    # monotone clock across the whole event timeline
    ts = [e.t for e in r.events]
    assert all(a <= b for a, b in zip(ts, ts[1:]))
    # zero leaks: every request is accounted exactly once, and all
    # admitted demand was served (no residual backlog at drain)
    statuses = {s: sum(rq.status == s for rq in r.requests)
                for s in ("done", "shed", "waiting", "scheduled")}
    assert statuses["waiting"] == statuses["scheduled"] == 0
    assert statuses["done"] + statuses["shed"] == 10_000
    assert statuses["done"] == r.counters.admitted
    assert r.backlog_gbits == 0.0
    gbits_in = sum(rq.gbits for rq in r.requests)
    gbits_shed = sum(rq.gbits for rq in r.requests
                     if rq.status == "shed")
    served = sum(t.shipped_gbits for t in r.tenants)
    assert served == pytest.approx(gbits_in - gbits_shed, rel=1e-6)


# ---------------------------------------------------------------------------
# cheap-fallback tier (core.policies) + schedule verification
# ---------------------------------------------------------------------------

def _starve_solver(monkeypatch):
    """Make every LP result claim residual demand: the retry ladder then
    provably exhausts, handing each window to the fallback tier."""
    real_group = solver.solve_fast_group
    real_warm = solver.solve_fast_warm
    monkeypatch.setattr(
        solver, "solve_fast_group",
        lambda *a, **k: [dataclasses.replace(r, remaining_gbits=1.0)
                         for r in real_group(*a, **k)])
    monkeypatch.setattr(
        solver, "solve_fast_warm",
        lambda *a, **k: dataclasses.replace(real_warm(*a, **k),
                                            remaining_gbits=1.0))


def test_fallback_tier_rescues_starved_solver(monkeypatch):
    """When the retry ladder exhausts, the baseline-policy tier must
    take the windows, drain the demand, and produce certificate-clean
    schedules (verify_schedules on) — and the next window warm-starts
    from the policy result without complaint."""
    _starve_solver(monkeypatch)
    cfg = dataclasses.replace(CFG, iters=200, fallback_policy="scf",
                              verify_schedules=True)
    r = service.run_service(light_tenants(), cfg)
    assert r.counters.fallbacks > 0
    assert r.backlog_gbits <= 1e-6
    assert any(e.kind == "fallback" for e in r.events)
    assert all(rq.status == "done" for rq in r.requests)


def test_fallback_disabled_churns_retries(monkeypatch):
    """Same exhausted ladder with the tier off: the loop must fall
    through with retry churn and zero fallback events — the tier never
    activates implicitly."""
    _starve_solver(monkeypatch)
    cfg = dataclasses.replace(CFG, iters=200, fallback_policy=None)
    r = service.run_service(light_tenants(), cfg)
    assert r.counters.fallbacks == 0
    assert r.counters.retries > 0
    assert not any(e.kind == "fallback" for e in r.events)


def test_healthy_run_never_falls_back():
    """At the normal iteration budget the ladder never exhausts, so the
    tier stays dormant and the event log is unchanged by its presence
    (the golden service pin relies on this)."""
    on = service.run_service(light_tenants(), CFG)
    off = service.run_service(
        light_tenants(), dataclasses.replace(CFG, fallback_policy=None))
    assert on.counters.fallbacks == off.counters.fallbacks == 0
    assert on.event_log() == off.event_log()


def test_verify_schedules_certifies_members():
    """verify_schedules=True must pass cleanly on a healthy run — every
    member schedule the loop executes carries a zero-residual
    certificate."""
    cfg = dataclasses.replace(CFG, verify_schedules=True)
    r = service.run_service(light_tenants(), cfg)
    assert r.backlog_gbits == 0.0


# ---------------------------------------------------------------------------
# CLI smoke mode (python -m repro.sweep --service)
# ---------------------------------------------------------------------------

def test_sweep_service_cli_smoke(tmp_path, capsys):
    from repro.sweep.__main__ import main
    rc = main(["--service", "1", "--topos", "spine-leaf",
               "--patterns", "uniform", "--total-gbits", "6",
               "--n-map", "2", "--n-reduce", "2",
               "--arrival-coflows", "2", "--iters", "800",
               "--slo-s", "8", "--out", str(tmp_path)])
    assert rc == 0                          # zero backlog leaked
    out = capsys.readouterr().out
    assert "latency p50=" in out and "p99=" in out
    assert "shed=0 " in out                 # low load never sheds
    log = (tmp_path / "service_events.log").read_text()
    assert log.startswith("t=") and "arrive" in log and "done" in log
