"""Chaos engine: seeded trace determinism, exact repair inverses,
mid-run degradation through both rolling-horizon drivers, stranded-flow
recovery, and the zero-demand-leak invariant.

Everything here is exact, not statistical: event traces are seeded and
byte-stable, the fully-repaired fabric is the *same object* the run
started with, and a chaos-off run takes byte-identical decisions to a
healthy one."""
import dataclasses

import numpy as np
import pytest

from repro import service
from repro.core import arrivals, failures, solver, topology, traffic
from repro.core import chaos as chaosmod

TOPO = topology.build("spine-leaf")
PON = topology.build("pon3")


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_executables():
    """The chaos grid compiles many one-off degraded-fabric LP shapes
    under both operators.  On a single-core runner those executables stay live
    in jax's jit caches for the rest of the session and push the
    process over the native JIT code-arena limit hundreds of tests
    later (XLA backend_compile segfaults, reproducibly).  Dropping them
    once this module is done returns the suite to its baseline compile
    load; later modules recompile what they need."""
    yield
    import jax

    jax.clear_caches()


def storm_events(topo=TOPO, seed=0):
    return chaosmod.generate_preset_events(topo, ("storm",), seed)


def small_trace(total=8.0, n_coflows=2, seed=0):
    pat = traffic.pattern("uniform", n_map=4, n_reduce=3,
                          total_gbits=total)
    aspec = arrivals.ArrivalSpec(n_coflows=n_coflows,
                                 mean_interarrival_s=1.0)
    return arrivals.generate_trace(TOPO, pat, aspec, seed)


# ---------------------------------------------------------------------------
# trace generation: seeded, byte-stable, sorted, id-disjoint
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("topo", [TOPO, PON], ids=lambda t: t.name)
@pytest.mark.parametrize("presets", [("mtbf",), ("storm",),
                                     ("mtbf", "storm")])
def test_trace_byte_identical_per_seed(topo, presets):
    a = chaosmod.generate_preset_events(topo, presets, seed=1)
    b = chaosmod.generate_preset_events(topo, presets, seed=1)
    assert chaosmod.format_trace(a) == chaosmod.format_trace(b)
    c = chaosmod.generate_preset_events(topo, presets, seed=2)
    assert chaosmod.format_trace(a) != chaosmod.format_trace(c)
    assert a, "preset trace must not be empty"


def test_trace_pairing_and_order():
    evs = chaosmod.generate_preset_events(TOPO, ("mtbf", "storm"), 0)
    # every event id appears exactly twice: one fail, one repair, with
    # the repair strictly not before its fail
    by_id = {}
    for ev in evs:
        by_id.setdefault(ev.event_id, []).append(ev)
    for eid, pair in by_id.items():
        kinds = sorted(e.kind for e in pair)
        assert kinds == ["fail", "repair"], eid
        fail = next(e for e in pair if e.kind == "fail")
        rep = next(e for e in pair if e.kind == "repair")
        assert rep.t >= fail.t
        assert rep.scenario.name == fail.scenario.name
    # sorted by (t, repair-before-fail, id)
    keys = [(ev.t, ev.kind != "repair", ev.event_id) for ev in evs]
    assert keys == sorted(keys)
    # the scenario name carries the id suffix (composed-name uniqueness)
    assert all(ev.scenario.name.endswith(f"@{ev.event_id}") for ev in evs)


def test_spec_and_event_validation():
    with pytest.raises(ValueError):
        chaosmod.ChaosSpec(classes=("no-such-class",))
    with pytest.raises(ValueError):
        chaosmod.ChaosSpec(classes=("none",))       # "none" is not a failure
    with pytest.raises(ValueError):
        chaosmod.ChaosSpec(mtbf_s=0.0)
    with pytest.raises(ValueError):
        chaosmod.ChaosSpec(storms=-1)
    with pytest.raises(ValueError):
        chaosmod.ChaosEvent(0.0, "explode", 0,
                            failures.FailureScenario("x"))
    with pytest.raises(KeyError):
        chaosmod.generate_preset_events(TOPO, ("no-such-preset",), 0)


# ---------------------------------------------------------------------------
# trace-exact availability integration
# ---------------------------------------------------------------------------

def test_degraded_seconds_closed_form():
    scen = failures.sample(TOPO, "link1", 0)
    evs = [chaosmod.ChaosEvent(1.0, "fail", 0, scen),
           chaosmod.ChaosEvent(3.0, "repair", 0, scen),
           chaosmod.ChaosEvent(2.0, "fail", 1, scen),
           chaosmod.ChaosEvent(2.5, "repair", 1, scen)]
    # overlapping outages count once: degraded span is [1, 3]
    assert chaosmod.degraded_seconds(evs, 4.0) == pytest.approx(2.0)
    assert chaosmod.availability(evs, 4.0) == pytest.approx(0.5)
    # truncation at t_end, including an outage still open there
    assert chaosmod.degraded_seconds(evs, 2.5) == pytest.approx(1.5)
    assert chaosmod.degraded_seconds(evs[:1], 4.0) == pytest.approx(3.0)
    # empty trace / degenerate span -> fully available
    assert chaosmod.availability([], 10.0) == 1.0
    assert chaosmod.availability(evs, 0.0) == 1.0


# ---------------------------------------------------------------------------
# FabricState replay: exact inverses, no-op storms, monotone clock
# ---------------------------------------------------------------------------

def test_fabric_full_repair_is_healthy_object():
    fab = chaosmod.FabricState(TOPO, storm_events())
    assert fab.topo is TOPO and not fab.degraded
    t_last = max(ev.t for ev in fab.events)
    applied, _ = fab.advance_to(t_last + 1.0)
    assert len(applied) == len(fab.events)
    assert fab.applied == len(fab.events)
    assert not fab.degraded and fab.active_names == ()
    # not merely equal: the healthy object itself, so the solver's
    # structure cache key is untouched by a heal-everything trace
    assert fab.topo is TOPO
    assert fab.next_event_t is None


def test_fabric_degrades_and_heals_stepwise():
    evs = storm_events()
    fab = chaosmod.FabricState(TOPO, evs)
    first_fail = min(ev.t for ev in evs if ev.kind == "fail")
    applied, changed = fab.advance_to(first_fail)
    assert applied and changed and fab.degraded
    assert fab.topo is not TOPO
    assert fab.topo.cap.sum() < TOPO.cap.sum()
    with pytest.raises(ValueError):
        fab.advance_to(first_fail - 0.5)            # no rewinding
    # advancing to the same instant is idempotent
    again, changed2 = fab.advance_to(first_fail)
    assert not again and not changed2


def test_noop_storm_within_one_boundary():
    """A fail + repair pair landing inside one boundary interval nets
    out to a provable no-op: events are applied, capacity is unchanged,
    and the fabric is the healthy object again."""
    scen = failures.sample(TOPO, "switch", 0)
    evs = [chaosmod.ChaosEvent(0.1, "fail", 0, scen),
           chaosmod.ChaosEvent(0.2, "repair", 0, scen)]
    fab = chaosmod.FabricState(TOPO, evs)
    applied, changed = fab.advance_to(0.5)
    assert len(applied) == 2
    assert not changed
    assert fab.topo is TOPO


def test_zero_length_outage_resolves_repair_first():
    scen = failures.sample(TOPO, "link1", 0)
    evs = [chaosmod.ChaosEvent(1.0, "repair", 0, scen),
           chaosmod.ChaosEvent(1.0, "fail", 1, scen)]
    # sorted replay applies the id-0 repair before the id-1 fail, so
    # the surviving active set is exactly {1}
    fab = chaosmod.FabricState(TOPO, [evs[1], evs[0]])
    fab.advance_to(1.0)
    assert fab.degraded and set(fab.active_names) == {scen.name}


# ---------------------------------------------------------------------------
# run_online: no-op chaos takes byte-identical decisions to healthy
# ---------------------------------------------------------------------------

def test_online_noop_storm_matches_healthy(operator):
    trace = small_trace()
    scen = failures.sample(TOPO, "switch", 0)
    # both events land before the first epoch boundary: applied there,
    # capacity byte-unchanged, trace exhausted -> epochs must replay
    # the healthy run's decisions exactly
    evs = [chaosmod.ChaosEvent(1e-12, "fail", 0, scen),
           chaosmod.ChaosEvent(2e-12, "repair", 0, scen)]
    kw = dict(iters=1500, tol=5e-3)
    healthy = arrivals.run_online(TOPO, trace, "energy", **kw)
    chaotic = arrivals.run_online(TOPO, trace, "energy", chaos=evs,
                                  fallback_policy="scf", **kw)
    assert chaotic.n_epochs == healthy.n_epochs
    for eh, ec in zip(healthy.epochs, chaotic.epochs):
        assert ec.energy_j == eh.energy_j
        assert ec.shipped_gbits == eh.shipped_gbits
        assert ec.executed_slots == eh.executed_slots
        assert ec.certified
    assert chaotic.total_energy_j == healthy.total_energy_j
    assert chaotic.makespan_s == healthy.makespan_s
    assert chaotic.epochs[0].chaos_events == 2
    assert chaotic.stranded_gbits == 0.0
    assert chaotic.deferred_failure_gbits == 0.0


# ---------------------------------------------------------------------------
# run_online: storm replay is deterministic per seed under every operator
# ---------------------------------------------------------------------------

def test_online_storm_replay_deterministic(operator):
    trace = small_trace(n_coflows=3)
    kw = dict(iters=1500, tol=5e-3, fallback_policy="scf")
    r1 = arrivals.run_online(TOPO, trace, "energy",
                             chaos=storm_events(), **kw)
    r2 = arrivals.run_online(TOPO, trace, "energy",
                             chaos=storm_events(), **kw)
    # byte-identical replay log, run to run
    assert "\n".join(r1.chaos_log) == "\n".join(r2.chaos_log)
    assert r1.availability == r2.availability
    assert r1.recoveries == r2.recoveries
    # events actually hit the run, and every epoch carried a certificate
    assert sum(e.chaos_events for e in r1.epochs) > 0
    assert 0.0 <= r1.availability < 1.0
    assert all(e.certified for e in r1.epochs)
    assert all(e.feasible for e in r1.epochs)
    # zero demand leak: injected == shipped + backlog + deferred
    injected = sum(a.coflow.total_gbits for a in trace)
    shipped = sum(e.shipped_gbits for e in r1.epochs)
    assert injected == pytest.approx(
        shipped + r1.backlog_gbits + r1.deferred_failure_gbits, abs=1e-6)


def test_online_event_application_backend_independent(monkeypatch):
    """The trace and its application times are solver-independent: both
    operators (COO, dense tiles) apply the same events at the same
    boundaries."""
    trace = small_trace(n_coflows=3)
    logs = {}
    for tiles in (False, True):
        monkeypatch.setattr(solver, "_tiled_spmv", lambda t=tiles: t)
        r = arrivals.run_online(TOPO, trace, "energy", iters=1500,
                                tol=5e-3, chaos=storm_events(),
                                fallback_policy="scf")
        logs[tiles] = [l for l in r.chaos_log
                       if " fail " in l or " repair " in l]
    assert logs[False]
    assert logs[True] == logs[False]


# ---------------------------------------------------------------------------
# run_online: stranded-flow recovery (pinned deterministic outage)
# ---------------------------------------------------------------------------

def test_online_spine_outage_strands_and_recovers():
    trace = small_trace(total=48.0)
    spine0 = next(i for i, d in enumerate(TOPO.devices)
                  if d.name == "spine0")
    scen = failures.FailureScenario(name="spine0-down",
                                    failed_devices=(spine0,))
    evs = [chaosmod.ChaosEvent(0.2, "fail", 0, scen),
           chaosmod.ChaosEvent(2.0, "repair", 0, scen)]
    res = arrivals.run_online(TOPO, trace, "energy", epoch_s=0.5,
                              iters=1500, tol=5e-3, chaos=evs,
                              fallback_policy="scf")
    # carried volume routed through the dead spine is detected, logged,
    # and re-routed: the run still drains everything feasibly
    assert res.stranded_gbits > 1.0
    assert any(" strand " in l for l in res.chaos_log)
    assert res.recoveries and all(t >= 0.0 for t in res.recoveries)
    assert any(" recover " in l for l in res.chaos_log)
    assert res.backlog_gbits <= 1e-6
    assert res.deferred_failure_gbits <= 1e-6
    assert all(e.certified and e.feasible for e in res.epochs)
    assert sum(e.stranded_gbits for e in res.epochs) \
        == pytest.approx(res.stranded_gbits)
    # the outage [0.2, 2.0] is integrated trace-exactly over the run
    last = res.epochs[-1]
    t_end = last.t_start + last.executed_slots * TOPO.slot_duration
    assert res.availability == pytest.approx(
        chaosmod.availability(evs, t_end), rel=1e-9)


# ---------------------------------------------------------------------------
# service loop: chaos replay is deterministic; chaos-off is inert
# ---------------------------------------------------------------------------

def service_tenants(n=2):
    pat = traffic.pattern("uniform", n_map=4, n_reduce=3,
                          total_gbits=6.0)
    aspec = arrivals.ArrivalSpec(n_coflows=2, mean_interarrival_s=2.0)
    return [service.TenantSpec(f"t{k}", TOPO, pat, aspec, seed=k)
            for k in range(n)]


def test_service_chaos_replay_byte_identical(operator):
    cfg = service.ServiceConfig(iters=1500, tol=5e-3, chaos=("storm",),
                                chaos_seed=1)
    r1 = service.run_service(service_tenants(), cfg)
    r2 = service.run_service(service_tenants(), cfg)
    assert r1.event_log() == r2.event_log()
    rb = r1.robustness
    assert rb.events_applied > 0
    assert rb.events_applied == sum(
        1 for e in r1.events if e.kind in ("fail", "repair"))
    assert 0.0 <= rb.availability <= 1.0
    assert 0.0 <= rb.degraded_s <= rb.span_s
    assert rb.availability == r2.robustness.availability


def test_service_chaos_off_leaves_run_healthy():
    base = service.ServiceConfig(iters=1500, tol=5e-3)
    r = service.run_service(service_tenants(n=1), base)
    rb = r.robustness
    assert rb == service.RobustnessStats()
    assert rb.availability == 1.0 and rb.events_applied == 0
    assert not any(e.kind in ("fail", "repair", "deferfail", "strand",
                              "recover") for e in r.events)
    assert not r.latency_degraded.samples
    # the chaos knobs themselves round-trip through replace() inertly
    r2 = service.run_service(service_tenants(n=1),
                             dataclasses.replace(base, chaos=(),
                                                 chaos_seed=7))
    assert r2.event_log() == r.event_log()


# ---------------------------------------------------------------------------
# sweep axis: --chaos cells land in CSV, report, and event-trace log
# ---------------------------------------------------------------------------

def test_sweep_chaos_axis(tmp_path):
    from repro.sweep import SweepSpec, run_sweep, write_csv, write_markdown
    spec = SweepSpec(topos=("spine-leaf",), objectives=("energy",),
                     patterns=("uniform",), seeds=(0,),
                     chaos=("storm",), total_gbits=8.0, n_map=4,
                     n_reduce=3, iters=1200, oracle_check=0)
    records, problems = run_sweep(spec)
    assert len(records) == len(problems) == 2          # 1 healthy + 1 chaos
    chaos_rows = [r for r in records if r.chaos != "none"]
    assert len(chaos_rows) == 1
    row = chaos_rows[0]
    assert row.arrivals == "poisson" and row.epochs > 0
    assert 0.0 <= row.availability <= 1.0
    assert row.feasible
    header = write_csv(records, tmp_path / "r.csv").read_text() \
        .splitlines()[0]
    for col in ("chaos", "availability", "stranded_gbits", "recover_s",
                "deferred_gbits"):
        assert col in header, col
    md = write_markdown(records, tmp_path / "r.md").read_text()
    assert "Availability under chaos" in md


def test_sweep_rejects_unknown_chaos_preset():
    from repro.sweep import SweepSpec
    spec = SweepSpec(topos=("spine-leaf",), chaos=("hurricane",))
    with pytest.raises(ValueError, match="chaos preset"):
        spec.validate()
