"""The exact accounting (`timeslot.evaluate`, `verify.check_schedule`)
against a dense reference kept in this file: the paper's equations as a
loop over flows with `np.add.at` over every edge of the dense
(F, E, W, T) tensor.  The program reads only the schedule's nonzero
entries, so every family, the energy, completion time, fairness term,
`served`, `psi` and `active_devices` must agree to summation order, on
solved schedules, on planted faults, on an all-zero, a fully dense
random and an empty (F = 0) schedule; NaN and inf are refused."""
import copy
import functools

import numpy as np
import pytest

from repro.core import solver, timeslot, topology, traffic, verify
from repro.core.timeslot import TOL

RTOL = ATOL = 1e-12
TOPOS = ("spine-leaf", "pon3", "fat-tree", "bcube")
OBJECTIVES = ("energy", "time")


# ---------------------------------------------------------------------------
# the dense reference: one flow at a time, np.add.at over every edge
# ---------------------------------------------------------------------------

def _dense(p, x):
    """(families, metrics) of `x` by the dense per-flow formulas."""
    F, E, W, T = p.shape_x
    V, D = p.topo.n_vertices, p.topo.slot_duration
    psi = x.sum(axis=0)
    res = {"capacity": float(
        (psi - p.slot_cap_gbits[:, :, None]).max(initial=0.0))}
    egress = np.zeros((V, T))
    np.add.at(egress, p.e_src, psi.sum(axis=1))
    res["egress"] = float((egress[p.is_server] - p.rho * D).max(initial=0.0))
    ingress = np.zeros((V, T))
    np.add.at(ingress, p.e_dst, psi.sum(axis=1))
    sw = p.is_switch & np.isfinite(p.sigma)
    res["ingress"] = float(
        (ingress[sw] - p.sigma[sw, None] * D).max(initial=0.0))
    res["mask"] = float(
        (x * ~p.flow_edge_mask[:, :, None, None]).max(initial=0.0))
    passive = ~(p.is_server | p.is_switch)
    cons = 0.0
    delta = np.zeros((F, T))
    for f in range(F):
        net = np.zeros((V, W, T))
        np.add.at(net, p.e_src, x[f])
        np.subtract.at(net, p.e_dst, x[f])
        inter = np.ones(V, dtype=bool)
        inter[p.coflow.src[f]] = inter[p.coflow.dst[f]] = False
        cons = max(cons, float(np.abs(net[inter & passive]).max(initial=0.0)))
        cons = max(cons, float(np.abs(net.sum(axis=1)[inter]).max(initial=0.0)))
        s = p.coflow.src[f]
        delta[f] = (x[f, p.e_src == s].sum(axis=(0, 1))
                    - x[f, p.e_dst == s].sum(axis=(0, 1)))
    res["conservation"] = cons
    served = delta.sum(axis=1)
    res["demand"] = float(np.abs(served - p.coflow.size).max(initial=0.0))
    rel = 0.0
    if p.release_slot is not None:
        for f in range(F):
            r = int(p.release_slot[f])
            if r > 0:
                rel = max(rel, float(x[f, :, :, :r].max(initial=0.0)))
    res["release"] = rel
    wav = 0.0
    if p.topo.one_wavelength_tx and p.topo.awgr_in_ports:
        awgr_in = np.isin(p.e_dst, p.topo.awgr_in_ports)
        for i in np.flatnonzero(p.is_server):
            sel = (p.e_src == i) & awgr_in
            if sel.any():
                n_w_used = (psi[sel].sum(axis=0) > TOL).sum(axis=0)
                wav = max(wav, float(n_w_used.max(initial=0) - 1))
    res["wavelength"] = wav

    beta = np.zeros((V, W, T))
    np.add.at(beta, p.e_src, psi)
    np.add.at(beta, p.e_dst, psi)
    active = beta > TOL
    energy = D * float((active * p.p_max[:, None, None]).sum())
    energy += D * float((p.eps[:, None, None] * beta
                         * p.is_server[:, None, None]).sum())
    with np.errstate(divide="ignore", invalid="ignore"):
        tx_time = np.where(psi > TOL,
                           psi / np.maximum(p.topo.cap[:, :, None], 1e-30), 0.0)
    t_idx = np.arange(1, T + 1)[None, None, :]
    omega = np.where(psi > TOL, D * (t_idx - 1) + tx_time, 0.0)
    metrics = {"energy_j": energy,
               "completion_s": float(omega.max(initial=0.0)),
               "fairness_term": p.q_weight * float(
                   (delta * t_idx[0, 0][None, :]).sum()),
               "max_violation": max(res.values()),
               "psi": psi, "active_devices": active, "served": served}
    return res, metrics


def _assert_matches_dense(p, x):
    """evaluate and check_schedule agree with `_dense` on `x`; returns
    the certificate."""
    want_res, want = _dense(p, x)
    cert = verify.check_schedule(p, x)
    m = timeslot.evaluate(p, x)
    assert list(cert.residuals) == list(verify.FAMILIES)
    for k in verify.FAMILIES:
        np.testing.assert_allclose(cert.residuals[k], want_res[k],
                                   rtol=RTOL, atol=ATOL, err_msg=k)
    for k in ("energy_j", "completion_s", "fairness_term", "max_violation",
              "served", "psi"):
        got = getattr(m, k)
        assert np.shape(got) == np.shape(want[k]), k
        assert np.asarray(got).dtype == np.asarray(want[k]).dtype, k
        np.testing.assert_allclose(got, want[k], rtol=RTOL, atol=ATOL,
                                   err_msg=k)
    assert m.active_devices.dtype == want["active_devices"].dtype
    np.testing.assert_array_equal(m.active_devices, want["active_devices"])
    assert m.max_violation == cert.max_residual
    assert m.feasible == cert.ok == (want["max_violation"] <= 1e-4)
    return cert


# ---------------------------------------------------------------------------
# problems and schedules
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _problem(topo_name: str) -> timeslot.ScheduleProblem:
    topo = topology.build(topo_name)
    cf = traffic.generate(topo, traffic.pattern(
        "uniform", n_map=4, n_reduce=3, total_gbits=8.0), 0)
    return timeslot.ScheduleProblem(
        topo, cf, n_slots=timeslot.suggest_n_slots(topo, cf), path_slack=2)


@functools.lru_cache(maxsize=None)
def _schedule(topo_name: str, objective: str) -> np.ndarray:
    x = solver.solve_fast(_problem(topo_name), objective, iters=1500).schedule
    x.setflags(write=False)
    return x


@pytest.mark.parametrize("objective", OBJECTIVES)
@pytest.mark.parametrize("topo_name", TOPOS)
def test_accounting_matches_dense_formulas(topo_name, objective):
    """Solved schedules, and the same with noise on every admissible
    entry (so that every family reads nonzero), on a fabric whose
    servers relay (BCube), one with passive AWGR vertices and one TX
    wavelength a slot (PON3), and two electronic ones."""
    p = _problem(topo_name)
    x = _schedule(topo_name, objective)
    assert _assert_matches_dense(p, x).ok
    noisy = x + 0.05 * np.random.default_rng(7).random(x.shape) * \
        p.flow_edge_mask[:, :, None, None]
    _assert_matches_dense(p, noisy)


def _inadmissible(p):
    """An (f, e) the flow-edge mask forbids, on an edge with capacity."""
    f, e = np.nonzero(~p.flow_edge_mask & (p.topo.cap.sum(axis=1) > 0))
    return int(f[0]), int(e[0])


def _plant(kind):
    """(problem, schedule, family that must catch the fault)."""
    name = "pon3" if kind in ("two_tx", "converted") else "spine-leaf"
    p, x = _problem(name), np.array(_schedule(name, "energy"))
    if kind == "outside_mask":
        f, e = _inadmissible(p)
        x[f, e, 0, 0] = 0.5
        return p, x, "mask"
    if kind == "negative":
        f, e = np.argwhere(x.sum(axis=(2, 3)) > 0.1)[0]
        x[f, e, 0, 0] = -0.5
        return p, x, "demand" if p.e_src[e] == p.coflow.src[f] \
            else "conservation"
    if kind == "early":
        # every flow released at the first slot it uses, one a slot later
        q = _released_at_first_use(p, x)
        f = int(np.argmax(q.release_slot == 0))
        q.release_slot[f] = 1
        return q, x, "release"
    if kind == "converted":
        # a passive AWGR port that forwards a flow on another wavelength
        # than it arrived on: conserved summed over wavelengths, not per
        # wavelength (eq. 25)
        passive = ~(p.is_server | p.is_switch)
        f, e, w, t = next(
            (f, e, w, t) for f, e, w, t in np.argwhere(x > 0.1)
            if passive[p.e_src[e]] and p.topo.cap[e, (w + 1) % 4] > 0)
        x[f, e, (w + 1) % 4, t] += x[f, e, w, t]
        x[f, e, w, t] = 0.0
        return p, x, "conservation"
    assert kind == "two_tx"
    awgr_in = np.isin(p.e_dst, p.topo.awgr_in_ports)
    f = 0
    e = int(np.flatnonzero((p.e_src == p.coflow.src[f]) & awgr_in)[0])
    x[f, e, :2, 0] += 0.01
    return p, x, "wavelength"


def _released_at_first_use(p, x):
    """A copy of `p` that releases each flow at the first slot it uses."""
    q = copy.copy(p)
    q.release_slot = np.argmax(x.sum(axis=(1, 2)) > 0, axis=1)
    return q


@pytest.mark.parametrize("kind", ["outside_mask", "negative", "early",
                                  "converted", "two_tx"])
def test_planted_fault_is_caught(kind):
    p, x, family = _plant(kind)
    cert = _assert_matches_dense(p, x)
    assert not cert.ok and cert.residuals[family] > cert.tol, cert.summary()
    assert not timeslot.evaluate(p, x).feasible


@pytest.mark.parametrize("objective", OBJECTIVES)
def test_release_at_first_used_slot_is_met(objective):
    x = _schedule("pon3", objective)
    q = _released_at_first_use(_problem("pon3"), x)
    assert q.release_slot.max() > 0
    cert = _assert_matches_dense(q, x)
    assert cert.ok and cert.residuals["release"] == 0.0


@pytest.mark.parametrize("where", ["admissible", "outside_mask"])
@pytest.mark.parametrize("value", [np.nan, np.inf, -np.inf])
def test_non_finite_entry_is_refused(value, where):
    p = _problem("spine-leaf")
    x = np.array(_schedule("spine-leaf", "energy"))
    if where == "admissible":
        f, e = np.argwhere(x.sum(axis=(2, 3)) > 0.1)[0]
    else:
        f, e = _inadmissible(p)
    x[f, e, 0, 1] = value
    cert = verify.check_schedule(p, x)
    m = timeslot.evaluate(p, x)
    assert not cert.ok and not m.feasible
    assert not np.isfinite(cert.max_residual)
    assert not np.isfinite(m.max_violation)
    with pytest.raises(AssertionError, match="infeasible schedule"):
        cert.assert_ok()


@pytest.mark.parametrize("topo_name", ["pon3", "bcube"])
def test_all_zero_schedule(topo_name):
    p = _problem(topo_name)
    cert = _assert_matches_dense(p, np.zeros(p.shape_x))
    assert cert.residuals["demand"] == pytest.approx(p.coflow.size.max())
    m = timeslot.evaluate(p, np.zeros(p.shape_x))
    assert m.energy_j == 0.0 and m.completion_s == 0.0
    assert not m.active_devices.any()


@pytest.mark.parametrize("topo_name", ["pon3", "fat-tree"])
def test_fully_dense_random_schedule(topo_name):
    p = _problem(topo_name)
    x = np.random.default_rng(3).random(p.shape_x) - 0.25
    assert np.count_nonzero(x) == x.size
    assert not _assert_matches_dense(p, x).ok


def test_empty_coflow():
    topo = topology.build("spine-leaf")
    p = timeslot.ScheduleProblem(
        topo, traffic.custom_coflow([], [], [], topo.n_vertices), n_slots=3)
    x = np.zeros(p.shape_x)
    assert x.shape[0] == 0
    assert _assert_matches_dense(p, x).ok
    m = timeslot.evaluate(p, x)
    assert m.served.shape == (0,) and m.psi.shape == p.shape_x[1:]


def test_accounting_stats_count_calls_entries_and_cells():
    p = _problem("pon3")
    x = _schedule("pon3", "time")
    before = timeslot.accounting_stats().snapshot()
    timeslot.evaluate(p, x)
    verify.check_schedule(p, x)
    timeslot.evaluate(p, np.zeros(p.shape_x))
    after = timeslot.accounting_stats()
    assert after.calls - before.calls == 3
    assert after.entries - before.entries == 2 * np.count_nonzero(x)
    assert after.cells - before.cells == 3 * x.size
    assert 0 < np.count_nonzero(x) < x.size
