"""Property-based solver-stack invariants (plus the degenerate cases
they surfaced).

Each property is a plain checker over an RNG so it runs in two modes:

  * a seeded deterministic sweep (always on — the tier-1 suite must
    exercise these without optional deps);
  * a hypothesis-driven sweep over the same checkers when hypothesis is
    installed (requirements-dev.txt; CI runs it).

Invariants pinned here:

  1. the blocked-ELL pack (one shard) and its operator pair equal the
     dense matvec (both gather directions) on random sparsity patterns;
  2. path_decompose conserves per-flow volume exactly — decomposed
     path volumes per flow sum to the flow's demand;
  3. evaluate's aggregate metrics are invariant under a flow-order
     permutation of the CoflowSet (and `served` permutes with it);
  4. a zero-flow CoflowSet (an empty arrival epoch) flows through
     build_routing_lp / solve_fast / evaluate as empty-but-valid
     results instead of raising, under both operators;
  5. metamorphic policy/LP relations: scaling demands by k scales the
     min-time functional exactly k and leaves ECMP routing invariant;
     zeroing one flow never pushes the others' finishes later under
     the strict-priority packer; the "fair" LP with uniform weights is
     the energy LP (bitwise arrays, matching schedules).
"""
import numpy as np
import pytest

from repro.core import policies, solver, timeslot, topology, traffic
from repro.kernels import pdhg_spmv

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # pragma: no cover - dev extra
    HAVE_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS,
    reason="hypothesis sweeps need hypothesis (requirements-dev.txt)")

TOPOS = ("spine-leaf", "pon3")


# ---------------------------------------------------------------------------
# property checkers (seed -> assertions)
# ---------------------------------------------------------------------------

def check_ell_spmv_matches_dense(seed: int) -> None:
    rng = np.random.default_rng(seed)
    m = int(rng.integers(1, 50))
    n = int(rng.integers(1, 40))
    nnz = int(rng.integers(0, m * n + 1))
    flat = rng.choice(m * n, size=nnz, replace=False)
    row, col = flat // n, flat % n
    val = rng.normal(size=nnz)
    op = pdhg_spmv.ell_pack_sharded(row, col, val, m, n, shards=1)
    Kx, KTy = pdhg_spmv.ell_pair(op.row_idx, op.row_val, op.col_idx,
                                 op.col_val, op.row_meta, op.col_meta)
    K = np.zeros((m, n), np.float32)
    np.add.at(K, (row, col), val.astype(np.float32))
    x = rng.normal(size=n).astype(np.float32)
    y = rng.normal(size=m).astype(np.float32)
    kx = np.asarray(Kx(np.pad(x, (0, op.n_pad - n))))
    kty = np.asarray(KTy(np.pad(y, (0, op.m_pad - m))))
    np.testing.assert_allclose(kx[:m], K @ x, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(kty[:n], K.T @ y, atol=1e-4, rtol=1e-4)
    assert np.all(kx[m:] == 0.0) and np.all(kty[n:] == 0.0)


def _random_problem(rng: np.random.Generator,
                    topo_name: str) -> timeslot.ScheduleProblem:
    topo = topology.build(topo_name)
    pat = traffic.TrafficPattern(
        "prop", placement=str(rng.choice(traffic.PLACEMENTS)),
        skew=str(rng.choice(traffic.SKEWS)),
        n_map=int(rng.integers(2, 5)), n_reduce=int(rng.integers(2, 4)),
        total_gbits=float(rng.uniform(2.0, 10.0)))
    cf = traffic.generate(topo, pat, int(rng.integers(0, 2**31 - 1)))
    return timeslot.ScheduleProblem(
        topo, cf, n_slots=timeslot.suggest_n_slots(topo, cf), path_slack=2)


def check_path_decompose_conserves_volume(seed: int) -> None:
    rng = np.random.default_rng(seed)
    p = _random_problem(rng, str(rng.choice(TOPOS)))
    lp, idx = solver.build_routing_lp(p, "energy")
    res = solver.solve_lp(lp, iters=400, max_restarts=0)   # coarse on purpose
    K = len(idx.kf)
    paths = solver.path_decompose(p, idx, np.maximum(res.x[:K], 0.0))
    by_flow = np.zeros(p.coflow.n_flows)
    for fp in paths:
        assert fp.volume > 0.0
        # every path is a src->dst chain of admissible triples
        e = idx.ke[fp.triples]
        assert int(p.e_src[e[0]]) == int(p.coflow.src[fp.flow])
        assert int(p.e_dst[e[-1]]) == int(p.coflow.dst[fp.flow])
        np.testing.assert_array_equal(p.e_dst[e[:-1]], p.e_src[e[1:]])
        by_flow[fp.flow] += fp.volume
    # exact conservation: decomposition re-assigns the full demand even
    # from a sloppy LP iterate (healthy topology => a route exists)
    np.testing.assert_allclose(by_flow, p.coflow.size, atol=1e-6)


def check_evaluate_permutation_invariant(seed: int) -> None:
    rng = np.random.default_rng(seed)
    p = _random_problem(rng, str(rng.choice(TOPOS)))
    F, E, W, T = p.shape_x
    # arbitrary (not necessarily feasible) schedule: evaluate must score
    # the permuted instance identically, violations included
    x = np.where(rng.random((F, E, W, T)) < 0.1,
                 rng.uniform(0.0, 2.0, (F, E, W, T)), 0.0)
    m0 = timeslot.evaluate(p, x)
    perm = rng.permutation(F)
    cfp = traffic.CoflowSet(p.coflow.src[perm], p.coflow.dst[perm],
                            p.coflow.size[perm], p.coflow.n_vertices)
    pp = timeslot.ScheduleProblem(p.topo, cfp, n_slots=T, rho=p.rho,
                                  path_slack=p.path_slack)
    m1 = timeslot.evaluate(pp, x[perm])
    assert np.isclose(m0.energy_j, m1.energy_j, rtol=1e-9)
    assert np.isclose(m0.completion_s, m1.completion_s, rtol=1e-9)
    assert np.isclose(m0.fairness_term, m1.fairness_term, rtol=1e-9)
    assert np.isclose(m0.max_violation, m1.max_violation, rtol=1e-9,
                      atol=1e-12)
    assert m0.feasible == m1.feasible
    np.testing.assert_allclose(m0.served[perm], m1.served, rtol=1e-9,
                               atol=1e-12)
    np.testing.assert_allclose(m0.psi, m1.psi, rtol=1e-9, atol=1e-12)


def _finish_slots(x: np.ndarray) -> np.ndarray:
    """Per flow: last slot with positive shipped volume (-1 if none)."""
    ship = x.sum(axis=(1, 2))                       # (F, T)
    out = np.full(ship.shape[0], -1)
    for f in range(ship.shape[0]):
        nz = np.flatnonzero(ship[f] > 1e-9)
        if nz.size:
            out[f] = int(nz[-1])
    return out


def check_demand_scaling(seed: int) -> None:
    """Scaling every demand by k: ECMP's routing is invariant (route
    choice is demand-oblivious), the min-time LP functional of the
    packed schedule scales EXACTLY k (volumes scale k along identical
    routes), and the slot-quantized completion grows by at most ~k."""
    rng = np.random.default_rng(seed)
    k = float(rng.uniform(2.0, 4.0))
    p = _random_problem(rng, str(rng.choice(TOPOS)))
    cf = p.coflow
    cfk = traffic.CoflowSet(cf.src, cf.dst, cf.size * k, cf.n_vertices)
    pk = timeslot.ScheduleProblem(
        p.topo, cfk, n_slots=timeslot.suggest_n_slots(p.topo, cfk),
        path_slack=p.path_slack)
    pol = policies.get("ecmp")
    _, paths = pol.route(p, "time")
    _, paths_k = pol.route(pk, "time")
    assert ([fp.triples.tolist() for fp in paths]
            == [fp.triples.tolist() for fp in paths_k])
    r, rk = pol.solve(p, "time"), pol.solve(pk, "time")
    assert r.remaining_gbits <= 1e-6 and rk.remaining_gbits <= 1e-6
    np.testing.assert_allclose(
        policies.lp_cost(pk, "time", rk.schedule),
        k * policies.lp_cost(p, "time", r.schedule), rtol=1e-9)
    D = p.topo.slot_duration
    assert rk.metrics.completion_s >= r.metrics.completion_s - 1e-9
    assert rk.metrics.completion_s \
        <= k * r.metrics.completion_s + 2.0 * D + 1e-9


def check_zero_flow_monotone(seed: int) -> None:
    """Zeroing one flow's demand never pushes any other flow's finish
    slot later under the strict-priority packer (freed capacity only
    helps; the priority order of the survivors is unchanged)."""
    rng = np.random.default_rng(seed)
    p = _random_problem(rng, str(rng.choice(TOPOS)))
    pol = policies.get("scf")
    f0 = _finish_slots(pol.solve(p, "time").schedule)
    j = int(rng.integers(p.coflow.n_flows))
    size2 = p.coflow.size.copy()
    size2[j] = 0.0
    cf2 = traffic.CoflowSet(p.coflow.src, p.coflow.dst, size2,
                            p.coflow.n_vertices)
    p2 = timeslot.ScheduleProblem(p.topo, cf2, n_slots=p.n_slots,
                                  path_slack=p.path_slack)
    f2 = _finish_slots(pol.solve(p2, "time").schedule)
    others = np.arange(p.coflow.n_flows) != j
    assert np.all(f2[others] <= f0[others]), \
        (j, f0.tolist(), f2.tolist())


def check_fair_lp_matches_energy(seed: int, *, solve: bool = False) -> None:
    """The weighted max-min fairness LP degenerates to the energy LP:
    with flow_weight=None the assembled arrays are bitwise identical;
    with a uniform weight w only the triple-cost coordinates scale by
    1/w (which cscale normalization erases — the schedules match)."""
    rng = np.random.default_rng(seed)
    p = _random_problem(rng, str(rng.choice(TOPOS)))
    lp_e, idx = solver.build_routing_lp(p, "energy")
    lp_f, _ = solver.build_routing_lp(p, "fair")      # weights None
    for attr in ("c", "row", "col", "val", "b", "h"):
        np.testing.assert_array_equal(getattr(lp_e, attr),
                                      getattr(lp_f, attr), err_msg=attr)
    w = float(rng.uniform(0.5, 4.0))
    pw = timeslot.ScheduleProblem(
        p.topo, p.coflow, n_slots=p.n_slots, path_slack=p.path_slack,
        flow_weight=np.full(p.coflow.n_flows, w))
    lp_w, _ = solver.build_routing_lp(pw, "fair")
    K = len(idx.kf)
    np.testing.assert_allclose(lp_w.c[:K], lp_e.c[:K] / w, rtol=1e-12)
    np.testing.assert_array_equal(lp_w.c[K:], lp_e.c[K:])
    if solve:
        r_f = solver.solve_fast(pw, "fair", iters=800)
        r_e = solver.solve_fast(p, "energy", iters=800)
        np.testing.assert_allclose(r_f.schedule, r_e.schedule,
                                   rtol=1e-7, atol=1e-9)


# ---------------------------------------------------------------------------
# seeded deterministic sweeps (always run)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(8))
def test_ell_spmv_matches_dense(seed):
    check_ell_spmv_matches_dense(seed)


@pytest.mark.parametrize("seed", range(4))
def test_path_decompose_conserves_volume(seed):
    check_path_decompose_conserves_volume(seed)


@pytest.mark.parametrize("seed", range(4))
def test_evaluate_permutation_invariant(seed):
    check_evaluate_permutation_invariant(seed)


@pytest.mark.parametrize("seed", range(4))
def test_demand_scaling_metamorphic(seed):
    check_demand_scaling(seed)


@pytest.mark.parametrize("seed", range(4))
def test_zero_flow_monotone_metamorphic(seed):
    check_zero_flow_monotone(seed)


@pytest.mark.parametrize("seed", range(2))
def test_fair_lp_matches_energy(seed):
    check_fair_lp_matches_energy(seed, solve=(seed == 0))


# ---------------------------------------------------------------------------
# hypothesis sweeps (same checkers, fuzzed seeds)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    seeds = st.integers(min_value=0, max_value=2**31 - 1)

    @needs_hypothesis
    @settings(max_examples=25, deadline=None)
    @given(seed=seeds)
    def test_ell_spmv_matches_dense_hyp(seed):
        check_ell_spmv_matches_dense(seed)

    @needs_hypothesis
    @settings(max_examples=8, deadline=None)
    @given(seed=seeds)
    def test_path_decompose_conserves_volume_hyp(seed):
        check_path_decompose_conserves_volume(seed)

    @needs_hypothesis
    @settings(max_examples=8, deadline=None)
    @given(seed=seeds)
    def test_evaluate_permutation_invariant_hyp(seed):
        check_evaluate_permutation_invariant(seed)

    @needs_hypothesis
    @settings(max_examples=6, deadline=None)
    @given(seed=seeds)
    def test_demand_scaling_metamorphic_hyp(seed):
        check_demand_scaling(seed)

    @needs_hypothesis
    @settings(max_examples=6, deadline=None)
    @given(seed=seeds)
    def test_fair_lp_matches_energy_hyp(seed):
        check_fair_lp_matches_energy(seed)


# ---------------------------------------------------------------------------
# degenerate instances the property sweeps surfaced: zero-flow co-flows
# (empty arrival epochs) must produce empty-but-valid results
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("objective", ["energy", "time"])
def test_zero_flow_coflow_solves(objective, operator):
    topo = topology.build("spine-leaf")
    cf = traffic.empty_coflow(topo.n_vertices)
    p = timeslot.ScheduleProblem(
        topo, cf, n_slots=timeslot.suggest_n_slots(topo, cf))
    lp, idx = solver.build_routing_lp(p, objective)
    assert len(idx.kf) == 0 and lp.m == 0
    r = solver.solve_fast(p, objective)
    assert r.schedule.shape == p.shape_x
    assert r.schedule.size == 0 and r.remaining_gbits == 0.0
    m = r.metrics
    assert m.feasible and m.energy_j == 0.0 and m.completion_s == 0.0


def test_zero_flow_coflow_evaluate_and_batch():
    topo = topology.build("spine-leaf")
    cf = traffic.empty_coflow(topo.n_vertices)
    p = timeslot.ScheduleProblem(topo, cf, n_slots=2)
    m = timeslot.evaluate(p, np.zeros(p.shape_x))
    assert m.feasible and m.served.shape == (0,)
    # an empty member must not poison a stacked batch
    p_real = _random_problem(np.random.default_rng(0), "spine-leaf")
    res = solver.solve_fast_batch([p, p], "energy")
    assert all(r.metrics.feasible for r in res)
    mixed = solver.solve_fast_ensemble([p_real, p], "energy", iters=2000)
    assert mixed[1].metrics.energy_j == 0.0
    assert mixed[0].metrics.feasible
