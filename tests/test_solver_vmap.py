"""The PDHG fast path vmaps across traffic instances (DESIGN §3 claim):
one jit, N shuffle volumes solved in a single batched run — the property
that lets the online scheduler amortize planning across concurrent jobs."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import solver, timeslot, topology, traffic


def test_vmap_over_demand_vectors():
    topo = topology.build("spine-leaf")
    cf = traffic.shuffle_traffic(topo, 8.0, n_map=4, n_reduce=3, seed=1)
    prob = timeslot.ScheduleProblem(topo, cf, n_slots=3, rho=8.0)
    lp, idx = solver.build_routing_lp(prob, "time")

    # scale the demand rows (flow sizes) across instances; structure fixed
    scales = jnp.array([0.25, 0.5, 1.0])   # <=1: xmax is built for the base volume
    F = cf.n_flows
    demand_rows = lp.b[-F:]

    def solve_one(scale):
        b = jnp.asarray(lp.b).at[-F:].set(jnp.asarray(demand_rows) * scale)
        xmax = jnp.asarray(np.where(np.isfinite(lp.xmax), lp.xmax, 1e12))
        x, _, primal, _ = solver._pdhg_resume(
            jnp.asarray(lp.c / max(abs(lp.c).max(), 1e-12)),
            jnp.asarray(lp.row), jnp.asarray(lp.col), jnp.asarray(lp.val),
            b, jnp.asarray(lp.h), xmax, jnp.zeros(lp.n), jnp.zeros(lp.m),
            lp.m, lp.n, lp.m_eq, 3000)
        return x[-1], primal                     # theta, residual

    thetas, primals = jax.vmap(solve_one)(scales)
    assert np.all(np.asarray(primals) < 1e-2)
    # completion-time LP bound scales ~linearly with volume
    t = np.asarray(thetas)
    assert t[0] < t[1] < t[2]
    np.testing.assert_allclose(t[2] / t[1], 2.0, rtol=0.15)
    np.testing.assert_allclose(t[1] / t[0], 2.0, rtol=0.2)
