"""`path_decompose` peels each flow over its own support and must return
what the whole-fabric peeler returned, bit for bit.

`_path_decompose_dfs` below is the peeler as it stood before: one
`_route_search` over every out-edge of the fabric per path, volumes in a
dense (E, W) scratch.  It is the equivalence baseline; do not optimize it.
"""
import numpy as np
import pytest

from repro.core import failures, solver, timeslot, topology, traffic
from repro.core.solver import FlowPath, _out_edges, _route_search


def _path_decompose_dfs(p, idx, vol):
    F, E, W, _ = p.shape_x
    passive = ~(p.is_server | p.is_switch)
    kf, ke, kw = idx.kf, idx.ke, idx.kw
    out_edges = _out_edges(p)
    convert_ok = ~passive
    bounds = np.searchsorted(kf, np.arange(F + 1))
    k_map = np.full((E, W), -1, dtype=np.int64)
    g = np.zeros((E, W))

    paths = []
    for f in range(F):
        lo, hi = bounds[f], bounds[f + 1]
        es, ws = ke[lo:hi], kw[lo:hi]
        k_map[es, ws] = np.arange(lo, hi)
        vf = vol[lo:hi]
        g[es, ws] = np.where(vf > 1e-9, vf, 0.0)
        src, dst = int(p.coflow.src[f]), int(p.coflow.dst[f])
        budget = float(p.coflow.size[f])
        n_before = len(paths)
        guard = 4 * E * W + 16
        while (budget > 1e-9 and guard > 0
               and g[es, ws].max(initial=0.0) > 1e-9):
            guard -= 1
            path = _route_search(p, out_edges, src, dst,
                                 lambda e, w: g[e, w] > 1e-9, convert_ok)
            if not path:
                break
            pe = np.array([e for e, _ in path], dtype=np.int64)
            pw = np.array([w for _, w in path], dtype=np.int64)
            amt = min(budget, float(g[pe, pw].min()))
            np.subtract.at(g, (pe, pw), amt)
            budget -= amt
            paths.append(FlowPath(f, k_map[pe, pw], amt, int(pw[0])))
        if len(paths) > n_before and budget > 1e-9:
            scale = float(p.coflow.size[f]) / (float(p.coflow.size[f])
                                               - budget)
            for fp in paths[n_before:]:
                fp.volume *= scale
        if len(paths) == n_before:
            path = _route_search(p, out_edges, src, dst,
                                 lambda e, w: k_map[e, w] >= 0, convert_ok)
            if path:
                pe = np.array([e for e, _ in path], dtype=np.int64)
                pw = np.array([w for _, w in path], dtype=np.int64)
                paths.append(FlowPath(f, k_map[pe, pw], budget, int(pw[0])))
        k_map[es, ws] = -1
        g[es, ws] = 0.0
    return paths


def _problem(name, seed=3, total=8.0, n_map=4, n_reduce=3, scen=None):
    t = topology.build(name)
    cf = traffic.shuffle_traffic(t, total, n_map=n_map, n_reduce=n_reduce,
                                 seed=seed)
    p = timeslot.ScheduleProblem(
        t, cf, n_slots=timeslot.suggest_n_slots(t, cf), path_slack=2)
    if scen is not None:
        p = failures.degrade_problem(p, scen(t))
    return p


def _lp_volumes(p, objective, iters):
    lp, idx = solver.build_routing_lp(p, objective)
    res = solver.solve_lp(lp, iters=iters, max_restarts=0)
    return idx, np.maximum(res.x[:len(idx.kf)], 0.0)


def _random_volumes(p, seed, near_gate=False):
    """Volume on all triples but a random eighth, up to a quarter of the
    flow's demand each: cycles, dead ends and detours the LP would not
    leave, so the DFS backtracks over many peels.  Near the gate,
    volumes are multiples of an eighth of the demand plus a few 4e-10:
    peels leave remainders on both sides of the 1e-9 gate."""
    _, idx = solver.build_routing_lp(p, "energy")
    rng = np.random.default_rng(seed)
    K = len(idx.kf)
    vol = rng.uniform(0.0, 2.0, K)
    if near_gate:
        vol = np.round(vol * 4) / 4
    vol = vol * p.coflow.size[idx.kf] / 8
    if near_gate:
        vol = vol + rng.integers(0, 4, K) * 4e-10
    return idx, vol * (rng.random(K) < 0.875)


def _link1(t):
    return failures.sample(t, "link1", 4)


def _leaf0_down(t):
    return failures.fail_device(t, "leaf0")


# case -> (fabric, problem options, volumes, PDHG iterations or the seed
# of random volumes)
CASES = {
    "fat-tree": ("fat-tree", {}, "energy", 1500),
    "fat-tree-10x6": ("fat-tree", {"total": 30.0, "n_map": 10,
                                   "n_reduce": 6}, "energy", 1500),
    "spine-leaf-time": ("spine-leaf", {}, "time", 1500),
    "pon3-energy": ("pon3", {}, "energy", 1500),
    "pon3-time": ("pon3", {"seed": 5}, "time", 1500),
    "bcube": ("bcube", {}, "energy", 1500),
    "dcell": ("dcell", {}, "energy", 1500),
    "spine-leaf-leaf0-down": ("spine-leaf", {"scen": _leaf0_down},
                              "energy", 1500),
    "pon3-link1": ("pon3", {"scen": _link1}, "energy", 1500),
    "fat-tree-coarse": ("fat-tree", {}, "energy", 40),
    "pon3-coarse": ("pon3", {}, "time", 40),
    "fat-tree-random": ("fat-tree", {}, "random", 11),
    "pon3-random": ("pon3", {}, "random", 12),
    "bcube-random": ("bcube", {}, "random", 13),
    "fat-tree-near-gate": ("fat-tree", {}, "near-gate", 14),
    "pon3-near-gate": ("pon3", {}, "near-gate", 15),
}


def _case(case):
    name, kw, volumes, arg = CASES[case]
    p = _problem(name, **kw)
    if volumes in ("random", "near-gate"):
        return _random_volumes(p, arg, volumes == "near-gate") + (p,)
    return _lp_volumes(p, volumes, arg) + (p,)


def _assert_same(got, want):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.flow == b.flow
        assert a.triples.dtype == b.triples.dtype
        np.testing.assert_array_equal(a.triples, b.triples)
        assert a.volume == b.volume
        assert a.tx_wavelength == b.tx_wavelength
        assert type(a.tx_wavelength) is type(b.tx_wavelength)


@pytest.mark.parametrize("case", sorted(CASES))
def test_path_decompose_matches_whole_fabric_peeler(case):
    idx, vol, p = _case(case)
    want = _path_decompose_dfs(p, idx, vol)
    assert want, case
    _assert_same(solver.path_decompose(p, idx, vol), want)


def test_coarse_iterate_takes_the_rescale_branch():
    idx, vol, p = _case("fat-tree-coarse")
    bounds = np.searchsorted(idx.kf, np.arange(p.coflow.n_flows + 1))
    # a flow whose LP volume leaving its source is short of the demand
    # cannot peel it all: its paths are rescaled to the demand
    short = [f for f in range(p.coflow.n_flows)
             if vol[bounds[f]:bounds[f + 1]][
                 p.e_src[idx.ke[bounds[f]:bounds[f + 1]]]
                 == p.coflow.src[f]].sum() < p.coflow.size[f] - 1e-6]
    assert short
    paths = solver.path_decompose(p, idx, vol)
    _assert_same(paths, _path_decompose_dfs(p, idx, vol))
    for f in short:
        got = sum(fp.volume for fp in paths if fp.flow == f)
        assert got == pytest.approx(float(p.coflow.size[f]), rel=1e-12)


@pytest.mark.parametrize("name", ["fat-tree", "pon3"])
def test_flow_without_volume_takes_the_fallback_route(name):
    p = _problem(name)
    idx, vol = _lp_volumes(p, "energy", 1500)
    bounds = np.searchsorted(idx.kf, np.arange(p.coflow.n_flows + 1))
    vol = vol.copy()
    vol[bounds[2]:bounds[3]] = 1e-10        # all of flow 2 under the gate
    paths = solver.path_decompose(p, idx, vol)
    _assert_same(paths, _path_decompose_dfs(p, idx, vol))
    mine = [fp for fp in paths if fp.flow == 2]
    assert len(mine) == 1
    assert mine[0].volume == float(p.coflow.size[2])


def test_decompose_stats_count_paths_and_states():
    idx, vol, p = _case("pon3-energy")
    before = solver.decompose_stats().snapshot()
    paths = solver.path_decompose(p, idx, vol)
    after = solver.decompose_stats()
    assert after.paths - before.paths == len(paths)
    # every path's search expands at least its own hops' tail states
    hops = sum(len(fp.triples) for fp in paths)
    assert after.states - before.states >= hops
