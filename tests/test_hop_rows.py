"""Hop-count rows (timeslot.hop_rows) and the flow-edge mask built from
them: equal to a breadth-first search from every vertex over the alive
edges, bit for bit, on every family and on degraded fabrics; memoized
per vertex; timed by the `problem.mask` and `problem.hops` spans."""
import numpy as np
import pytest

from repro import trace
from repro.core import failures, timeslot, topology, traffic


def _bfs_distances(topo):
    """(V, V) hop counts by one Python BFS per vertex over edges with
    capacity left (the loop the scipy rows replaced)."""
    V = topo.n_vertices
    nbrs = [[] for _ in range(V)]
    alive = topo.cap.sum(axis=1) > 0.0
    for e, (u, v) in enumerate(topo.edges):
        if alive[e]:
            nbrs[int(u)].append(int(v))
    dist = np.full((V, V), np.inf)
    for s in range(V):
        dist[s, s] = 0.0
        frontier, d = [s], 0
        while frontier:
            d += 1
            nxt = []
            for u in frontier:
                for v in nbrs[u]:
                    if dist[s, v] > d:
                        dist[s, v] = d
                        nxt.append(v)
            frontier = nxt
    return dist


def _bfs_mask(p):
    """The flow-edge mask as it was built from the dense matrix."""
    t = p.topo
    src, dst = p.coflow.src, p.coflow.dst
    mask = np.ones((p.coflow.n_flows, t.n_edges), dtype=bool)
    mask &= ~(p.e_dst[None, :] == src[:, None])
    mask &= ~(p.e_src[None, :] == dst[:, None])
    if not t.server_relay:
        u_srv, v_srv = p.is_server[p.e_src], p.is_server[p.e_dst]
        mask &= ~(u_srv[None, :] & (p.e_src[None, :] != src[:, None]))
        mask &= ~(v_srv[None, :] & (p.e_dst[None, :] != dst[:, None]))
    if p.path_slack is not None:
        dist = _bfs_distances(t)
        through = (dist[src][:, p.e_src] + 1
                   + dist[:, dst].T[:, p.e_dst])
        mask &= through <= (dist[src, dst] + p.path_slack)[:, None]
    return mask


def _fabrics():
    out = [(name, topology.build(name)) for name in topology.BUILDERS]
    out.append(("bcube-k2-n3", topology.bcube(n=3, k=2)))
    for name in ("fat-tree", "bcube", "dcell", "pon3"):
        topo = topology.build(name)
        out.append((f"{name}-link3", failures.apply(
            topo, failures.sample(topo, "link3", 5))))
    topo = topology.build("spine-leaf")
    out.append(("spine-leaf-spine0", failures.apply(
        topo, failures.fail_device(topo, "spine0"))))
    return out


FABRICS = _fabrics()


@pytest.mark.parametrize("name,topo", FABRICS, ids=[n for n, _ in FABRICS])
def test_rows_equal_bfs(name, topo):
    dist = _bfs_distances(topo)
    every = range(topo.n_vertices)
    np.testing.assert_array_equal(timeslot.hop_rows(topo, every), dist)
    np.testing.assert_array_equal(timeslot.hop_rows(topo, every, to=True),
                                  dist.T)
    some = [3, 0, 3]
    np.testing.assert_array_equal(timeslot.hop_rows(topo, some), dist[some])
    assert timeslot.hop_rows(topo, []).shape == (0, topo.n_vertices)


@pytest.mark.parametrize("slack", [None, 0, 1, 2])
@pytest.mark.parametrize("name,topo", FABRICS, ids=[n for n, _ in FABRICS])
def test_mask_bit_identical(name, topo, slack):
    cf = traffic.shuffle_traffic(topo, 8.0, n_map=4, n_reduce=3, seed=3)
    p = timeslot.ScheduleProblem(topo, cf, n_slots=4, path_slack=slack)
    assert p.flow_edge_mask.dtype == bool
    np.testing.assert_array_equal(p.flow_edge_mask, _bfs_mask(p))


def test_dead_edges_are_not_walked():
    topo = topology.build("spine-leaf")
    leaf0 = next(i for i, d in enumerate(topo.devices) if d.name == "leaf0")
    srv = next(i for i, d in enumerate(topo.devices) if d.name == "srv0.0")
    cut = failures.apply(topo, failures.fail_device(topo, "leaf0"))
    assert timeslot.hop_rows(topo, [srv])[0, leaf0] == 1
    assert np.isinf(timeslot.hop_rows(cut, [srv])[0, leaf0])


def test_rows_are_memoized_and_timed_on_a_miss():
    topo = topology.bcube(n=3, k=2)
    cf = traffic.shuffle_traffic(topo, 8.0, n_map=4, n_reduce=3, seed=1)
    with trace.recording() as rec:
        timeslot.ScheduleProblem(topo, cf, n_slots=4, path_slack=0)
        first = len(rec.records)
        again = timeslot.ScheduleProblem(topo, cf, n_slots=4, path_slack=0)
    names = [r.name for r in rec.records]
    assert names[:first].count("problem.mask") == 1
    hops = [r for r in rec.records[:first] if r.name == "problem.hops"]
    assert len(hops) == 2                       # forward, then reversed
    assert all(rec.records[h.parent].name == "problem.mask" for h in hops)
    assert names[first:] == ["problem.mask"]    # every row was cached
    assert again.flow_edge_mask.any()


def test_mask_span_only_inside_a_recording(monkeypatch):
    import jax

    made = []
    real = jax.profiler.TraceAnnotation
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name, **kw: made.append(name) or real(name))
    topo = topology.build("bcube")
    cf = traffic.shuffle_traffic(topo, 8.0, n_map=4, n_reduce=3, seed=1)
    timeslot.ScheduleProblem(topo, cf, n_slots=4, path_slack=0)
    assert made == [] and trace.active() is None
    with trace.recording() as rec:
        timeslot.ScheduleProblem(topo, cf, n_slots=4)
    assert [r.name for r in rec.records] == made == ["problem.mask"]
    assert rec.seconds()["problem.mask"][0] > 0
