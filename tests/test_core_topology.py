"""Topology graphs match the paper's Table II / Figs. 4-5."""
import numpy as np
import pytest

from repro.core import topology


@pytest.mark.parametrize("name,servers,switches,links,static_w", [
    ("fat-tree", 16, 20, 48, 20 * 94.33 + 16 * 1.0),
    ("spine-leaf", 16, 6, 24, 6 * 193.0 + 16 * 1.0),
    ("bcube", 16, 8, 32, 8 * 94.33 + 16 * 14.0),
    ("dcell", 20, 5, 30, 5 * 94.33 + 20 * 14.0),
    ("pon5", 16, 5, 26, 217.0 + 4 * 12.0 + 16 * 14.0),
])
def test_counts_and_power(name, servers, switches, links, static_w):
    t = topology.build(name)
    assert len(t.servers) == servers
    assert len(t.switches) == switches
    assert t.n_edges == 2 * links            # directed
    assert t.static_power() == pytest.approx(static_w, rel=1e-6)
    t.validate()


def test_dcell_tasks_on_16_of_20():
    t = topology.build("dcell")
    assert len(t.task_servers) == 16
    assert len(t.servers) == 20


def test_pon3_structure():
    t = topology.build("pon3")
    assert len(t.servers) == 16
    assert t.n_wavelengths == 4
    assert t.slot_duration == 0.25
    assert not t.server_relay            # eq. (46)
    assert t.one_wavelength_tx           # eq. (47)
    assert t.static_power() == pytest.approx(217 + 4 * 12 + 16 * 2.0)
    # every ordered (rack/OLT) pair has exactly one wavelength-routed path
    lam = topology.TABLE_I_LAMBDA
    for i in range(5):
        row = [lam[i, j] for j in range(5) if j != i]
        col = [lam[j, i] for j in range(5) if j != i]
        assert sorted(row) == [0, 1, 2, 3]   # eq. (5): distinct per source
        assert sorted(col) == [0, 1, 2, 3]   # eq. (4): distinct per dest


def test_all_topologies_have_connected_task_servers():
    for name in topology.BUILDERS:
        t = topology.build(name)
        # BFS over undirected reachability from first task server
        adj = {}
        for u, v in t.edges:
            adj.setdefault(int(u), set()).add(int(v))
        seen, stack = set(), [t.task_servers[0]]
        while stack:
            u = stack.pop()
            if u in seen:
                continue
            seen.add(u)
            stack.extend(adj.get(u, ()))
        assert set(t.task_servers) <= seen, name


# ---------------------------------------------------------------------------
# Parameterized large-instance generators (scale fabric)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [4, 8, 16])
def test_fat_tree_closed_forms(k):
    t = topology.fat_tree(k)
    assert len(t.servers) == k ** 3 // 4
    assert len(t.switches) == 5 * k * k // 4
    assert t.n_edges == 2 * (3 * k ** 3 // 4)       # directed = 2x bidir
    # per-layer degree structure: every edge switch has k/2 agg uplinks
    # and k/2 server downlinks; agg<->core links equal the server count
    deg = np.zeros(t.n_vertices, int)
    for u, _ in t.edges:
        deg[u] += 1
    for s in t.servers:
        assert deg[s] == 1
    names = [d.name for d in t.devices]
    agg_core = sum(1 for (u, v) in t.edges
                   if names[u].startswith("agg") and names[v].startswith("core"))
    assert agg_core == k ** 3 // 4                  # one direction counted
    t.validate()


def _dcell_servers(n: int, levels: int) -> int:
    t = n
    for _ in range(levels):
        t = (t + 1) * t
    return t


@pytest.mark.parametrize("n,levels", [(2, 1), (2, 2), (3, 2), (2, 3)])
def test_dcell_multi_recursion(n, levels):
    t = topology.dcell_multi(n, levels)
    tl = _dcell_servers(n, levels)
    assert len(t.servers) == tl
    assert len(t.switches) == tl // n               # one per DCell_0
    # t_l server<->switch links plus t_l/2 pairing links per level
    assert t.n_edges == tl * (2 + levels)           # directed edges
    deg = np.zeros(t.n_vertices, int)
    for u, _ in t.edges:
        deg[u] += 1
    for s in t.servers:
        assert deg[s] == levels + 1                 # switch + one per level
    assert t.task_servers == t.servers              # all servers eligible
    t.validate()


def test_dcell_multi_level1_matches_closed_form_count():
    # DCell_1(4) has the paper instance's structure: 20 servers, 5 switches
    t = topology.dcell_multi(4, 1)
    assert len(t.servers) == 20
    assert len(t.switches) == 5
    assert t.n_edges == 2 * 30


def test_dcell_multi_rejects_zero_levels():
    with pytest.raises(ValueError):
        topology.dcell_multi(2, 0)


@pytest.mark.parametrize("G", [3, 5, 8, 17])
def test_awgr_lambda_latin_square(G):
    lam = topology.awgr_lambda(G)
    assert lam.shape == (G, G)
    assert all(lam[i, i] == -1 for i in range(G))
    for i in range(G):
        row = sorted(lam[i, j] for j in range(G) if j != i)
        col = sorted(lam[j, i] for j in range(G) if j != i)
        assert row == list(range(G - 1))            # eq. (5) per source
        assert col == list(range(G - 1))            # eq. (4) per dest


@pytest.mark.parametrize("n_cells,n_racks,spr", [(1, 4, 4), (2, 4, 4),
                                                 (2, 2, 2), (3, 3, 2)])
def test_pon_multicell_counts(n_cells, n_racks, spr):
    t = topology.pon_multicell(n_cells, n_racks, spr)
    G = n_racks + 1
    assert len(t.servers) == n_cells * n_racks * spr
    # hub + per cell: OLT card + racks*(backplane + 2 AWGR ports + servers)
    # + the card's own AWGR port pair
    assert t.n_vertices == 1 + n_cells * (1 + n_racks * (3 + spr) + 2)
    e_cell = 2 + n_racks * spr * 4 + 2 + G * (G - 1)
    assert t.n_edges == n_cells * e_cell
    assert t.n_wavelengths == n_racks               # G-1 wavelengths
    assert len(t.awgr_in_ports) == n_cells * G
    assert not t.server_relay and t.one_wavelength_tx
    assert t.task_servers == t.servers


def test_pon_multicell_single_cell_matches_pon3_shape():
    multi = topology.pon_multicell(1, 4, 4)
    single = topology.pon3()
    assert len(multi.servers) == len(single.servers)
    assert multi.n_wavelengths == single.n_wavelengths
    # the multi-cell adds the hub and its WDM trunk over pon3
    assert multi.n_vertices == single.n_vertices + 1
    assert multi.n_edges == single.n_edges + 2


@pytest.mark.parametrize("name,kw", [
    ("dcell-multi", dict(n=2, levels=2)),
    ("pon-multicell", dict(n_cells=2, n_racks=2, servers_per_rack=2)),
    ("pon-cascaded", dict(n_cells=2, n_racks=2, servers_per_rack=2)),
])
def test_new_families_solve_and_certify(name, kw, operator):
    from repro.core import solver, timeslot, traffic, verify

    topo = topology.BUILDERS[name](**kw)
    pat = traffic.pattern("uniform", n_map=4, n_reduce=3)
    cf = traffic.generate(topo, pat, seed=0)
    p = timeslot.ScheduleProblem(topo, cf,
                                 n_slots=timeslot.suggest_n_slots(topo, cf))
    r = solver.solve_fast(p, "energy")
    cert = verify.check_schedule(p, r.schedule)
    assert cert.ok, cert
    assert r.metrics.feasible
    assert r.remaining_gbits < 1e-6


@pytest.mark.parametrize("n_cells,n_racks,spr", [(2, 4, 2), (2, 2, 2),
                                                 (3, 3, 2), (4, 2, 1)])
def test_pon_cascaded_counts(n_cells, n_racks, spr):
    t = topology.pon_cascaded(n_cells, n_racks, spr)
    G = n_racks + 1
    assert len(t.servers) == n_cells * n_racks * spr
    # per cell: OLT card + racks*(backplane + 2 AWGR ports + servers)
    # + the card's stage-1 port pair + its cascade port pair
    assert t.n_vertices == n_cells * (1 + n_racks * (3 + spr) + 2 + 2)
    e_cell = n_racks * spr * 4 + 2 + G * (G - 1) + 2
    assert t.n_edges == n_cells * e_cell + n_cells * (n_cells - 1)
    assert t.n_wavelengths == max(n_racks, n_cells - 1)
    # every stage-1 ingress plus one cascade ingress per cell
    assert len(t.awgr_in_ports) == n_cells * (G + 1)
    assert not t.server_relay and t.one_wavelength_tx
    assert t.task_servers == t.servers
    # both passive stages are zero-power: only OLT cards + backplanes bill
    import numpy as np
    passive = [d for d in t.devices if d.kind == topology.KIND_PASSIVE]
    assert all(d.p_max == 0.0 for d in passive)
    assert t.static_power() == n_cells * (
        topology.O_OLT + n_racks * topology.O_BACKPLANE
        + n_racks * spr * topology.P_TUNABLE)


def test_pon_cascaded_stage2_is_latin_square():
    import numpy as np
    n_cells = 3
    t = topology.pon_cascaded(n_cells, 2, 1)
    names = [d.name for d in t.devices]
    cin = [names.index(f"cas_in{c}") for c in range(n_cells)]
    cout = [names.index(f"cas_out{c}") for c in range(n_cells)]
    lam2 = topology.awgr_lambda(n_cells)
    seen = {}
    for e, (u, v) in enumerate(t.edges):
        if int(u) in cin and int(v) in cout:
            c, c2 = cin.index(int(u)), cout.index(int(v))
            ws = np.flatnonzero(t.cap[e] > 0)
            # each stage-2 path carries exactly the cyclic-table wavelength
            assert ws.tolist() == [int(lam2[c, c2])]
            assert t.cap[e, ws[0]] == topology.LINK_GBPS
            seen[(c, c2)] = int(lam2[c, c2])
    assert len(seen) == n_cells * (n_cells - 1)


def test_pon_cascaded_requires_two_cells():
    with pytest.raises(ValueError):
        topology.pon_cascaded(1)


def test_pon_cascaded_cross_cell_routable():
    """Every server pair — including cross-cell through both passive
    AWGR stages — must have an admissible wavelength-continuous route."""
    import numpy as np
    from repro.core import failures, timeslot, traffic

    t = topology.pon_cascaded(2, 2, 2)
    srvs = t.servers
    src, dst = zip(*[(a, b) for a in srvs for b in srvs if a != b])
    cf = traffic.CoflowSet(np.array(src), np.array(dst),
                           np.ones(len(src)), t.n_vertices)
    p = timeslot.ScheduleProblem(t, cf,
                                 n_slots=timeslot.suggest_n_slots(t, cf))
    assert failures.routable_flows(p).all()
