"""The BCube_k(n) configuration and its "digits" mix, without a chip:
the fabric against its closed forms and the reference's fingerprint,
draws that are their template up to a symmetry, a small digits cell
whose check passes a sound run and refuses the control and an altered
schedule, and the `mask_ms.digits` reader."""
import dataclasses
import json
import math
import pathlib
import sys
import time

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import calibrate  # noqa: E402
import harness  # noqa: E402

DATA = BENCH / "testdata" / "bcube"
SEED = 2 ** 31 + 11


def _bcube_k1(n):
    """BCube_1(n) as `topology.bcube` built it before it took k."""
    from repro.core import topology as T

    b = T._Builder(f"bcube-n{n}")
    servers = [[b.add(f"srv{g}.{i}", T.KIND_SERVER, T.P_NIC, T.EPS_NIC)
                for i in range(n)] for g in range(n)]
    lvl0 = [b.add(f"sw0.{g}", T.KIND_SWITCH, T.O_SG500) for g in range(n)]
    lvl1 = [b.add(f"sw1.{i}", T.KIND_SWITCH, T.O_SG500) for i in range(n)]
    for g in range(n):
        for i in range(n):
            b.link(servers[g][i], lvl0[g], T._grey())
            b.link(servers[g][i], lvl1[i], T._grey())
    sigma = {s: n * T.LINK_GBPS for s in lvl0 + lvl1}
    return b.build(n_wavelengths=1, slot_duration=1.0, switch_sigma=sigma)


@pytest.mark.parametrize("n", [2, 4, 5])
def test_k1_is_the_fabric_it_was(n):
    from repro.core import topology

    old = _bcube_k1(n)
    new = topology.build("bcube", **({} if n == 4 else {"n": n}))
    assert new.name == old.name and new.devices == old.devices
    for a in ("edges", "cap"):
        got, want = getattr(new, a), getattr(old, a)
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)
    assert list(new.switch_sigma.items()) == list(old.switch_sigma.items())
    assert new.task_servers == old.task_servers
    assert (new.server_relay, new.n_wavelengths, new.slot_duration) == \
        (old.server_relay, old.n_wavelengths, old.slot_duration)


@pytest.mark.parametrize("n,k", [(4, 1), (2, 2), (3, 2), (2, 3), (8, 3)])
def test_closed_forms_and_fingerprints(n, k):
    from repro.core import topology

    t = topology.build("bcube", n=n, k=k)
    servers, switches = n ** (k + 1), (k + 1) * n ** k
    assert len(t.servers) == servers and len(t.switches) == switches
    assert t.servers == list(range(servers))
    assert t.n_edges == 2 * (k + 1) * servers
    assert t.static_power() == pytest.approx(
        servers * math.ceil((k + 1) / 2) * 14.0 + switches * 94.33)
    assert {d.eps for d in t.devices if d.kind == "server"} == {14.29}
    assert set(t.switch_sigma) == set(t.switches)
    assert set(t.switch_sigma.values()) == {n * 10.0}
    t.validate()
    deg = np.bincount(t.edges[:, 0], minlength=t.n_vertices)
    assert (deg[:servers] == k + 1).all() and (deg[servers:] == n).all()
    # a level-l switch joins the n servers that differ only in digit l
    digits = (np.arange(servers)[:, None] // n ** np.arange(k + 1)) % n
    up = t.edges[t.edges[:, 0] < servers]
    for level in range(k + 1):
        lo = servers + level * n ** k
        sel = (up[:, 1] >= lo) & (up[:, 1] < lo + n ** k)
        srv, sw = up[sel, 0], up[sel, 1]
        assert len(np.unique(srv)) == servers
        others = np.delete(digits[srv], level, axis=1)
        for s in np.unique(sw):
            assert len(np.unique(others[sw == s], axis=0)) == 1
            assert sorted(digits[srv[sw == s], level]) == list(range(n))
    fabric = harness.load_module(BENCH / "fabrics" / "bcube.py").build(n=n,
                                                                        k=k)
    assert fabric.fingerprint() == harness.fabric_of(t).fingerprint()


def test_config_fingerprint_and_deck_from():
    """The configuration's fingerprint is the reference's, and the mix's
    deck is what its `deck_from` says."""
    cfg = harness.load_json(BENCH / "configs" / "bcube-k3-n8.json")
    mix = harness.load_json(BENCH / "mixes" / "sweep-energy-digits.json")
    fabric = harness.load_module(BENCH / "fabrics" / "bcube.py").build(
        **cfg["fabric"]["kwargs"])
    assert fabric.fingerprint() == cfg["fingerprint"]
    n_map = cfg["shuffle"]["n_map"]
    n_tasks = n_map + cfg["shuffle"]["n_reduce"]
    radix = mix["radix"]
    for i, template in enumerate(mix["deck"]):
        servers = np.random.default_rng([2009, i]).permutation(
            fabric.task_servers)[:n_tasks]
        addr = np.array(np.unravel_index(servers, radix)).T   # a_k first
        labels = [[] for _ in radix]
        pattern = [[labels[d].index(a) if a in labels[d]
                    else labels[d].append(a) or len(labels[d]) - 1
                    for d, a in enumerate(row)] for row in addr]
        assert template == {"map": pattern[:n_map],
                            "reduce": pattern[n_map:], "count": 1}


def _degrees(lp):
    return (np.sort(np.bincount(lp.row, minlength=lp.m)),
            np.sort(np.bincount(lp.col, minlength=lp.n)))


def test_draws_are_their_template_up_to_a_symmetry():
    driver, _, _ = harness.prepare("bcube3-sweep", SEED)
    seen = {}
    for key in range(5):
        driver.seed = SEED + 7 ** key
        for (p, rp), (j, _) in zip(driver.problems(1, key),
                                   driver.order(1, key)):
            servers = np.concatenate([rp.src[::6], rp.dst[:6]])
            assert len(set(servers.tolist())) == 16
            assert (rp.src == p.coflow.src).all()
            lp, _ = driver.solver.build_routing_lp(p, driver.objective)
            seen.setdefault(j, []).append(
                ((lp.n, lp.m_eq, lp.m, len(lp.val)), _degrees(lp),
                 tuple(servers)))
    assert sorted(seen) == [0, 1, 2, 3]
    for draws in seen.values():
        (shape, (rows, cols), _) = draws[0]
        for other, (r, c), _ in draws[1:]:
            assert other == shape
            np.testing.assert_array_equal(r, rows)
            np.testing.assert_array_equal(c, cols)
        assert len({servers for *_, servers in draws}) == len(draws)


@pytest.fixture
def cell(monkeypatch):
    monkeypatch.setattr(harness, "SPEC", DATA / "BENCHMARK.json")
    monkeypatch.setattr(harness, "DATA", DATA)
    return "bcube2-sweep"


def test_digits_cell_sound_run_is_correct(cell):
    line = harness.run(cell, SEED, 0.5, False, time.perf_counter())
    assert line["correct"] and line["attempted"] > 0 and not line["failed"]
    assert line["metrics"]["schedules_per_s"]["value"] > 0
    assert list(line)[-1] == "checks"
    traced = harness.run(cell, SEED + 1, 0.5, True, time.perf_counter())
    assert traced["correct"]
    assert traced["metrics"]["mask_ms.digits"]["value"] > 0


def test_digits_cell_control_fails(cell):
    driver, limits, _ = harness.prepare(cell, SEED)
    driver.warm_up()
    for r in calibrate.readings(driver, [SEED, 7]):
        prog, ctrl = r["program"], r["control"]
        assert all(prog[k] <= limits[k]["limit"] for k in limits), prog
        for k in ("lp_gap", "pdhg_obj_gap", "metric_gap"):
            assert ctrl[k] > limits[k]["limit"], (k, ctrl)


def test_digits_cell_catches_an_altered_schedule(cell, monkeypatch):
    from repro.core import solver

    orig = solver._assemble_fast_result

    def altered(*args, **kwargs):
        r = orig(*args, **kwargs)
        r.schedule = r.schedule.copy()
        r.schedule[0] *= 1.01
        return r

    monkeypatch.setattr(solver, "_assemble_fast_result", altered)
    line = harness.run(cell, SEED, 0.5, False, time.perf_counter())
    assert not line["correct"]
    c = line["checks"]["metric_gap"]
    assert c["value"] > c["limit"]


def test_mask_reader_reads_nothing_without_program_spans():
    read = harness.load_module(BENCH / "metrics" / "mask_ms.digits.py").read
    obs = {"schedules": [None] * 4, "spans": {}}
    assert read(obs) is None
    assert read(dict(obs, program_spans={"lp.build": [0.1]})) is None
    assert read(dict(obs, program_spans={"problem.mask": [0.002] * 4})) == \
        pytest.approx(2.0)
    assert read({"schedules": [], "program_spans": {}}) is None
