"""The benchmark's yardstick, checked without a chip: trace reduction,
roofline bytes, fabric fingerprints, that every cell's files are found
by name, and that a run without a TPU prints no result."""
import json
import math
import os
import pathlib
import re
import subprocess
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402
import harness  # noqa: E402
import reference as ref  # noqa: E402
import roofline  # noqa: E402

TPU = "/device:TPU:0"


def _ev(line, name, start, dur, plane=TPU):
    return devtrace.Event(plane, line, name, start, dur)


def _sweep_busy(events, w0, w1):
    """Busy ns by counting open intervals at every boundary (an algorithm
    independent of devtrace's merge)."""
    marks = []
    for e in events:
        s, t = max(e.start_ns, w0), min(e.end_ns, w1)
        if t > s:
            marks += [(s, 1), (t, -1)]
    busy, depth, last = 0, 0, None
    for x, d in sorted(marks):
        if depth > 0:
            busy += x - last
        depth += d
        last = x
    return busy


def test_reduce_hand_made_trace():
    host = "/host:CPU"
    events = [
        _ev("XLA Ops", "%fusion.1 = f32[8]{0} fusion(%a)", 1_000_000, 2_000_000),
        _ev("XLA Ops", "%scatter.2 = f32[8]{0} scatter(%b)", 2_000_000, 2_000_000),
        _ev("XLA Ops", "%fusion.1 = f32[8]{0} fusion(%a)", 4_050_000, 950_000),
        _ev("XLA Modules", "jit__pdhg_run_adaptive(7)", 1_000_000, 4_000_000),
        _ev("XLA Ops", "%copy.3 = f32[8]{0} copy(%c)", 7_000_000, 1_000_000),
        _ev("XLA Modules", "jit_concatenate(9)", 7_000_000, 1_000_000),
        devtrace.Event(host, "python3", "window", 0, 10_000_000),
        devtrace.Event(host, "python3", "step", 0, 10_000_000),
        devtrace.Event(host, "python3", "pack", 5_000_000, 2_000_000),
    ]
    r = devtrace.reduce(events, annotations={"window", "step", "pack"})
    assert r.window_s == pytest.approx(0.010)
    # busy: [1, 4) + [4.05, 5) + [7, 8) ms
    assert r.busy_s == pytest.approx(0.00495)
    assert r.idle_share == pytest.approx(50.5)
    assert r.program_s("_pdhg_run_adaptive") == pytest.approx(0.004)
    assert r.op_s["fusion.1"] == pytest.approx(0.00295)
    gaps = dict(r.breakdown()["idle_gaps"])
    assert gaps["between ops"] == pytest.approx(0.00005)     # 4.00-4.05 ms
    assert gaps["pack"] == pytest.approx(0.002)               # 5-7 ms
    assert gaps["step"] == pytest.approx(0.001 + 0.002)       # 0-1, 8-10 ms


def test_reduce_recorded_v5e_trace():
    doc = json.loads((BENCH / "testdata" / "trace_v5e.json").read_text())
    events = [devtrace.Event(*row) for row in doc["events"]]
    r = devtrace.reduce(events)
    ops = [e for e in events if e.line == devtrace.OPS_LINE]
    w0 = min(e.start_ns for e in events)
    w1 = max(e.end_ns for e in events)
    assert r.window_s == pytest.approx((w1 - w0) * 1e-9)
    assert r.busy_s == pytest.approx(_sweep_busy(ops, w0, w1) * 1e-9)
    assert 0 < r.busy_s <= r.window_s
    assert r.idle_share == pytest.approx(100 * (1 - r.busy_s / r.window_s))
    mods = [e for e in events if e.line == devtrace.MODULES_LINE
            and "_pdhg_run_adaptive" in e.name]
    assert r.program_s("_pdhg_run_adaptive") == pytest.approx(
        sum(e.dur_ns for e in mods) * 1e-9)
    assert sum(r.gaps_s.values()) == pytest.approx(r.window_s - r.busy_s)
    assert all(" = " not in name for name in r.op_s)


def test_reduce_without_device_events_reads_nothing():
    r = devtrace.reduce([devtrace.Event("/host:CPU", "python3", "window",
                                        0, 5)], annotations={"window"})
    assert r.busy_s == 0 and r.idle_share is None
    assert r.program_s("_pdhg_run_adaptive") == 0


def test_roofline_bytes_hand_count():
    # n=3 columns, m=2 rows, 4 nonzeros: the operator twice at 8 B a
    # nonzero (64 B); primal step x, c, tau, xmax in, x+ out (5 x 3 x 4
    # = 60 B); extrapolation reads x+, x (2 x 3 x 4 = 24 B); dual step
    # y gathered, then y, sigma, q in, y+ out (5 x 2 x 4 = 40 B)
    assert roofline.bytes_per_iteration(3, 2, 4) == 64 + 60 + 24 + 40
    assert roofline.pdhg_bytes([(3, 2, 4, 10), (3, 2, 4, 5)]) == 15 * 188
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


@pytest.mark.parametrize("config", ["fattree-k16", "pon3"])
def test_fabric_fingerprints(config):
    from repro.core import topology

    cfg = harness.load_json(BENCH / "configs" / f"{config}.json")
    fab_cfg = cfg["fabric"]
    fabric = harness.load_module(
        BENCH / "fabrics" / f"{fab_cfg['builder']}.py").build(
            **fab_cfg["kwargs"])
    program = harness.fabric_of(
        topology.build(fab_cfg["builder"], **fab_cfg["kwargs"]))
    assert fabric.fingerprint() == cfg["fingerprint"]
    assert program.fingerprint() == cfg["fingerprint"]
    program.cap = program.cap.copy()
    program.cap[0, 0] *= 0.5
    assert program.fingerprint() != cfg["fingerprint"]


def test_prepare_refuses_a_fabric_that_differs(monkeypatch, tmp_path):
    data = BENCH / "testdata"
    for sub in ("mixes", "limits"):
        (tmp_path / sub).symlink_to(data / sub)
    (tmp_path / "configs").mkdir()
    cfg = harness.load_json(data / "configs" / "fattree-k4.json")
    cfg["fingerprint"] = "0" * 64
    (tmp_path / "configs" / "fattree-k4.json").write_text(json.dumps(cfg))
    monkeypatch.setattr(harness, "SPEC", data / "BENCHMARK.json")
    monkeypatch.setattr(harness, "DATA", tmp_path)
    with pytest.raises(SystemExit, match="fingerprints differ"):
        harness.prepare("k4-sweep", 1)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "k16-sweep",
         "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 2
    assert out.stdout == ""
    assert "needs 1 TPU chip" in out.stderr


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("spec_path", [BENCH.parent / "BENCHMARK.json",
                                       BENCH / "testdata" / "BENCHMARK.json"])
def test_benchmark_files_found_by_name(spec_path):
    spec = json.loads(spec_path.read_text())
    data = spec_path.parent if spec_path.parent.name == "testdata" else BENCH
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and 0 < e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        cfg = harness.load_json(data / "configs" / f"{w['config']}.json")
        mix = harness.load_json(data / "mixes" / f"{w['traffic']}.json")
        limits = harness.load_json(data / "limits" / f"{w['name']}.json")
        assert (BENCH / "fabrics" / f"{cfg['fabric']['builder']}.py").exists()
        assert (BENCH / "drivers" / f"{mix['kind']}.py").exists()
        assert all(math.isfinite(v["limit"]) for v in limits.values())
        mine, layers = harness.metrics_for(spec, w["name"])
        assert {"setup_s"} < {m["name"] for m in mine}
        assert layers
        for m in layers:
            assert m["moves"] in {e["name"] for e in mine}
            read = harness.load_module(BENCH / "metrics" / f"{m['name']}.py")
            assert callable(read.read)
    if spec_path.parent == BENCH.parent:
        names = {c["name"] for c in spec["configs"]}
        assert names == {w["config"] for w in spec["workloads"]}
        for c in spec["configs"]:
            assert (BENCH.parent / c["file"]).exists()


def test_reference_lp_rounds_to_bfloat16():
    fabric = harness.load_module(BENCH / "fabrics" / "fat-tree.py").build(
        k=4)
    p = ref.Problem(fabric, np.array([4, 4]), np.array([30, 31]),
                    np.array([1.3, 0.7]), 4, 8.0, 0)
    lp = ref.build_lp(p, "energy")
    low = ref.build_lp(p, "energy", dtype=ref.BF16)
    assert lp.cols == low.cols and lp.A.shape == low.A.shape
    assert np.all(np.abs(low.c - lp.c) <= 2 ** -8 * np.abs(lp.c))
    assert np.any(low.c != lp.c)



def test_deck_gives_every_step_the_same_placement_classes():
    driver, _, _ = harness.prepare("pon3-sweep", 2 ** 33 + 5)
    racks = np.array_split(driver.fabric.task_servers, 4)

    def classes(cfs):
        return sorted(tuple(sorted((int(np.isin(rack, cf.dst).sum())
                                    for rack in racks), reverse=True))
                      for cf in cfs)

    def count(paths):
        return tuple(sorted((sum(p == [g] for p in paths)
                             for g in range(4)), reverse=True))

    deck = sorted(count(t["reduce"]) for _, t in driver.deck)
    steps = [driver.coflows(1, k) for k in range(3)]
    assert all(classes(s) == deck for s in steps)
    for cf in steps[0]:
        assert len(set(cf.src)) == 10 and len(set(cf.dst)) == 6
        assert not set(cf.src) & set(cf.dst)
    assert [cf.dst.tolist() for cf in steps[0]] != \
        [cf.dst.tolist() for cf in steps[1]]
    again = harness.prepare("pon3-sweep", 2 ** 33 + 5)[0].coflows(1, 0)
    assert [cf.dst.tolist() for cf in again] == \
        [cf.dst.tolist() for cf in steps[0]]


def test_template_draws_give_one_lp_shape():
    """On the fat-tree every draw of a template is the template up to a
    symmetry: the program's LP keeps its shape, the servers change."""
    driver, _, _ = harness.prepare("k16-sweep", 7)
    shapes = {}
    for seed in (7, 2 ** 32 + 11):
        driver.seed = seed
        for (p, rp), (j, _) in zip(driver.problems(1, 0), driver.order(1, 0)):
            lp, _ = driver.solver.build_routing_lp(p, driver.objective)
            shapes.setdefault(j, []).append(
                ((lp.n, lp.m_eq, lp.m, len(lp.val)), tuple(rp.src)))
    assert len(shapes) == 4
    for (shape_a, src_a), (shape_b, src_b) in shapes.values():
        assert shape_a == shape_b and src_a != src_b
