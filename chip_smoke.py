"""Drive the scheduler's main path once on a TPU and check what it makes.

    python chip_smoke.py                # one chip: every phase below
    python chip_smoke.py --four-chips   # four chips: the sharded phase only

One chip, in order (any failure exits non-zero; no exception is caught):

  1. device gate: the first JAX device must be a TPU; there is no CPU
     fallback;
  2. golden cells: spine-leaf and pon3 x min-energy and min-time, seed 0,
     through `solve_fast` (dense tiles on the chip), each schedule certified
     by `core.verify`, metrics held to tests/golden/metrics.json at the
     golden test's RTOL; then the pinned two-tenant `run_service` run;
  3. the sweep CLI, in process, over every topology with both
     objectives, 2 seeds, and the exact MILP (HiGHS) spot-checking one
     instance;
  4. fat-tree k=16 (1,024 servers) at benchmarks/scale_bench.py's
     traffic through `solve_fast_batch`: 4 seeds x both objectives, every
     schedule certified;
  5. `run_service` with 4 tenants under the measured cost model: no
     demand may leak.

`--four-chips` solves the fat-tree-k16 instance row-sharded over four
chips (`shards=4`) and on one chip; metrics must agree to 1e-4 relative
and both schedules certify.

All traffic is generated from seeds.  Wall times printed here are smoke
values, not metrics.  The last line of standard output is the verdict:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import csv
import json
import math
import pathlib
import sys
import tempfile
import time


ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "tests")]

# the repo's own modules come first: with chip_smoke.py alone in a
# directory this fails before JAX is touched
import test_golden_metrics as golden  # noqa: E402
from benchmarks.scale_bench import SIZES  # noqa: E402
from repro import compile_cache, service  # noqa: E402
from repro.core import (arrivals, solver, timeslot, topology,  # noqa: E402
                        traffic, verify)
from repro.sweep.__main__ import main as sweep_main  # noqa: E402

# benchmarks/scale_bench.py's settings for its fat-tree-k16 rows
K16_ITERS = 1500
K16_TOL = 2e-3
METRIC_KEYS = ("energy_j", "completion_s", "fairness_term", "served_gbits")


def say(msg: str) -> None:
    print(msg, flush=True)


def rel_dev(got: float, want: float) -> float:
    return abs(got - want) / max(abs(want), 1e-300)


def device_gate(need: int):
    """The devices JAX found; exits unless they are `need` or more TPUs."""
    import jax

    devices = jax.devices()
    d = devices[0]
    say(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devices)}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (jax.devices()[0] is "
                         f"{d.platform!r}); refusing to run elsewhere")
    if len(devices) < need:
        raise SystemExit(f"chip_smoke: need {need} TPU devices, JAX found "
                         f"{len(devices)}")
    return devices


def phase_golden() -> None:
    """The pinned golden grid and service run, held to the CPU goldens."""
    want_all = golden._golden()
    for topo_name, objective in golden.GRID:
        key = f"{topo_name}/min-{objective}/seed{golden.SEED}"
        got = golden._solve(topo_name, objective)   # certifies
        want = want_all[key]
        assert got["feasible"] and want["feasible"], key
        devs = {k: rel_dev(got[k], want[k]) for k in METRIC_KEYS}
        say(f"golden {key}: E={got['energy_j']!r} J "
            f"M={got['completion_s']!r} s cert=ok max_rel_dev="
            f"{max(devs.values()):.3e} ({max(devs, key=devs.get)})")
        for k in METRIC_KEYS:
            assert math.isclose(got[k], want[k], rel_tol=golden.RTOL,
                                abs_tol=1e-9), (key, k, got[k], want[k])

    want = want_all[golden.SERVICE_KEY]
    got = golden._service_run()          # every member certified
    for k in ("n_done", "arrived", "admitted"):
        assert got[k] == want[k], (golden.SERVICE_KEY, k, got[k], want[k])
    worst = 0.0
    for k in ("total_energy_j", "makespan_s", "tenant_energy_j",
              "tenant_shipped_gbits", "tenant_makespan_s"):
        gs = got[k] if isinstance(got[k], list) else [got[k]]
        ws = want[k] if isinstance(want[k], list) else [want[k]]
        assert len(gs) == len(ws), k
        for g, w in zip(gs, ws):
            assert math.isclose(g, w, rel_tol=golden.RTOL, abs_tol=1e-9), \
                (golden.SERVICE_KEY, k, g, w)
            worst = max(worst, rel_dev(g, w))
    say(f"golden {golden.SERVICE_KEY}: E={got['total_energy_j']!r} J "
        f"done={got['n_done']} max_rel_dev={worst:.3e}")


def phase_sweep() -> None:
    """The sweep CLI over every topology, with one exact-MILP check."""
    with tempfile.TemporaryDirectory() as out:
        rc = sweep_main(["--topos", "all", "--objectives",
                         "energy,completion", "--seeds", "2",
                         "--oracle-check", "1", "--out", out])
        assert rc == 0, f"sweep exited {rc}"
        with open(pathlib.Path(out) / "results.csv") as fh:
            rows = list(csv.DictReader(fh))
    assert rows and all(r["feasible"] == "True" for r in rows), \
        "sweep reported infeasible instances"
    checked = [r for r in rows if r["oracle_gap"]]
    assert len(checked) == 1, f"{len(checked)} oracle spot-checks"
    gap = float(checked[0]["oracle_gap"])
    assert math.isfinite(gap), gap
    r = checked[0]
    say(f"sweep: {len(rows)} instances, 0 infeasible; exact MILP gap "
        f"{r['topo']}/{r['pattern']}/min-{r['objective']} seed "
        f"{r['seed']}: {gap!r} (mip_gap={r['oracle_mip_gap']})")


def k16_problems(seeds) -> list[timeslot.ScheduleProblem]:
    topo_name, topo_kw, pat_kw, slack = SIZES["fat-tree-k16"]
    topo = topology.build(topo_name, **topo_kw)
    pat = traffic.pattern("uniform", **pat_kw)
    out = []
    for seed in seeds:
        cf = traffic.generate(topo, pat, seed=seed)
        out.append(timeslot.ScheduleProblem(
            topo, cf, n_slots=timeslot.suggest_n_slots(topo, cf),
            path_slack=slack))
    return out


def phase_k16() -> None:
    """fat-tree-k16, 4 seeds x both objectives, one batched dispatch each."""
    problems = k16_problems(range(4))
    for objective in ("energy", "time"):
        lp, _ = solver.build_routing_lp(problems[0], objective)
        t0 = time.perf_counter()
        results = solver.solve_fast_batch(problems, objective,
                                          iters=K16_ITERS, tol=K16_TOL)
        wall = time.perf_counter() - t0
        for seed, (p, r) in enumerate(zip(problems, results)):
            verify.check_schedule(p, r.schedule).assert_ok(
                f"fat-tree-k16/min-{objective}/seed{seed}")
        say(f"k16 min-{objective}: n={lp.n} m={lp.m} nnz={len(lp.val)} "
            f"(seed 0) iterations={[r.iterations for r in results]} "
            f"E={[float(r.metrics.energy_j) for r in results]} "
            f"certs=ok smoke_wall_s={wall:.3f}")


def phase_service() -> None:
    """Four tenants under the measured cost model; demand is conserved."""
    pat = traffic.pattern("uniform", n_map=3, n_reduce=2, total_gbits=36.0)
    spec = arrivals.ArrivalSpec(n_coflows=3, mean_interarrival_s=1.0)
    tenants = [service.TenantSpec(
        f"tenant{k}", topology.build(("spine-leaf", "pon3")[k % 2]), pat,
        spec, seed=k, objective=("energy", "time")[k // 2])
        for k in range(4)]
    cfg = service.ServiceConfig(cost=service.SolveCostModel(mode="measured"),
                                verify_schedules=True)
    for attempt in ("compile", "warm"):
        res = service.run_service(tenants, cfg)
        injected = sum(r.gbits for r in res.requests)
        shipped = sum(t.shipped_gbits for t in res.tenants)
        accounted = (shipped + res.backlog_gbits
                     + res.robustness.deferred_gbits)
        assert res.counters.shed == 0, res.counters
        assert math.isclose(injected, accounted, rel_tol=1e-9,
                            abs_tol=1e-6), (injected, shipped,
                                            res.backlog_gbits)
        lat = res.latency
        say(f"service ({attempt}): 4 tenants, {res.counters.arrived} "
            f"requests, {res.counters.windows} windows, "
            f"{res.counters.solver_dispatches} dispatches; injected "
            f"{injected!r} Gbit = shipped {shipped!r} + backlog "
            f"{res.backlog_gbits!r} + deferred "
            f"{res.robustness.deferred_gbits!r}; smoke latency "
            f"p50={lat.p50:.6f} s p99={lat.p99:.6f} s")


def phase_four_chips(devices) -> None:
    """fat-tree-k16 row-sharded over 4 chips vs the one-chip default."""
    import jax.numpy as jnp
    from repro.core import pdhg
    from repro.runtime.sharding import solver_mesh

    (p,) = k16_problems([0])
    runs = {}
    for label, kw in (("1 chip", {}), ("4 shards", dict(shards=4))):
        t0 = time.perf_counter()
        r = solver.solve_fast(p, "energy", iters=K16_ITERS, tol=K16_TOL,
                              **kw)
        wall = time.perf_counter() - t0
        verify.check_schedule(p, r.schedule).assert_ok(label)
        runs[label] = r
        say(f"four-chips {label}: E={r.metrics.energy_j!r} J "
            f"M={r.metrics.completion_s!r} s iterations={r.iterations} "
            f"cert=ok smoke_wall_s={wall:.3f}")
    one, four = runs["1 chip"].metrics, runs["4 shards"].metrics
    for name, a, b in (("energy_j", one.energy_j, four.energy_j),
                       ("completion_s", one.completion_s, four.completion_s),
                       ("served_gbits", one.served.sum(), four.served.sum())):
        assert math.isclose(a, b, rel_tol=1e-4), (name, a, b)
        say(f"four-chips {name}: rel_dev={rel_dev(float(b), float(a)):.3e}")

    # the burst's own outputs: y is split by rows over all four chips
    lp, _ = solver.build_routing_lp(p, "energy")
    op, vecs, ell = solver._pack_sharded(solver.block_stack([lp]).lp, 4)
    x, y, worst = pdhg.burst_sharded(
        solver_mesh(4), *vecs, *ell, jnp.zeros(op.n_pad),
        jnp.zeros(op.m_pad), row_meta=op.row_meta, col_meta=op.col_meta,
        iters=10)
    spans = {name: sorted(s.device.id for s in a.addressable_shards)
             for name, a in (("x", x), ("y", y), ("worst", worst))}
    say(f"four-chips burst output devices: {spans}")
    want = sorted(d.id for d in devices[:4])
    assert all(ids == want for ids in spans.values()), spans
    assert {s.data.shape[0] for s in y.addressable_shards} == \
        {op.m_pad // 4}, "y is not split by rows"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the row-sharded phase, on four chips")
    args = ap.parse_args(argv)
    cache = compile_cache.enable()
    devices = device_gate(4 if args.four_chips else 1)
    say(f"compile cache: {cache}")

    phases = ([("four-chips", lambda: phase_four_chips(devices))]
              if args.four_chips else
              [("golden", phase_golden), ("sweep", phase_sweep),
               ("k16", phase_k16), ("service", phase_service)])
    for name, run in phases:
        t0 = time.perf_counter()
        run()
        say(f"phase {name} passed (smoke_wall_s="
            f"{time.perf_counter() - t0:.3f})")

    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
