"""Benchmark: cold vs warm-started failure-ensemble re-solves.

A failure study multiplies the sweep grid: every healthy instance
re-solves under each degraded fabric.  This benchmark measures that
inner loop both ways:

  * cold — ``solver.solve_fast_ensemble(ensemble)``: each degraded
    instance solves from scratch (zero PDHG state), exactly what a
    sweep without the incremental machinery would pay;
  * warm — ``solver.solve_fast_ensemble(ensemble, warm=healthy)``:
    every member starts from its healthy instance's projected PDHG
    state (surviving routing paths keep their volume, duals map
    row-by-row — core.solver.project_warm_start), so the fused adaptive
    dispatch freezes most members within one residual-check chunk.

Both sides run the same block-diagonal stacked dispatches to the same
per-instance tolerance, and every schedule is verified feasible with the
exact paper model before timings count.  An untimed cold pass populates
the XLA compile cache first so neither side pays compilation; the gate
applies to the aggregate warm-vs-cold speedup over all measured cells.

Run:  PYTHONPATH=src python benchmarks/failure_bench.py [--seeds 8]
Prints ``name,ms,derived`` CSV rows like the other benchmarks and
merges machine-readable records into BENCH_solver.json at the repo root
(schema: benchmarks/bench_json.py).
"""
from __future__ import annotations

import argparse
import time

import numpy as np

try:
    import bench_json                      # script: python benchmarks/...
except ImportError:                        # module: python -m benchmarks....
    from benchmarks import bench_json
from repro import compile_cache
from repro.core import failures, solver, timeslot, topology, traffic


def build_cell(topo_name: str, n_seeds: int, presets: list[str],
               n_map: int, n_reduce: int, total_gbits: float):
    """Healthy seed vector + its failure ensemble (presets x seeds)."""
    topo = topology.build(topo_name)
    pat = traffic.pattern("uniform", n_map=n_map, n_reduce=n_reduce,
                          total_gbits=total_gbits)
    healthy = [timeslot.ScheduleProblem(
                   topo, cf, n_slots=timeslot.suggest_n_slots(topo, cf),
                   path_slack=2)
               for cf in traffic.generate_batch(topo, pat, range(n_seeds))]
    degraded, origin = [], []
    for preset in presets:
        for s, hp in enumerate(healthy):
            scen = failures.sample(topo, preset, s)
            # the sweep fixes tight horizons with a per-instance retry
            # ladder; the bench times one dispatch, so give the packer the
            # doubled horizon up front (the routing LP is horizon-aggregate
            # — T only affects the cheap numpy packing passes)
            dp = failures.degrade_problem(hp, scen)
            degraded.append(timeslot.ScheduleProblem(
                dp.topo, dp.coflow, n_slots=2 * dp.n_slots, rho=dp.rho,
                path_slack=dp.path_slack))
            origin.append(s)
    return healthy, degraded, origin


def bench_cell(topo_name: str, objective: str, n_seeds: int,
               presets: list[str], iters: int, tol: float, scale,
               records: list[dict]):
    n_map, n_reduce, total = scale
    healthy_probs, degraded, origin = build_cell(
        topo_name, n_seeds, presets, n_map, n_reduce, total)

    t0 = time.perf_counter()
    healthy = solver.solve_fast_batch(healthy_probs, objective, iters=iters,
                                      tol=tol)
    t_healthy = time.perf_counter() - t0
    warm_pool = [healthy[i] for i in origin]

    # untimed passes populate the XLA compile cache for BOTH ladders (cold
    # and warm stack different straggler shapes, hence different kernels)
    solver.solve_fast_ensemble(degraded, objective, iters=iters, tol=tol)
    solver.solve_fast_ensemble(degraded, objective, warm=warm_pool,
                               iters=iters, tol=tol)

    t0 = time.perf_counter()
    cold = solver.solve_fast_ensemble(degraded, objective, iters=iters,
                                      tol=tol)
    t_cold = time.perf_counter() - t0

    t0 = time.perf_counter()
    warm = solver.solve_fast_ensemble(degraded, objective, warm=warm_pool,
                                      iters=iters, tol=tol)
    t_warm = time.perf_counter() - t0

    for r in cold + warm:
        assert r.metrics.feasible, topo_name
    it_cold = float(np.mean([r.iterations for r in cold]))
    it_warm = float(np.mean([r.iterations for r in warm]))
    cell = f"{topo_name}/min-{objective}"
    print(f"failure/{cell}/healthy,{t_healthy*1e3:.1f},"
          f"{n_seeds} seeds ({n_map}x{n_reduce} tasks, {total:g} Gbit)")
    print(f"failure/{cell}/cold,{t_cold*1e3:.1f},"
          f"{len(degraded)} degraded instances ({it_cold:.0f} iters/inst)")
    print(f"failure/{cell}/warm,{t_warm*1e3:.1f},"
          f"{t_cold/t_warm:.2f}x speedup ({it_warm:.0f} iters/inst)")
    records += [
        bench_json.record(
            f"failure/{cell}/healthy", topology=topo_name,
            objective=objective, wall_ms=t_healthy * 1e3,
            iterations=float(np.mean([r.iterations for r in healthy])),
            derived=f"{n_seeds} seeds ({n_map}x{n_reduce} tasks, "
                    f"{total:g} Gbit)"),
        bench_json.record(
            f"failure/{cell}/cold", topology=topo_name,
            objective=objective, wall_ms=t_cold * 1e3,
            iterations=it_cold,
            derived=f"{len(degraded)} degraded instances"),
        bench_json.record(
            f"failure/{cell}/warm", topology=topo_name,
            objective=objective, wall_ms=t_warm * 1e3,
            iterations=it_warm,
            derived=f"{t_cold/t_warm:.2f}x speedup vs cold"),
    ]
    return t_cold, t_warm


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--iters", type=int, default=3000)
    ap.add_argument("--tol", type=float, default=2e-3,
                    help="LP tolerance (sweep default; schedules are "
                         "re-scored exactly regardless)")
    ap.add_argument("--topos", default="bcube,dcell,pon3")
    ap.add_argument("--objectives", default="energy,time")
    ap.add_argument("--failures", default="link1,link3,switch,degrade50")
    ap.add_argument("--n-map", type=int, default=4)
    ap.add_argument("--n-reduce", type=int, default=3)
    ap.add_argument("--total-gbits", type=float, default=8.0)
    ap.add_argument("--min-speedup", type=float, default=1.15,
                    help="gate on the aggregate warm-vs-cold speedup")
    ap.add_argument("--json-out", default=str(bench_json.DEFAULT_PATH),
                    help="BENCH_solver.json to merge records into "
                         "('' disables)")
    args = ap.parse_args(argv)
    compile_cache.enable()
    scale = (args.n_map, args.n_reduce, args.total_gbits)
    presets = args.failures.split(",")
    records: list[dict] = []
    sum_cold = sum_warm = 0.0
    for t in args.topos.split(","):
        for obj in args.objectives.split(","):
            tc, tw = bench_cell(t, obj, args.seeds, presets, args.iters,
                                args.tol, scale, records)
            sum_cold += tc
            sum_warm += tw
    return bench_json.finish_comparison(
        "failure_bench", "failure", sum_cold, sum_warm, records,
        total_label="cold total", speed_label="warm-vs-cold speedup",
        json_out=args.json_out,
        min_speedup=args.min_speedup,
        run_args={"seeds": args.seeds, "iters": args.iters, "tol": args.tol,
                  "topos": args.topos, "objectives": args.objectives,
                  "failures": args.failures,
                  "n_map": args.n_map, "n_reduce": args.n_reduce,
                  "total_gbits": args.total_gbits})


if __name__ == "__main__":
    raise SystemExit(main())
