"""Benchmark: the batched sweep engine vs a per-instance Python loop.

Measures sweep grid cells end-to-end, both ways:

  * loop  — ``[solver.solve_fast(p) for p in problems]``: the repo's
    per-instance fast path, exactly how a sweep ran before the batching
    layer.  Each instance pays its own XLA dispatches plus the host-side
    warm-restart ladder (run a chunk, sync the residual to Python,
    double, repeat — overshooting convergence by up to 2x per doubling).
  * batch — ``solver.solve_fast_batch(problems)``: the sweep engine.
    All instances stack block-diagonally into single jitted dispatches
    whose convergence loop runs in-graph (per-instance residuals every
    500 iterations, converged instances freeze), with stragglers
    re-stacked into narrower dispatches instead of dragging the batch.

Both sides solve to the same per-instance tolerance, include XLA
compilation (the wall time a fresh sweep cell pays), and every schedule
is verified feasible with the exact paper model before timings count.
The speedup gate applies to the aggregate over all cells.

The win is largest where the sweep lives — many small/medium LPs per
cell (bcube/dcell/PON rack cells: ~3-5x).  On topologies whose single
instances already saturate XLA's scatter throughput (fat-tree,
spine-leaf at paper scale) the engine approaches parity (~1.6-2.3x);
run ``--topos fat-tree,spine-leaf`` to measure that regime.

Run:  PYTHONPATH=src python benchmarks/sweep_bench.py [--seeds 16]
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
from repro.core import solver, timeslot, topology, traffic


def build_problems(topo_name: str, n_seeds: int, pat_name: str,
                   n_map: int, n_reduce: int, total_gbits: float):
    topo = topology.build(topo_name)
    pat = traffic.pattern(pat_name, n_map=n_map, n_reduce=n_reduce,
                          total_gbits=total_gbits)
    return [timeslot.ScheduleProblem(
                topo, cf, n_slots=timeslot.suggest_n_slots(topo, cf),
                path_slack=2)
            for cf in traffic.generate_batch(topo, pat, range(n_seeds))]


def bench_cell(topo_name: str, objective: str, pat_name: str, n_seeds: int,
               iters: int, tol: float, scale: tuple[int, int, float],
               records: list[dict]):
    n_map, n_reduce, total = scale
    probs = build_problems(topo_name, n_seeds, pat_name, n_map, n_reduce,
                           total)

    t0 = time.perf_counter()
    loop = [solver.solve_fast(p, objective, iters=iters, tol=tol)
            for p in probs]
    t_loop = time.perf_counter() - t0

    t0 = time.perf_counter()
    batch = solver.solve_fast_batch(probs, objective, iters=iters, tol=tol)
    t_batch = time.perf_counter() - t0

    for r in loop + batch:
        assert r.metrics.feasible and r.remaining_gbits < 1e-6, topo_name
    cell = f"{topo_name}/{pat_name}/min-{objective}"
    it_mean = float(np.mean([r.iterations for r in batch]))
    print(f"sweep/{cell}/loop,{t_loop*1e3:.1f},"
          f"{n_seeds} seeds ({n_map}x{n_reduce} tasks, {total:g} Gbit)")
    print(f"sweep/{cell}/batch,{t_batch*1e3:.1f},"
          f"{t_loop/t_batch:.2f}x speedup")
    records += [
        bench_json.record(
            f"sweep/{cell}/loop", topology=topo_name, objective=objective,
            wall_ms=t_loop * 1e3,
            iterations=float(np.mean([r.iterations for r in loop])),
            derived=f"{n_seeds} seeds ({n_map}x{n_reduce} tasks, "
                    f"{total:g} Gbit)"),
        bench_json.record(
            f"sweep/{cell}/batch", topology=topo_name, objective=objective,
            wall_ms=t_batch * 1e3, iterations=it_mean,
            derived=f"{t_loop/t_batch:.2f}x speedup vs loop"),
    ]
    return t_loop, t_batch


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=16)
    ap.add_argument("--iters", type=int, default=3000)
    ap.add_argument("--tol", type=float, default=2e-3,
                    help="LP tolerance (sweep default; schedules are "
                         "re-scored exactly regardless)")
    ap.add_argument("--topos", default="bcube,dcell,pon3")
    ap.add_argument("--objectives", default="energy,time")
    ap.add_argument("--pattern", default="uniform")
    ap.add_argument("--n-map", type=int, default=4)
    ap.add_argument("--n-reduce", type=int, default=3)
    ap.add_argument("--total-gbits", type=float, default=8.0)
    ap.add_argument("--min-speedup", type=float, default=3.0,
                    help="gate on the aggregate speedup over all cells")
    ap.add_argument("--json-out", default=str(bench_json.DEFAULT_PATH),
                    help="BENCH_solver.json to merge records into "
                         "('' disables)")
    args = ap.parse_args(argv)
    compile_cache.enable()
    scale = (args.n_map, args.n_reduce, args.total_gbits)
    records: list[dict] = []
    sum_loop = sum_batch = 0.0
    for t in args.topos.split(","):
        for obj in args.objectives.split(","):
            tl, tb = bench_cell(t, obj, args.pattern, args.seeds,
                                args.iters, args.tol, scale, records)
            sum_loop += tl
            sum_batch += tb
    return bench_json.finish_comparison(
        "sweep_bench", "sweep", sum_loop, sum_batch, records,
        total_label="loop total", speed_label="speedup vs per-instance loop",
        json_out=args.json_out,
        min_speedup=args.min_speedup,
        run_args={"seeds": args.seeds, "iters": args.iters, "tol": args.tol,
                  "topos": args.topos, "objectives": args.objectives,
                  "pattern": args.pattern,
                  "n_map": args.n_map, "n_reduce": args.n_reduce,
                  "total_gbits": args.total_gbits})


if __name__ == "__main__":
    raise SystemExit(main())
