"""Benchmark: the problem-construction fast path (build-vs-solve split).

PDHG iterations stopped dominating sweep wall time once the hot loop was
fused (PR 3) and re-solves warm-started (PRs 2/4); what remained was the
pure-Python LP row emission and per-shape re-packing around every solve.
This benchmark measures that build path three ways over a sweep-style
grid, per (topology, objective):

  * legacy — ``solver._build_routing_lp_loops``: the pre-vectorization
    builder (per-row Python closures, ``(f, e, w)`` dict keys), kept
    verbatim as the measurement baseline;
  * cold   — the vectorized assembly with the structure cache disabled
    (every call pays `_build_structure`'s index arithmetic);
  * warm   — the vectorized assembly with the structure cache hot (the
    steady state of arrival traces, retry ladders, and scaled-
    degradation ensembles: only `_fill_lp`'s O(nnz) value refresh runs).

It also times one batched solve per cell so the report shows the
build-vs-solve wall split the sweep actually experiences.

The gate applies to the aggregate legacy/warm ratio (the
"vectorized+cached" fast path, default ``--min-speedup 3``).  Cache
equivalence itself (bit-for-bit identical LPs and metrics) is pinned by
tests/test_build_cache.py, and the zero-rebuild property of re-solved
arrival traces is asserted there via the same counters this benchmark
prints.

Run:  PYTHONPATH=src python benchmarks/build_bench.py [--seeds 8]
Prints ``name,ms,derived`` CSV rows like the other benchmarks and merges
machine-readable records into BENCH_solver.json at the repo root
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

OBJECTIVES = ("energy", "time")


def build_problems(topo_name: str, pat_name: str, n_seeds: int,
                   n_map: int, n_reduce: int, total_gbits: float):
    topo = topology.build(topo_name)
    pat = traffic.pattern(pat_name, n_map=n_map, n_reduce=n_reduce,
                          total_gbits=total_gbits)
    return [timeslot.ScheduleProblem(
                topo, cf, n_slots=timeslot.suggest_n_slots(topo, cf),
                path_slack=2)
            for cf in traffic.generate_batch(topo, pat, range(n_seeds))]


def _time_builds(probs, objective: str, builder) -> float:
    t0 = time.perf_counter()
    for p in probs:
        builder(p, objective)
    return time.perf_counter() - t0


def bench_build_cell(topo_name: str, objective: str, probs,
                     records: list[dict]):
    """One (topology, objective) cell's three build modes, timed and
    recorded once per cell.  Returns
    (legacy_s, cold_s, warm_s)."""
    cell = f"{topo_name}/min-{objective}"
    t_legacy = _time_builds(probs, objective,
                            solver._build_routing_lp_loops)
    solver.reset_build_caches()
    t_cold = _time_builds(
        probs, objective,
        lambda p, o: solver.build_routing_lp(p, o, cache=False))
    solver.reset_build_caches()
    _time_builds(probs, objective, solver.build_routing_lp)   # populate
    t_warm = _time_builds(probs, objective, solver.build_routing_lp)
    stats = solver.build_cache_stats()
    assert stats.structure_misses == len(probs), "cache should be hot"
    assert stats.structure_hits == len(probs), "warm pass should hit"
    print(f"build/{cell}/legacy,{t_legacy*1e3:.1f},"
          f"{len(probs)} builds (loop reference)")
    print(f"build/{cell}/cold,{t_cold*1e3:.1f},"
          f"{t_legacy/t_cold:.1f}x vs legacy (vectorized, cache off)")
    print(f"build/{cell}/warm,{t_warm*1e3:.1f},"
          f"{t_legacy/t_warm:.1f}x vs legacy (structure cache hot)")
    records += [
        bench_json.record(f"build/{cell}/legacy", topology=topo_name,
                          objective=objective, wall_ms=t_legacy * 1e3,
                          derived=f"{len(probs)} builds (loop reference)"),
        bench_json.record(f"build/{cell}/cold", topology=topo_name,
                          objective=objective, wall_ms=t_cold * 1e3,
                          derived=f"{t_legacy/t_cold:.1f}x vs legacy"),
        bench_json.record(f"build/{cell}/warm", topology=topo_name,
                          objective=objective, wall_ms=t_warm * 1e3,
                          derived=f"{t_legacy/t_warm:.1f}x vs legacy"),
    ]
    return t_legacy, t_cold, t_warm


def bench_solve_cell(topo_name: str, objective: str, probs, iters: int,
                     tol: float, t_warm: float, records: list[dict]):
    """One (topology, objective) batched solve, for the
    build-vs-solve wall split (`t_warm` is the cell's cached build
    time from bench_build_cell)."""
    cell = f"{topo_name}/min-{objective}"
    t0 = time.perf_counter()
    results = solver.solve_fast_batch(probs, objective, iters=iters,
                                      tol=tol)
    # the sweep's horizon-doubling retry ladder, so the build-vs-solve
    # split reflects what a real sweep cell pays
    for i, (p, r) in enumerate(zip(probs, results)):
        tries = 0
        while ((r.remaining_gbits > 1e-6 or not r.metrics.feasible)
               and tries < 2):
            p = timeslot.rehorizon(
                p, 2 * p.n_slots,
                path_slack=p.path_slack if tries == 0 else None)
            r = solver.solve_fast(p, objective, iters=iters, tol=tol)
            tries += 1
        results[i] = r
    t_solve = time.perf_counter() - t0
    for r in results:
        assert r.metrics.feasible, (topo_name, objective)

    split = t_warm / max(t_warm + t_solve, 1e-12)
    print(f"build/{cell}/solve,{t_solve*1e3:.1f},"
          f"warm build is {split:.2%} of build+solve wall")
    records.append(
        bench_json.record(f"build/{cell}/solve",
                          topology=topo_name, objective=objective,
                          wall_ms=t_solve * 1e3,
                          iterations=float(np.mean(
                              [r.iterations for r in results])),
                          derived=f"warm build {split:.2%} of "
                                  f"build+solve wall"))
    return t_solve


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--iters", type=int, default=1500)
    ap.add_argument("--tol", type=float, default=2e-3)
    ap.add_argument("--topos", default=",".join(topology.BUILDERS),
                    help="comma list (default: the full sweep grid)")
    ap.add_argument("--objectives", default="energy,time")
    ap.add_argument("--pattern", default="uniform")
    ap.add_argument("--n-map", type=int, default=10)
    ap.add_argument("--n-reduce", type=int, default=6)
    ap.add_argument("--total-gbits", type=float, default=30.0)
    ap.add_argument("--min-speedup", type=float, default=3.0,
                    help="gate on aggregate legacy/warm build ratio")
    ap.add_argument("--json-out", default=str(bench_json.DEFAULT_PATH),
                    help="BENCH_solver.json to merge records into "
                         "('' disables)")
    args = ap.parse_args(argv)
    compile_cache.enable()

    records: list[dict] = []
    t_legacy = t_cold = t_warm = 0.0
    for topo_name in args.topos.split(","):
        probs = build_problems(topo_name, args.pattern, args.seeds,
                               args.n_map, args.n_reduce,
                               args.total_gbits)
        for objective in args.objectives.split(","):
            tl, tc, tw = bench_build_cell(topo_name, objective, probs,
                                          records)
            t_legacy += tl
            t_cold += tc
            t_warm += tw
            bench_solve_cell(topo_name, objective, probs, args.iters,
                             args.tol, tw, records)

    speed_cold = t_legacy / max(t_cold, 1e-12)
    speed_warm = t_legacy / max(t_warm, 1e-12)
    print(f"build/aggregate/legacy,{t_legacy*1e3:.1f},total loop builds")
    print(f"build/aggregate/cold,{t_cold*1e3:.1f},"
          f"{speed_cold:.1f}x vs legacy")
    print(f"build/aggregate/warm,{t_warm*1e3:.1f},"
          f"{speed_warm:.1f}x vs legacy (vectorized+cached)")
    records += [
        bench_json.record("build/aggregate/legacy", wall_ms=t_legacy * 1e3,
                          derived="total loop-reference build time"),
        bench_json.record("build/aggregate/cold", wall_ms=t_cold * 1e3,
                          derived=f"{speed_cold:.1f}x vs legacy"),
        bench_json.record("build/aggregate/warm", wall_ms=t_warm * 1e3,
                          derived=f"{speed_warm:.1f}x vs legacy "
                                  f"(vectorized+cached)"),
    ]
    if args.json_out:
        path = bench_json.update(
            "build_bench", records, path=args.json_out,
            args={"seeds": args.seeds, "iters": args.iters,
                  "tol": args.tol, "topos": args.topos,
                  "objectives": args.objectives, "pattern": args.pattern,
                  "n_map": args.n_map, "n_reduce": args.n_reduce,
                  "total_gbits": args.total_gbits})
        print(f"build/json,0.0,records merged into {path}")
    if speed_warm < args.min_speedup:
        print(f"FAIL: aggregate build speedup {speed_warm:.2f}x "
              f"< {args.min_speedup}x (vectorized+cached vs legacy)")
        return 1
    print(f"OK: aggregate build speedup {speed_warm:.2f}x "
          f">= {args.min_speedup}x (vectorized+cached vs legacy)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
