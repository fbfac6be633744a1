"""Benchmark: baseline policies vs the LP fast path — wall and gap.

The policy zoo (repro.core.policies) trades optimality for speed: ECMP
hashing, least-loaded greedy routing, and the slot-packing heuristics
decide a schedule in milliseconds of pure NumPy where the PDHG fast
path spends seconds of device time.  This benchmark quantifies both
sides of that trade on the paper's instances:

  * **wall** — per-instance decision time of each policy vs the LP
    solve on the same ScheduleProblem (LP timed after an untimed
    compile pass, so the comparison is steady-state device time);
  * **gap**  — `core.policies.gap_vs_lp`: the LP-objective functional
    of the policy's schedule over the LP's, 1.00x meaning the policy
    tied the optimum within solver tolerance.

Every policy schedule is certified feasible by
`core.verify.check_schedule` before it is reported — a fast-but-wrong
baseline would fail the run, not flatter it.

Run:  PYTHONPATH=src python benchmarks/policy_bench.py [--topos ...]
Prints ``name,ms,derived`` CSV rows and merges records into
BENCH_solver.json (schema: benchmarks/bench_json.py).  The gate passes
if every policy's schedule certifies feasible with gap >= 1.0 and at
least one policy reaches --min-speedup x the LP's wall time
(--min-speedup 0 = report-only, the CI mode).
"""
from __future__ import annotations

import argparse
import time

try:
    import bench_json                      # script: python benchmarks/...
except ImportError:                        # module: python -m benchmarks....
    from benchmarks import bench_json
from repro import compile_cache
from repro.core import policies, solver, topology, traffic, verify
from repro.core.timeslot import ScheduleProblem, suggest_n_slots


def build_problem(topo_name: str, args) -> ScheduleProblem:
    topo = topology.build(topo_name)
    pat = traffic.pattern("uniform", n_map=args.n_map,
                          n_reduce=args.n_reduce,
                          total_gbits=args.total_gbits)
    cf = traffic.generate(topo, pat, seed=args.seed)
    return ScheduleProblem(topo, cf, n_slots=suggest_n_slots(topo, cf),
                           path_slack=2)


def bench_cell(topo_name: str, args, records: list[dict]
               ) -> dict[str, float]:
    """One topology cell; returns {policy: lp_wall/pol_wall}."""
    p = build_problem(topo_name, args)
    obj = args.objective
    cell = topo_name

    solver.solve_fast(p, obj, iters=args.iters)  # compile
    t0 = time.perf_counter()
    lp = solver.solve_fast(p, obj, iters=args.iters)
    t_lp = time.perf_counter() - t0
    verify.check_schedule(p, lp.schedule).assert_ok(f"lp {cell}")
    print(f"policy/{cell}/lp,{t_lp*1e3:.1f},"
          f"gap=1.00x ({lp.iterations} iters)")
    records.append(bench_json.record(
        f"policy/{cell}/lp", topology=topo_name, objective=obj,
        wall_ms=t_lp * 1e3, iterations=lp.iterations,
        derived="gap=1.00x (the LP reference)"))

    speedups: dict[str, float] = {}
    for name, pol in policies.POLICIES.items():
        pp = build_problem(topo_name, args)
        pol.solve(pp, obj)        # warm path-set caches
        t0 = time.perf_counter()
        r = pol.solve(pp, obj)
        t_pol = time.perf_counter() - t0
        r.certificate.assert_ok(f"{name} {cell}")
        assert r.remaining_gbits <= 1e-6, (name, r.remaining_gbits)
        gap = policies.gap_vs_lp(obj, pp, r.schedule, p, lp)
        speedups[name] = t_lp / max(t_pol, 1e-9)
        print(f"policy/{cell}/{name},{t_pol*1e3:.1f},"
              f"gap={gap:.2f}x ({speedups[name]:.0f}x faster than LP)")
        records.append(bench_json.record(
            f"policy/{cell}/{name}", topology=topo_name, objective=obj,
            wall_ms=t_pol * 1e3,
            derived=f"gap={gap:.2f}x vs LP, "
                    f"{speedups[name]:.0f}x faster"))
        if gap < 1.0 - 1e-4:
            raise SystemExit(f"FAIL: {name} gap {gap:.4f}x < 1.0x on "
                             f"{cell} — broken LP reference or verifier")
    return speedups


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--topos", default="spine-leaf,pon3")
    ap.add_argument("--objective", default="energy",
                    choices=("energy", "time", "fair"))
    ap.add_argument("--iters", type=int, default=3000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n-map", type=int, default=10)
    ap.add_argument("--n-reduce", type=int, default=6)
    ap.add_argument("--total-gbits", type=float, default=30.0)
    ap.add_argument("--min-speedup", type=float, default=10.0,
                    help="at least one policy must beat the LP's wall "
                         "time by this factor (0 = report-only)")
    ap.add_argument("--json-out", default=str(bench_json.DEFAULT_PATH),
                    help="BENCH_solver.json to merge records into "
                         "('' disables)")
    args = ap.parse_args(argv)
    compile_cache.enable()
    records: list[dict] = []
    best = 0.0
    for t in args.topos.split(","):
        speedups = bench_cell(t, args, records)
        best = max(best, max(speedups.values()))
    if args.json_out:
        path = bench_json.update(
            "policy_bench", records, path=args.json_out,
            args={"topos": args.topos, "objective": args.objective,
                  "iters": args.iters, "seed": args.seed,
                  "n_map": args.n_map, "n_reduce": args.n_reduce,
                  "total_gbits": args.total_gbits})
        print(f"policy/json,0.0,records merged into {path}")
    if args.min_speedup <= 0:       # report-only (CI): no gating
        print("OK: report-only (--min-speedup 0)")
        return 0
    if best < args.min_speedup:
        print(f"FAIL: best policy-vs-LP speedup {best:.1f}x < "
              f"{args.min_speedup}x")
        return 1
    print(f"OK: best policy-vs-LP speedup {best:.0f}x >= "
          f"{args.min_speedup}x, all gaps >= 1.0x, all schedules "
          f"certified feasible")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
