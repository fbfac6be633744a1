"""Benchmark: coalesced vs serial multi-tenant service dispatch.

The scheduler service (repro.service) groups same-shape-bucket tenants
into one stacked ``solve_fast_group`` dispatch per coalescing window;
the naive alternative dispatches every tenant's window problem on its
own.  This benchmark runs the same seeded multi-tenant workload both
ways and reports **sustained co-flows/sec at a fixed p99 decision-
latency budget**:

  * serial    — ``ServiceConfig(coalesce=False, overlap_build=False)``:
    one solver dispatch per ready tenant per window (what N independent
    run_online loops would pay);
  * coalesced — the service default: same-bucket tenants share one
    stacked dispatch (and its compiled executable), with next-group LP
    builds prefetched on a CPU thread during device solves.

Both modes run under the "measured" SolveCostModel, so the reported
p99 decision latency reflects real solve wall time on this machine;
untimed passes of BOTH modes run first so neither side pays XLA
compilation in the timed pass.  Scheduling decisions are identical
either way (stacked PDHG decouples over blocks — tests/test_service.py
pins coalesced == serial metrics), so the comparison is pure dispatch
efficiency: co-flows served per wall second, throughput = completed
requests / end-to-end wall time.

Run:  PYTHONPATH=src python benchmarks/service_bench.py [--tenants 4]
Prints ``name,ms,derived`` CSV rows and merges records into
BENCH_solver.json (schema: benchmarks/bench_json.py).  The gate passes
if the aggregate coalesced-vs-serial throughput ratio reaches
--min-speedup with the coalesced p99 within --p99-budget-s.
"""
from __future__ import annotations

import argparse
import time

try:
    import bench_json                      # script: python benchmarks/...
except ImportError:                        # module: python -m benchmarks....
    from benchmarks import bench_json
from repro import compile_cache, service
from repro.core import arrivals, topology, traffic


def build_tenants(topo_name: str, args) -> list[service.TenantSpec]:
    topo = topology.build(topo_name)
    pat = traffic.pattern("uniform", n_map=args.n_map,
                          n_reduce=args.n_reduce,
                          total_gbits=args.total_gbits)
    spec = arrivals.ArrivalSpec(family=args.family,
                                n_coflows=args.coflows,
                                mean_interarrival_s=args.mean_s)
    return [service.TenantSpec(name=f"tenant{k}", topo=topo, pattern=pat,
                               arrivals=spec, seed=k)
            for k in range(args.tenants)]


def run_mode(tenants, args, *, coalesce: bool):
    cfg = service.ServiceConfig(
        iters=args.iters, tol=args.tol,
        coalesce=coalesce, overlap_build=coalesce,
        slo_p99_s=args.p99_budget_s,
        cost=service.SolveCostModel(mode="measured"))
    t0 = time.perf_counter()
    res = service.run_service(tenants, cfg)
    wall = time.perf_counter() - t0
    assert res.backlog_gbits <= 1e-6, res.backlog_gbits
    return res, wall


def bench_cell(topo_name: str, args, records: list[dict]):
    tenants = build_tenants(topo_name, args)

    # untimed passes populate the compile caches for BOTH dispatch
    # shapes (serial B=1 stacks vs coalesced multi-member stacks)
    run_mode(tenants, args, coalesce=False)
    run_mode(tenants, args, coalesce=True)

    serial, t_serial = run_mode(tenants, args, coalesce=False)
    coal, t_coal = run_mode(tenants, args, coalesce=True)

    done_s = sum(r.status == "done" for r in serial.requests)
    done_c = sum(r.status == "done" for r in coal.requests)
    thr_s = done_s / t_serial
    thr_c = done_c / t_coal
    cell = topo_name
    print(f"service/{cell}/serial,{t_serial*1e3:.1f},"
          f"{thr_s:.2f} co-flows/s p99={serial.latency.p99:.3f}s "
          f"({serial.counters.dispatches} dispatches)")
    print(f"service/{cell}/coalesced,{t_coal*1e3:.1f},"
          f"{thr_c:.2f} co-flows/s p99={coal.latency.p99:.3f}s "
          f"({coal.counters.dispatches} dispatches, "
          f"{coal.counters.bucket_hits} bucket hits)")
    records += [
        bench_json.record(
            f"service/{cell}/serial", topology=topo_name,
            wall_ms=t_serial * 1e3,
            derived=f"{thr_s:.2f} co-flows/s at p99="
                    f"{serial.latency.p99:.3f}s "
                    f"({serial.counters.dispatches} dispatches)"),
        bench_json.record(
            f"service/{cell}/coalesced", topology=topo_name,
            wall_ms=t_coal * 1e3,
            derived=f"{thr_c:.2f} co-flows/s at p99="
                    f"{coal.latency.p99:.3f}s "
                    f"({coal.counters.dispatches} dispatches, "
                    f"{coal.counters.bucket_hits} bucket hits)"),
    ]
    return (done_s, t_serial, serial.latency.p99), \
        (done_c, t_coal, coal.latency.p99)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", type=int, default=12)
    ap.add_argument("--coflows", type=int, default=3,
                    help="co-flows per tenant trace")
    ap.add_argument("--iters", type=int, default=3000)
    ap.add_argument("--tol", type=float, default=2e-3)
    ap.add_argument("--topos", default="spine-leaf,pon3")
    ap.add_argument("--family", default="poisson",
                    help=f"arrival family ({','.join(arrivals.FAMILIES)})")
    ap.add_argument("--mean-s", type=float, default=1.0)
    ap.add_argument("--n-map", type=int, default=3)
    ap.add_argument("--n-reduce", type=int, default=2)
    ap.add_argument("--total-gbits", type=float, default=36.0,
                    help="per co-flow; large enough that tenants stay "
                         "busy across windows and actually coalesce")
    ap.add_argument("--p99-budget-s", type=float, default=10.0,
                    help="decision-latency budget the coalesced p99 must "
                         "stay within (includes the virtual coalescing-"
                         "window wait, so it is bounded below by ~1 "
                         "window even at zero solve cost)")
    ap.add_argument("--min-speedup", type=float, default=1.05,
                    help="the aggregate coalesced-vs-serial throughput "
                         "ratio must reach this")
    ap.add_argument("--json-out", default=str(bench_json.DEFAULT_PATH),
                    help="BENCH_solver.json to merge records into "
                         "('' disables)")
    args = ap.parse_args(argv)
    compile_cache.enable()
    records: list[dict] = []
    ds = dc = ts = tc = 0.0
    p99_c = 0.0
    for t in args.topos.split(","):
        (n_s, w_s, _), (n_c, w_c, p_c) = bench_cell(t, args, records)
        ds, ts = ds + n_s, ts + w_s
        dc, tc = dc + n_c, tc + w_c
        p99_c = max(p99_c, p_c)
    thr_s, thr_c = ds / ts, dc / tc
    print(f"service/aggregate,{tc*1e3:.1f},"
          f"{thr_c:.2f} coalesced vs {thr_s:.2f} serial co-flows/s "
          f"({thr_c/thr_s:.2f}x) p99={p99_c:.3f}s")
    records.append(bench_json.record(
        "service/aggregate", wall_ms=tc * 1e3,
        derived=f"{thr_c:.2f} coalesced vs {thr_s:.2f} serial "
                f"co-flows/s ({thr_c/thr_s:.2f}x) at p99={p99_c:.3f}s"))
    if args.json_out:
        path = bench_json.update(
            "service_bench", records, path=args.json_out,
            args={"tenants": args.tenants, "coflows": args.coflows,
                  "iters": args.iters, "tol": args.tol,
                  "topos": args.topos, "family": args.family, "mean_s": args.mean_s,
                  "n_map": args.n_map, "n_reduce": args.n_reduce,
                  "total_gbits": args.total_gbits,
                  "p99_budget_s": args.p99_budget_s})
        print(f"service/json,0.0,records merged into {path}")
    ratio = thr_c / max(thr_s, 1e-9)
    if args.min_speedup <= 0:       # report-only (CI): no gating
        print("OK: report-only (--min-speedup 0)")
        return 0
    if p99_c > args.p99_budget_s:
        print(f"FAIL: coalesced p99 {p99_c:.3f}s > budget "
              f"{args.p99_budget_s}s")
        return 1
    if ratio < args.min_speedup:
        print(f"FAIL: coalesced-vs-serial throughput {ratio:.2f}x "
              f"< {args.min_speedup}x")
        return 1
    print(f"OK: coalesced-vs-serial throughput {ratio:.2f}x "
          f">= {args.min_speedup}x within p99 budget")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
