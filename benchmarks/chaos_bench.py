"""Benchmark: rolling-horizon runs under trace-replayed failure storms.

The chaos engine (core.chaos) replays seeded failure/repair event
traces against the online driver (core.arrivals.run_online): fabrics
degrade mid-run at epoch boundaries, stranded in-flight volume is
re-routed by the warm-start projection, disconnected demand parks as
deferred-by-failure, and every post-failure schedule must carry a
core.verify feasibility certificate.  This benchmark prices that whole
recovery machinery per topology: the same seeded arrival trace runs
once healthy and once under the "storm" preset, and the derived
columns record the robustness outcome —

  * availability      — trace-exact fraction of the run at full capacity
  * time-to-recover   — mean failure-to-certified-replan seconds
  * stranded Gbits    — carried volume whose decomposed paths died
  * completion inflation — chaos makespan over healthy makespan

Event traces are byte-identical per seed, so any metric drift is
solver-side.  The gate (disabled by default: chaos is overhead, not
speedup) applies to the aggregate chaos-vs-healthy wall ratio.

Run:  PYTHONPATH=src python benchmarks/chaos_bench.py [--seeds 2]
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
from repro.core import arrivals, topology, traffic
from repro.core import chaos as chaosmod

PAPER_TOPOS = "fat-tree,spine-leaf,bcube,dcell,pon3,pon5,pon-cascaded"


def _run(topo, trace, objective: str, iters: int, tol: float,
         events=None):
    return arrivals.run_online(
        topo, trace, objective, iters=iters, tol=tol,
        chaos=list(events) if events is not None else None,
        fallback_policy="scf" if events is not None else None)


def bench_cell(topo_name: str, objective: str, n_seeds: int, preset: str,
               iters: int, tol: float, scale, arrival,
               records: list[dict]):
    n_map, n_reduce, total = scale
    n_coflows, mean_s = arrival
    topo = topology.build(topo_name)
    pat = traffic.pattern("uniform", n_map=n_map, n_reduce=n_reduce,
                          total_gbits=total)
    aspec = arrivals.ArrivalSpec(n_coflows=n_coflows,
                                 mean_interarrival_s=mean_s)
    traces = [arrivals.generate_trace(topo, pat, aspec, s)
              for s in range(n_seeds)]
    event_sets = [chaosmod.generate_preset_events(topo, (preset,), s)
                  for s in range(n_seeds)]

    # untimed passes populate the XLA compile cache for both ladders
    # (healthy and degraded epochs stack different shapes)
    _run(topo, traces[0], objective, iters, tol)
    _run(topo, traces[0], objective, iters, tol, events=event_sets[0])

    t0 = time.perf_counter()
    healthy = [_run(topo, tr, objective, iters, tol) for tr in traces]
    t_healthy = time.perf_counter() - t0

    t0 = time.perf_counter()
    stormy = [_run(topo, tr, objective, iters, tol, events=evs)
              for tr, evs in zip(traces, event_sets)]
    t_chaos = time.perf_counter() - t0

    for res in healthy + stormy:
        assert all(e.feasible for e in res.epochs), topo_name
    for res in stormy:
        # every post-failure schedule must have certified feasible
        assert all(e.certified for e in res.epochs), topo_name
        assert res.backlog_gbits <= 1e-6, topo_name

    avail = float(np.mean([r.availability for r in stormy]))
    strand = float(np.sum([r.stranded_gbits for r in stormy]))
    ttrs = [t for r in stormy for t in r.recoveries]
    ttr = float(np.mean(ttrs)) if ttrs else float("nan")
    mk_h = np.array([r.makespan_s for r in healthy])
    mk_c = np.array([r.makespan_s for r in stormy])
    ok = np.isfinite(mk_h) & np.isfinite(mk_c) & (mk_h > 0)
    infl = float(np.mean(mk_c[ok] / mk_h[ok])) if ok.any() else float("nan")
    events_n = sum(len(evs) for evs in event_sets)

    cell = f"{topo_name}/min-{objective}"
    print(f"chaos/{cell}/healthy,{t_healthy*1e3:.1f},"
          f"{n_seeds} traces ({n_map}x{n_reduce} tasks, {total:g} Gbit, "
          f"{n_coflows} co-flows)")
    print(f"chaos/{cell}/{preset},{t_chaos*1e3:.1f},"
          f"avail={avail:.4f} ttr={ttr:.3f}s strand={strand:.3f}Gbit "
          f"inflation={infl:.3f}x ({events_n} events)")
    records += [
        bench_json.record(
            f"chaos/{cell}/healthy", topology=topo_name,
            objective=objective, wall_ms=t_healthy * 1e3,
            iterations=float(np.mean(
                [r.total_iterations for r in healthy])),
            derived=f"{n_seeds} traces ({n_map}x{n_reduce} tasks, "
                    f"{total:g} Gbit)"),
        bench_json.record(
            f"chaos/{cell}/{preset}", topology=topo_name,
            objective=objective, wall_ms=t_chaos * 1e3,
            iterations=float(np.mean(
                [r.total_iterations for r in stormy])),
            derived=f"availability={avail:.4f} recover_s={ttr:.3f} "
                    f"stranded_gbits={strand:.3f} inflation={infl:.3f}x "
                    f"({events_n} events)"),
    ]
    return t_chaos, t_healthy


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=2,
                    help="arrival/event traces per cell")
    ap.add_argument("--iters", type=int, default=3000)
    ap.add_argument("--tol", type=float, default=2e-3)
    ap.add_argument("--topos", default=PAPER_TOPOS,
                    help="comma list (default: the six paper DCNs plus "
                         "the cascaded-AWGR PON)")
    ap.add_argument("--objectives", default="energy")
    ap.add_argument("--chaos", default="storm",
                    help=f"chaos preset ({', '.join(chaosmod.PRESETS)})")
    ap.add_argument("--n-map", type=int, default=4)
    ap.add_argument("--n-reduce", type=int, default=3)
    ap.add_argument("--total-gbits", type=float, default=8.0)
    ap.add_argument("--arrival-coflows", type=int, default=3)
    ap.add_argument("--arrival-mean-s", type=float, default=1.0)
    ap.add_argument("--min-speedup", type=float, default=0.0,
                    help="gate on the aggregate "
                         "chaos-vs-healthy wall ratio (0 = report only; "
                         "chaos adds work, so ratios sit below 1)")
    ap.add_argument("--json-out", default=str(bench_json.DEFAULT_PATH),
                    help="BENCH_solver.json to merge records into "
                         "('' disables)")
    args = ap.parse_args(argv)
    compile_cache.enable()
    if args.chaos not in chaosmod.PRESETS:
        ap.error(f"unknown chaos preset {args.chaos!r}; "
                 f"have {sorted(chaosmod.PRESETS)}")
    scale = (args.n_map, args.n_reduce, args.total_gbits)
    arrival = (args.arrival_coflows, args.arrival_mean_s)
    records: list[dict] = []
    sum_chaos = sum_healthy = 0.0
    for t in args.topos.split(","):
        for obj in args.objectives.split(","):
            tc, th = bench_cell(t, obj, args.seeds, args.chaos, args.iters,
                                args.tol, scale, arrival, records)
            sum_chaos += tc
            sum_healthy += th
    return bench_json.finish_comparison(
        "chaos_bench", "chaos", sum_healthy, sum_chaos, records,
        total_label="healthy total", speed_label="healthy-vs-chaos ratio",
        json_out=args.json_out,
        min_speedup=args.min_speedup,
        run_args={"seeds": args.seeds, "iters": args.iters,
                  "tol": args.tol, "topos": args.topos,
                  "objectives": args.objectives, "chaos": args.chaos,
                  "n_map": args.n_map, "n_reduce": args.n_reduce,
                  "total_gbits": args.total_gbits,
                  "arrival_coflows": args.arrival_coflows,
                  "arrival_mean_s": args.arrival_mean_s})


if __name__ == "__main__":
    raise SystemExit(main())
