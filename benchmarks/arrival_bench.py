"""Benchmark: warm vs cold rolling-horizon epoch re-solves.

The online arrival engine (core.arrivals.run_online) re-plans at every
epoch boundary; the question this benchmark answers is what the
previous epoch's projected PDHG state is worth:

  * cold — ``run_online(..., warm=False)``: every epoch solves from
    zero (what a naive re-planner would pay);
  * warm — ``run_online(..., warm=True)``: each epoch starts from the
    previous epoch's primal/dual state, carried residual flows mapped
    to their new indices (``solver.project_warm_start`` with
    ``flow_map``), so the adaptive dispatch freezes within one
    residual-check chunk once the carried routing is repaired.

Two speedups are reported per cell and in aggregate:

  * iterations — total PDHG iterations over all epochs, deterministic
    for a fixed seed/jax build (the primary gate: the paper-model
    work a warm start saves);
  * wall — end-to-end trace time.  Untimed passes of BOTH modes run
    first so neither side pays XLA compilation (warm and cold visit
    different epoch problem shapes, hence different kernels).

The load is tuned so co-flows span several epochs (per-mapper volume >
rho * epoch seconds) — with no carried flows a warm start has nothing
to project and both modes converge in the first burst.  Warm and cold
runs may pack slightly different (equally feasible, exactly re-scored)
schedules: a warm start converges to a different point of the LP's
optimal face.

Run:  PYTHONPATH=src python benchmarks/arrival_bench.py [--seeds 3]
Prints ``name,ms,derived`` CSV rows like the other benchmarks and
merges machine-readable records into BENCH_solver.json at the repo
root (schema: benchmarks/bench_json.py).  The gate passes if the
aggregate iteration OR wall speedup reaches --min-speedup.
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


def build_traces(topo_name: str, n_seeds: int, family: str, n_coflows: int,
                 mean_s: float, n_map: int, n_reduce: int, total: float):
    topo = topology.build(topo_name)
    pat = traffic.pattern("uniform", n_map=n_map, n_reduce=n_reduce,
                          total_gbits=total)
    spec = arrivals.ArrivalSpec(family=family, n_coflows=n_coflows,
                                mean_interarrival_s=mean_s)
    return topo, [arrivals.generate_trace(topo, pat, spec, s)
                  for s in range(n_seeds)]


def run_traces(topo, traces, objective: str, *, warm: bool, epoch_s: float,
               iters: int, tol: float):
    t0 = time.perf_counter()
    outs = [arrivals.run_online(topo, tr, objective, warm=warm,
                                epoch_s=epoch_s, iters=iters, tol=tol)
            for tr in traces]
    wall = time.perf_counter() - t0
    for r in outs:
        assert all(e.feasible for e in r.epochs), topo.name
        assert r.backlog_gbits <= 1e-6, (topo.name, r.backlog_gbits)
    return outs, wall


def bench_cell(topo_name: str, objective: str, args, records: list[dict]):
    topo, traces = build_traces(
        topo_name, args.seeds, args.family, args.coflows, args.mean_s,
        args.n_map, args.n_reduce, args.total_gbits)
    kw = dict(epoch_s=args.epoch_s, iters=args.iters, tol=args.tol)

    # untimed passes populate the XLA compile cache for BOTH modes (their
    # epoch problems diverge in shape once schedules differ)
    run_traces(topo, traces, objective, warm=False, **kw)
    run_traces(topo, traces, objective, warm=True, **kw)

    cold, t_cold = run_traces(topo, traces, objective, warm=False, **kw)
    warm, t_warm = run_traces(topo, traces, objective, warm=True, **kw)

    it_cold = float(sum(r.total_iterations for r in cold))
    it_warm = float(sum(r.total_iterations for r in warm))
    ep = int(sum(r.n_epochs for r in warm))
    cell = f"{topo_name}/min-{objective}"
    print(f"arrival/{cell}/cold,{t_cold*1e3:.1f},"
          f"{ep} epochs over {len(traces)} traces "
          f"({it_cold:.0f} total iters)")
    print(f"arrival/{cell}/warm,{t_warm*1e3:.1f},"
          f"{it_cold/max(it_warm, 1.0):.2f}x iters / "
          f"{t_cold/t_warm:.2f}x wall vs cold ({it_warm:.0f} total iters)")
    records += [
        bench_json.record(
            f"arrival/{cell}/cold", topology=topo_name, objective=objective,
            wall_ms=t_cold * 1e3, iterations=it_cold,
            derived=f"{ep} epochs over {len(traces)} traces"),
        bench_json.record(
            f"arrival/{cell}/warm", topology=topo_name, objective=objective,
            wall_ms=t_warm * 1e3, iterations=it_warm,
            derived=f"{it_cold/max(it_warm, 1.0):.2f}x iteration / "
                    f"{t_cold/t_warm:.2f}x wall speedup vs cold"),
    ]
    return (t_cold, t_warm), (it_cold, it_warm)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=3,
                    help="arrival traces per cell")
    ap.add_argument("--iters", type=int, default=3000)
    ap.add_argument("--tol", type=float, default=2e-3)
    ap.add_argument("--topos", default="spine-leaf,pon3")
    ap.add_argument("--objectives", default="energy,time")
    ap.add_argument("--family", default="poisson",
                    help=f"arrival family ({','.join(arrivals.FAMILIES)})")
    ap.add_argument("--coflows", type=int, default=5)
    ap.add_argument("--mean-s", type=float, default=2.0)
    ap.add_argument("--epoch-s", type=float, default=1.0)
    ap.add_argument("--n-map", type=int, default=4)
    ap.add_argument("--n-reduce", type=int, default=3)
    ap.add_argument("--total-gbits", type=float, default=48.0,
                    help="per co-flow; large enough that flows span "
                         "epochs, else warm starts have nothing to carry")
    ap.add_argument("--min-speedup", type=float, default=1.2,
                    help="gate on the aggregate warm-vs-cold speedup (iterations or wall, whichever "
                         "is higher)")
    ap.add_argument("--json-out", default=str(bench_json.DEFAULT_PATH),
                    help="BENCH_solver.json to merge records into "
                         "('' disables)")
    args = ap.parse_args(argv)
    compile_cache.enable()
    records: list[dict] = []
    tc = tw = ic = iw = 0.0
    for t in args.topos.split(","):
        for obj in args.objectives.split(","):
            (c_t, w_t), (c_i, w_i) = bench_cell(t, obj, args, records)
            tc, tw, ic, iw = tc + c_t, tw + w_t, ic + c_i, iw + w_i
    speed_w = tc / tw
    speed_i = ic / max(iw, 1.0)
    print(f"arrival/aggregate,{tw*1e3:.1f},"
          f"{speed_i:.2f}x iters / {speed_w:.2f}x wall warm-vs-cold")
    records.append(bench_json.record(
        "arrival/aggregate", wall_ms=tw * 1e3, iterations=iw,
        derived=f"{speed_i:.2f}x iteration / {speed_w:.2f}x wall "
                f"warm-vs-cold speedup"))
    if args.json_out:
        path = bench_json.update(
            "arrival_bench", records, path=args.json_out,
            args={"seeds": args.seeds, "iters": args.iters, "tol": args.tol,
                  "topos": args.topos, "objectives": args.objectives,
                  "family": args.family,
                  "coflows": args.coflows, "mean_s": args.mean_s,
                  "epoch_s": args.epoch_s, "n_map": args.n_map,
                  "n_reduce": args.n_reduce,
                  "total_gbits": args.total_gbits})
        print(f"arrival/json,0.0,records merged into {path}")
    speed = max(speed_w, speed_i)
    if speed < args.min_speedup:
        print(f"FAIL: aggregate warm-vs-cold speedup {speed:.2f}x "
              f"< {args.min_speedup}x")
        return 1
    print(f"OK: aggregate warm-vs-cold speedup {speed:.2f}x "
          f">= {args.min_speedup}x")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
