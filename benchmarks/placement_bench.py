"""Benchmark: placement-search evaluator throughput + search quality.

The placement search (repro.search) prices every candidate generation
with ONE stacked `core.solver.solve_fast_batch` dispatch.  Placement
changes flow endpoints, so per-candidate structure-cache hits are
impossible — batching is the only throughput lever, and this benchmark
quantifies it:

  * **batch** — evaluations/sec when a whole population is scored in
    one stacked dispatch (the search's inner loop);
  * **loop**  — evaluations/sec when the same candidates are scored one
    `solve_fast` call at a time (what a naive outer loop would do);
  * **search** — a small SA run's win rate against random placements
    (the optimized placement must beat a fresh random sample) and its
    gain over the best fixed spread/packed/local placement, certificate
    checked.

Candidate LP construction is identical work on both paths (a placement
changes endpoints, so both must rebuild), so the candidate problems are
built once, untimed, and the two paths are timed on the solve alone —
the same methodology as sweep_bench: both sides are timed cold,
including XLA compilation, because that is the wall a fresh search cell
pays (per-topology x n_slots x population shapes compile once and are
then reused by every generation), and both solve identical candidate
lists at identical PDHG budgets.  The batch side wins on dispatch and
compile amortization — one stacked program versus per-candidate
dispatches plus the host-side restart ladder — so the margin grows with
--population and shrinks as single instances saturate the device.

Run:  PYTHONPATH=src python benchmarks/placement_bench.py [--topos ...]
Prints ``name,ms,derived`` CSV rows and merges records into
BENCH_solver.json (schema: benchmarks/bench_json.py).  As in
sweep_bench, the gate applies to the aggregate over all cells: it
passes if batched evaluation reaches --min-speedup x the per-candidate loop's aggregate
throughput (--min-speedup 0 = report-only, the CI mode).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np

try:
    import bench_json                      # script: python benchmarks/...
except ImportError:                        # module: python -m benchmarks....
    from benchmarks import bench_json
from repro import compile_cache, search
from repro.core import solver, timeslot, topology, traffic


def _candidates(topo, pat, n: int, seed: int):
    """n deterministic random-spread placements + the pinned size vector."""
    rng = np.random.default_rng([seed, search.optimize.SEARCH_TAG, 7])
    map_out = traffic._map_outputs(pat, rng.spawn(1)[0])
    spread = dataclasses.replace(pat, placement="spread")
    return [traffic.sample_placement(topo, spread, rng)
            for _ in range(n)], map_out


def bench_cell(topo_name: str, args, records: list[dict]
               ) -> tuple[float, float]:
    """One topology cell; returns (loop_s, batch_s) walls."""
    topo = topology.build(topo_name)
    pat = traffic.pattern("uniform", n_map=args.n_map,
                          n_reduce=args.n_reduce,
                          total_gbits=args.total_gbits)
    cfg = search.SearchConfig(iters=args.iters, seed=args.seed)
    pls, map_out = _candidates(topo, pat, args.population, args.seed)
    n_slots = max(timeslot.suggest_n_slots(
        topo, traffic.generate_from_placement(topo, pat, pl,
                                              map_out=map_out))
        for pl in pls)
    cell = topo_name
    # one candidate generation's problems, built once (untimed): the
    # build is identical work on both evaluation paths
    problems = [timeslot.ScheduleProblem(
        topo, traffic.generate_from_placement(topo, pat, pl,
                                              map_out=map_out),
        n_slots=n_slots, rho=cfg.rho, path_slack=cfg.path_slack)
        for pl in pls]

    def run_batch():
        return solver.solve_fast_batch(problems, args.objective,
                                       iters=cfg.iters, tol=cfg.tol)

    def run_loop():
        return [solver.solve_fast(p, args.objective, iters=cfg.iters,
                                  tol=cfg.tol)
                for p in problems]

    # cold, loop first (sweep_bench order): both sides include the
    # compilation a fresh search cell pays
    t0 = time.perf_counter()
    run_loop()
    t_loop = time.perf_counter() - t0
    t0 = time.perf_counter()
    batch = run_batch()
    t_batch = time.perf_counter() - t0
    n = len(pls)
    eps_batch, eps_loop = n / t_batch, n / t_loop
    ratio = eps_batch / eps_loop
    scores = [search.optimize._score(args.objective, r) for r in batch]
    print(f"placement/{cell}/batch,{t_batch*1e3:.1f},"
          f"{eps_batch:.1f} evals/s over {n} candidates")
    print(f"placement/{cell}/loop,{t_loop*1e3:.1f},"
          f"{eps_loop:.1f} evals/s ({ratio:.1f}x slower than batch)")
    records.append(bench_json.record(
        f"placement/{cell}/batch", topology=topo_name,
        objective=args.objective, wall_ms=t_batch * 1e3,
        derived=f"{eps_batch:.1f} evals/s, {n} candidates, "
                f"{ratio:.2f}x vs loop"))
    records.append(bench_json.record(
        f"placement/{cell}/loop", topology=topo_name,
        objective=args.objective, wall_ms=t_loop * 1e3,
        derived=f"{eps_loop:.1f} evals/s (per-candidate solve_fast)"))

    # search quality: a small SA run must beat fresh random placements
    res = search.optimize_placement(
        topo, pat, args.objective, method="sa",
        cfg=dataclasses.replace(cfg, generations=args.generations,
                                population=args.population))
    res.best.result.certificate.assert_ok(f"search {cell}")
    wins = sum(res.best.score < s - 1e-12 for s in scores)
    win_pct = wins / max(len(scores), 1)
    print(f"placement/{cell}/search,0.0,"
          f"win={win_pct:.0%} vs {len(scores)} random, "
          f"gain={res.gain:.3f}x vs best fixed, cert=ok")
    records.append(bench_json.record(
        f"placement/{cell}/search", topology=topo_name,
        objective=args.objective, wall_ms=0.0,
        derived=f"win={win_pct:.0%} vs {len(scores)} random, "
                f"gain={res.gain:.3f}x, cert=ok"))
    return t_loop, t_batch


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--topos", default="spine-leaf,pon3")
    ap.add_argument("--objective", default="energy",
                    choices=("energy", "time", "fair"))
    ap.add_argument("--iters", type=int, default=1500)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n-map", type=int, default=4)
    ap.add_argument("--n-reduce", type=int, default=3)
    ap.add_argument("--total-gbits", type=float, default=8.0)
    ap.add_argument("--population", type=int, default=16,
                    help="candidates per evaluation batch")
    ap.add_argument("--generations", type=int, default=4,
                    help="SA generations for the quality row")
    ap.add_argument("--min-speedup", type=float, default=2.0,
                    help="batched evaluation must reach this multiple of "
                         "the per-candidate loop's aggregate throughput "
                         "(0 = report-only)")
    ap.add_argument("--json-out", default=str(bench_json.DEFAULT_PATH),
                    help="BENCH_solver.json to merge records into "
                         "('' disables)")
    args = ap.parse_args(argv)
    compile_cache.enable()
    records: list[dict] = []
    agg_loop = agg_batch = 0.0
    for t in args.topos.split(","):
        t_loop, t_batch = bench_cell(t, args, records)
        agg_loop += t_loop
        agg_batch += t_batch
    agg = agg_loop / agg_batch
    print(f"placement/aggregate,{agg_batch*1e3:.1f},"
          f"{agg:.2f}x speedup vs per-candidate loop")
    records.append(bench_json.record(
        "placement/aggregate", topology="all", objective=args.objective,
        wall_ms=agg_batch * 1e3,
        derived=f"{agg:.2f}x speedup vs per-candidate loop"))
    if args.json_out:
        path = bench_json.update(
            "placement_bench", records, path=args.json_out,
            args={"topos": args.topos, "objective": args.objective,
                  "iters": args.iters, "seed": args.seed,
                  "n_map": args.n_map, "n_reduce": args.n_reduce,
                  "total_gbits": args.total_gbits,
                  "population": args.population,
                  "generations": args.generations})
        print(f"placement/json,0.0,records merged into {path}")
    if args.min_speedup <= 0:       # report-only (CI): no gating
        print("OK: report-only (--min-speedup 0)")
        return 0
    if agg < args.min_speedup:
        print(f"FAIL: batched evaluation only {agg:.2f}x the "
              f"per-candidate loop (< {args.min_speedup}x)")
        return 1
    print(f"OK: batched evaluation {agg:.2f}x the per-candidate loop "
          f"aggregate (gate {args.min_speedup}x)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
