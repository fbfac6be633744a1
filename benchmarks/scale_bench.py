"""Benchmark: solver scaling over large-topology instances, per shard
count.

Each configured size builds one routing-LP instance on a parameterized
large topology (fat-tree k in {8,16}, multi-level DCell, multi-cell
PON — core.topology's generator families), solves it end-to-end through
the fast path (LP -> PDHG -> slot packing -> exact re-scoring), and
certifies the packed schedule with core.verify.check_schedule before
any timing counts.  Per size the rows cross the shard count: a row-block
partition of the PDHG operator across N devices
(runtime.sharding.solver_mesh; N = 1 is the single-device solve).  On
CPU the devices come from XLA_FLAGS=--xla_force_host_platform_device_count,
which this script sets itself BEFORE importing jax.  The flagship `fat-tree-k16` size is a k=16
fat-tree (1024 servers, 1344 vertices) whose routing LP exceeds 1e5
nonzeros — the scale gate `--min-nnz` asserts it.

Rows report wall-clock (build+solve+pack+certify), mean PDHG
iterations, and the process peak RSS after the run (resource.getrusage
ru_maxrss — cumulative high-water mark, so sizes should be read
smallest-first within one invocation).

On CPU, treat sharded runs as correctness/overhead measurements (host
"devices" share the same silicon).

Run:  PYTHONPATH=src python benchmarks/scale_bench.py \
          [--sizes spine-leaf,fat-tree-k8] [--shards 1,4]
Prints ``name,ms,derived`` CSV rows and merges machine-readable records
into BENCH_solver.json at the repo root (schema: benchmarks/bench_json.py).
"""
from __future__ import annotations

import argparse
import os
import resource
import sys
import time

# (topology builder name, builder kwargs, traffic kwargs, path_slack)
SIZES: dict[str, tuple[str, dict, dict, int | None]] = {
    "spine-leaf": ("spine-leaf", {},
                   dict(n_map=10, n_reduce=6, total_gbits=30.0), 2),
    "fat-tree-k8": ("fat-tree", dict(k=8),
                    dict(n_map=12, n_reduce=8, total_gbits=60.0), 0),
    "fat-tree-k16": ("fat-tree", dict(k=16),
                     dict(n_map=20, n_reduce=12, total_gbits=120.0), 0),
    "dcell-multi": ("dcell-multi", dict(n=3, levels=2),
                    dict(n_map=12, n_reduce=8, total_gbits=60.0), 0),
    "pon-multicell": ("pon-multicell", dict(n_cells=4),
                      dict(n_map=12, n_reduce=8, total_gbits=60.0), None),
}


def peak_rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def bench_size(size: str, shards: int, iters: int, tol: float,
               records: list[dict],
               min_nnz: dict[str, int]) -> None:
    from repro.core import solver, timeslot, topology, traffic, verify

    try:
        import bench_json
    except ImportError:
        from benchmarks import bench_json

    topo_name, topo_kw, pat_kw, slack = SIZES[size]
    topo = topology.build(topo_name, **topo_kw)
    pat = traffic.pattern("uniform", **pat_kw)
    cf = traffic.generate(topo, pat, seed=0)
    p = timeslot.ScheduleProblem(topo, cf,
                                 n_slots=timeslot.suggest_n_slots(topo, cf),
                                 path_slack=slack)
    lp, _ = solver.build_routing_lp(p, "energy")
    nnz = len(lp.val)
    floor = min_nnz.get(size, 0)
    assert nnz >= floor, (f"{size}: LP has {nnz} nonzeros, "
                          f"expected >= {floor}")

    t0 = time.perf_counter()
    r = solver.solve_fast(p, "energy", iters=iters, tol=tol, shards=shards)
    cert = verify.check_schedule(p, r.schedule)
    wall = time.perf_counter() - t0
    assert cert.ok, (size, shards, cert)

    name = f"scale/{size}/s{shards}"
    derived = (f"V={topo.n_vertices} E={topo.n_edges} nnz={nnz} "
               f"cert=ok peak={peak_rss_mb():.0f}MB")
    print(f"{name},{wall * 1e3:.1f},{derived}")
    records.append(bench_json.record(
        name, topology=topo.name, objective="energy", wall_ms=wall * 1e3, iterations=float(r.iterations),
        derived=derived))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="spine-leaf,fat-tree-k8",
                    help=f"comma list from {','.join(SIZES)} "
                         "(read peak-RSS smallest-first)")
    ap.add_argument("--shards", default="1",
                    help="comma list of device counts (e.g. 1,4); counts "
                         "> 1 force host devices via XLA_FLAGS before jax "
                         "loads")
    ap.add_argument("--iters", type=int, default=1500)
    ap.add_argument("--tol", type=float, default=2e-3,
                    help="LP tolerance (schedules are re-scored and "
                         "certified exactly regardless)")
    ap.add_argument("--min-nnz", type=int, default=100_000,
                    help="scale gate: the fat-tree-k16 LP must have at "
                         "least this many nonzeros (0 disables)")
    ap.add_argument("--json-out", default="",
                    help="BENCH_solver.json to merge records into; "
                         "default resolves next to this script "
                         "('' -> default, 'none' disables)")
    args = ap.parse_args(argv)

    shard_counts = [int(s) for s in args.shards.split(",") if s.strip()]
    n_dev = max(shard_counts)
    if n_dev > 1 and "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # must happen before jax initializes — re-exec with the flag
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_dev}").strip()
        os.execv(sys.executable, [sys.executable] + sys.argv)

    from repro import compile_cache
    compile_cache.enable()

    try:
        import bench_json
    except ImportError:
        from benchmarks import bench_json

    sizes = [s.strip() for s in args.sizes.split(",") if s.strip()]
    min_nnz = {"fat-tree-k16": args.min_nnz} if args.min_nnz else {}
    for s in sizes:
        if s not in SIZES:
            ap.error(f"unknown size {s!r}; have {','.join(SIZES)}")

    records: list[dict] = []
    for size in sizes:
        for shards in shard_counts:
            bench_size(size, shards, args.iters, args.tol, records,
                       min_nnz)

    if args.json_out != "none":
        path = args.json_out or bench_json.DEFAULT_PATH
        # unlike the cheap single-invocation benches, sizes here cost
        # minutes each — merge per-row so a partial re-run refreshes
        # only the rows it regenerated and keeps the rest
        records = _merge_previous(path, records)
        path = bench_json.update(
            "scale_bench", records, path=path,
            args={"sizes": args.sizes, "shards": args.shards,
                  "iters": args.iters, "tol": args.tol})
        print(f"scale/json,0.0,records merged into {path}")
    return 0


def _merge_previous(path, records: list[dict]) -> list[dict]:
    import json
    import pathlib
    try:
        doc = json.loads(pathlib.Path(path).read_text())
        prev = doc["benches"]["scale_bench"]["records"]
    except (OSError, ValueError, KeyError):
        return records
    fresh = {r["name"] for r in records}
    return [r for r in prev if r.get("name") not in fresh] + records


if __name__ == "__main__":
    raise SystemExit(main())
