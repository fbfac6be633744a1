"""Benchmark harness: one benchmark per paper table/figure + framework
perf tables.  Prints ``name,us_per_call,derived`` CSV.

  paper       figs 7-16 + rate sweep (lexicographic oracle + fast path)
  table1      AWGR wavelength-assignment MILP   (--full only, ~90 s)
  gap         fast-path vs oracle optimality/time table
  fabric      co-flow collective plans vs naive single-axis
  kernels     Pallas kernel wall-times (interpret mode -> call overhead)

Default sizes are reduced for CI; ``--full`` runs paper-scale (10x6
tasks, 1-120 Gbit, exact Table I cell).
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np


def bench_paper(full: bool):
    from . import paper_schedule as ps
    if full:
        vols = (1.0, 10.0, 30.0, 60.0, 120.0)
        kw = dict(n_map=10, n_reduce=6, time_limit=300.0)
        vols_skew = (10.0, 30.0, 60.0)
    else:
        vols = (2.0, 8.0)
        kw = dict(n_map=4, n_reduce=3, time_limit=120.0)
        vols_skew = (8.0,)
    ps.print_rows(ps.figs_7_to_10(volumes=vols, **kw), "figs7-10")
    ps.print_rows(ps.figs_11_to_14(volumes=vols_skew, **kw), "figs11-14")
    ps.print_rows(ps.figs_15_16(volumes=vols, **kw), "figs15-16")
    ps.print_rows(ps.rate_comparison(volumes=vols[-1:], **kw), "rate")
    if full:
        ps.table_1()


def bench_gap(full: bool):
    """Fast path vs oracle: optimality gap and speed."""
    from repro.core import oracle, solver, timeslot, topology, traffic
    topos = ["spine-leaf", "fat-tree", "bcube", "dcell", "pon3", "pon5"]
    for name in topos:
        topo = topology.build(name)
        cf = traffic.shuffle_traffic(topo, 8.0, n_map=4, n_reduce=3, seed=1)
        prob = timeslot.ScheduleProblem(topo, cf, n_slots=6, rho=8.0)
        for obj in ("time", "energy"):
            t0 = time.time()
            om = oracle.solve_lexico(prob, obj, time_limit=180).metrics
            t_o = time.time() - t0
            t0 = time.time()
            fm = solver.solve_fast(prob, obj, iters=4000).metrics
            t_f = time.time() - t0
            opt = om.energy_j if obj == "energy" else om.completion_s
            got = fm.energy_j if obj == "energy" else fm.completion_s
            gap = (got - opt) / max(opt, 1e-9)
            print(f"gap/{name}/{obj},{t_f*1e6:.0f},"
                  f"oracle={opt:.3f};fast={got:.3f};gap={gap:.3f};"
                  f"oracle_s={t_o:.1f};speedup={t_o/max(t_f,1e-9):.1f}x")


def bench_baselines(full: bool):
    """Varys-style comparison (paper §I cites 3.66x/5.65x over fair/FIFO):
    co-flow-aware optimum vs FIFO / fair-sharing / SEBF in OUR model."""
    from repro.core import heuristics, oracle, timeslot, topology, traffic
    for name in ("spine-leaf", "fat-tree", "pon3"):
        topo = topology.build(name)
        cf = traffic.shuffle_traffic(topo, 16.0, n_map=4, n_reduce=3, seed=2)
        prob = timeslot.ScheduleProblem(topo, cf, n_slots=6, rho=8.0)
        t0 = time.time()
        m_opt = oracle.solve_lexico(prob, "time", time_limit=180).metrics
        dt = time.time() - t0
        out = {"coflow_opt": m_opt.completion_s}
        for rule in ("fifo", "fair", "sebf"):
            m = timeslot.evaluate(prob, heuristics.schedule(prob, rule))
            out[rule] = m.completion_s
        d = ";".join(f"{k}={v:.3f}" for k, v in out.items())
        d += f";fifo_speedup={out['fifo']/out['coflow_opt']:.2f}x"
        print(f"baselines/{name},{dt*1e6:.0f},{d}")


def bench_fabric(full: bool):
    from repro.core import fabric
    spec = fabric.v5e_fabric()
    layers = [(f"l{i}", 110e6) for i in range(32)]
    for bucket_mb, slots in ((64, 16), (256, 12)):
        buckets = fabric.grad_buckets_for(layers, bucket_bytes=bucket_mb * 1e6,
                                          data_axes=(0, 1))
        t0 = time.time()
        plan = fabric.plan_collectives(spec, buckets, n_slots=slots)
        dt = time.time() - t0
        naive = fabric.plan_collectives(
            spec, [fabric.Bucket(b.name, b.bytes, (0,), b.release_slot)
                   for b in buckets], n_slots=slots)
        print(f"fabric/bucket{bucket_mb}MB,{dt*1e6:.0f},"
              f"makespan={plan.completion_s*1e3:.2f}ms;"
              f"naive={naive.completion_s*1e3:.2f}ms;"
              f"speedup={naive.completion_s/plan.completion_s:.2f}x")


def bench_kernels(full: bool):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    key = jax.random.PRNGKey(0)
    for (S, H, Hkv, hd) in [(512, 8, 2, 128), (2048, 8, 8, 128)]:
        q = jax.random.normal(key, (1, S, H, hd), jnp.float32)
        k = jax.random.normal(key, (1, S, Hkv, hd), jnp.float32)
        v = jax.random.normal(key, (1, S, Hkv, hd), jnp.float32)
        out = ops.flash_attention(q, k, v)     # compile
        out.block_until_ready()
        t0 = time.time()
        n = 3
        for _ in range(n):
            out = ops.flash_attention(q, k, v)
        out.block_until_ready()
        dt = (time.time() - t0) / n
        print(f"kernels/flash_attn_S{S},{dt*1e6:.0f},"
              f"platform={jax.default_backend()};"
              f"ref_validated=tests/test_kernels.py")
    a = jax.nn.sigmoid(jax.random.normal(key, (4, 1024, 512)))
    b = jax.random.normal(key, (4, 1024, 512))
    h, _ = ops.rglru(a, b)
    h.block_until_ready()
    t0 = time.time()
    h, _ = ops.rglru(a, b)
    h.block_until_ready()
    print(f"kernels/rglru_1024x512,{(time.time()-t0)*1e6:.0f},"
          f"platform={jax.default_backend()}")


BENCHES = {
    "paper": bench_paper,
    "baselines": bench_baselines,
    "gap": bench_gap,
    "fabric": bench_fabric,
    "kernels": bench_kernels,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="all",
                    help=f"comma list of {sorted(BENCHES)}")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slow)")
    args = ap.parse_args()
    from repro import compile_cache
    compile_cache.enable()
    names = list(BENCHES) if args.only == "all" else args.only.split(",")
    print("name,us_per_call,derived")
    for n in names:
        BENCHES[n](args.full)


if __name__ == "__main__":
    main()
