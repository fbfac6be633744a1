"""Differential harness: sharded PDHG vs the plain single-device solve.

Each test spawns a subprocess with 4 fake CPU devices
(XLA_FLAGS=--xla_force_host_platform_device_count=4) and solves one of
the six paper topologies through the fast path at shards in {1, 2, 4}.
The subprocess prints the paper metrics plus a SHA-256 over the packed
schedule's raw psi bytes; the parent compares against the plain
single-device solve in THIS process:

  * shards=1 must be BITWISE identical (same psi digest) — the shards=1
    route never enters shard_map, so adding devices to the process must
    not perturb a single bit of the plain solve;
  * shards=2 and shards=4 must agree on every metric to rtol 1e-4 —
    the row-sharded blocked-ELL operator and its psum(K^T y) sum in
    another float order than the COO operator, so exact equality is not
    guaranteed, closeness is.

Subprocesses are required because device count is fixed at jax import
time and the main pytest process must keep its real 1-device view.
"""
import hashlib
import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import solver, timeslot, topology, traffic

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")

PAPER_TOPOS = ["fat-tree", "spine-leaf", "bcube", "dcell", "pon3", "pon5"]
ITERS = 1200

_WORKER = """
    import hashlib
    import numpy as np
    from repro.core import solver, timeslot, topology, traffic

    topo = topology.build({topo_name!r})
    pat = traffic.pattern("uniform", n_map=4, n_reduce=3)
    cf = traffic.generate(topo, pat, seed=0)
    p = timeslot.ScheduleProblem(
        topo, cf, n_slots=timeslot.suggest_n_slots(topo, cf))
    for shards in (1, 2, 4):
        r = solver.solve_fast(p, "energy", iters={iters}, shards=shards)
        psi = np.ascontiguousarray(r.metrics.psi, dtype=np.float64)
        digest = hashlib.sha256(psi.tobytes()).hexdigest()
        print(f"RESULT shards={{shards}} "
              f"energy={{r.metrics.energy_j!r}} "
              f"completion={{r.metrics.completion_s!r}} "
              f"feasible={{r.metrics.feasible}} "
              f"psi={{digest}}")
"""


def run_worker(topo_name: str, devices: int = 4) -> dict[int, dict]:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    code = textwrap.dedent(_WORKER.format(topo_name=topo_name, iters=ITERS))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    out: dict[int, dict] = {}
    for line in r.stdout.splitlines():
        if not line.startswith("RESULT "):
            continue
        kv = dict(f.split("=", 1) for f in line.split()[1:])
        out[int(kv["shards"])] = dict(
            energy=float(kv["energy"]), completion=float(kv["completion"]),
            feasible=kv["feasible"] == "True", psi=kv["psi"])
    assert set(out) == {1, 2, 4}, r.stdout
    return out


def _reference(topo_name: str):
    """The plain single-device solve in the main (1-device) process."""
    topo = topology.build(topo_name)
    pat = traffic.pattern("uniform", n_map=4, n_reduce=3)
    cf = traffic.generate(topo, pat, seed=0)
    p = timeslot.ScheduleProblem(
        topo, cf, n_slots=timeslot.suggest_n_slots(topo, cf))
    r = solver.solve_fast(p, "energy", iters=ITERS)
    psi = np.ascontiguousarray(r.metrics.psi, dtype=np.float64)
    return r, hashlib.sha256(psi.tobytes()).hexdigest()


@pytest.mark.parametrize("topo_name", PAPER_TOPOS)
def test_sharded_matches_single_device(topo_name):
    ref, ref_digest = _reference(topo_name)
    got = run_worker(topo_name)

    # mesh=1 in a multi-device process is the plain solve — bitwise
    assert got[1]["psi"] == ref_digest, \
        f"{topo_name}: shards=1 schedule diverged from the plain solve"
    assert got[1]["energy"] == ref.metrics.energy_j
    assert got[1]["completion"] == ref.metrics.completion_s

    for s in (2, 4):
        assert got[s]["feasible"] == ref.metrics.feasible
        assert got[s]["energy"] == pytest.approx(
            ref.metrics.energy_j, rel=1e-4), f"{topo_name} shards={s}"
        assert got[s]["completion"] == pytest.approx(
            ref.metrics.completion_s, rel=1e-4), f"{topo_name} shards={s}"


def test_sharded_lp_iterates_close_to_single_device():
    """Below the schedule layer: raw LP solutions agree to 1e-4."""
    topo = topology.build("spine-leaf")
    pat = traffic.pattern("uniform", n_map=4, n_reduce=3)
    cf = traffic.generate(topo, pat, seed=0)
    p = timeslot.ScheduleProblem(
        topo, cf, n_slots=timeslot.suggest_n_slots(topo, cf))
    lp, _ = solver.build_routing_lp(p, "energy")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    code = textwrap.dedent("""
        import numpy as np
        from repro.core import solver, timeslot, topology, traffic
        topo = topology.build("spine-leaf")
        pat = traffic.pattern("uniform", n_map=4, n_reduce=3)
        cf = traffic.generate(topo, pat, seed=0)
        p = timeslot.ScheduleProblem(
            topo, cf, n_slots=timeslot.suggest_n_slots(topo, cf))
        lp, _ = solver.build_routing_lp(p, "energy")
        xs = [solver.solve_lp(lp, iters=600, shards=s).x
              for s in (1, 2, 4)]
        print("MAXDIFF", max(float(np.abs(x - xs[0]).max())
                             for x in xs[1:]))
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("MAXDIFF")][0]
    scale = max(1.0, float(np.max(np.abs(
        solver.solve_lp(lp, iters=600).x))))
    assert float(line.split()[1]) <= 1e-4 * scale


# -------- in-process coverage of the sharded machinery (1 device is a
# -------- valid mesh: psum over a 1-device axis is the exact identity)
def test_sharded_driver_on_one_device_mesh_matches_plain_ell():
    """The sharded driver on a one-device mesh is the shared burst over
    the blocked-ELL pair, bit for bit."""
    import jax.numpy as jnp

    from repro.core import pdhg
    from repro.kernels import pdhg_spmv

    topo = topology.build("spine-leaf")
    pat = traffic.pattern("uniform", n_map=4, n_reduce=3)
    cf = traffic.generate(topo, pat, seed=0)
    p = timeslot.ScheduleProblem(
        topo, cf, n_slots=timeslot.suggest_n_slots(topo, cf))
    lp, _ = solver.build_routing_lp(p, "energy")
    g = solver.block_stack([lp]).lp
    st = solver._stage_sharded(g, 1, 1)
    shard_x, shard_y = st.unpad(*st.run(*st.place(np.zeros(lp.n),
                                                  np.zeros(lp.m)),
                                        400)[:2])
    op, vecs, ell = solver._pack_sharded(g, 1)
    c, tau, xmax, q, sig, ub = vecs
    Kx, KTy = pdhg_spmv.ell_pair(*ell, op.row_meta, op.col_meta)
    x, y = pdhg.burst(Kx, KTy, c, tau, sig, q, xmax, ub,
                      jnp.zeros(op.n_pad), jnp.zeros(op.m_pad), 400)
    np.testing.assert_array_equal(shard_x, np.asarray(x)[:lp.n])
    np.testing.assert_array_equal(shard_y, np.asarray(y)[:lp.m])


@pytest.mark.parametrize("objective", ["energy", "time"])
def test_pack_sharded_vectors_match_float64_formulas(objective):
    """The row-sharded pack's vectors come from core.pdhg (float32 sums
    on the device); on the fat-tree-k16 LP they equal the float64 host
    formulas the pack used before, rounded once to float32.  K's entries
    are small integers, so every row and column sum is exact in float32
    and 1/sum rounds the same from either width."""
    topo = topology.build("fat-tree", k=16)
    pat = traffic.pattern("uniform", n_map=20, n_reduce=12,
                          total_gbits=120.0)
    cf = traffic.generate(topo, pat, seed=0)
    p = timeslot.ScheduleProblem(
        topo, cf, n_slots=timeslot.suggest_n_slots(topo, cf), path_slack=0)
    lp, _ = solver.build_routing_lp(p, objective)
    assert len(lp.val) > 100_000
    op, vecs, _ = solver._pack_sharded(solver.block_stack([lp]).lp, 4)
    n, m = lp.n, lp.m
    abs_val = np.abs(lp.val)
    col_sum, row_sum = np.zeros(n), np.zeros(m)
    np.add.at(col_sum, lp.col, abs_val)
    np.add.at(row_sum, lp.row, abs_val)
    cscale = max(float(np.abs(lp.c).max(initial=0.0)), 1e-12)
    want = (lp.c / cscale, 1.0 / np.maximum(col_sum, 1e-12),
            np.where(np.isfinite(lp.xmax), lp.xmax, 1e12),
            np.concatenate([lp.b, lp.h]), 1.0 / np.maximum(row_sum, 1e-12))
    pads = (op.n_pad - n,) * 3 + (op.m_pad - m,) * 2
    for name, got, w, pad in zip(("c", "tau", "xmax", "q", "sig"), vecs,
                                 want, pads):
        np.testing.assert_array_equal(
            np.asarray(got), np.pad(w.astype(np.float32), (0, pad)),
            err_msg=name)
    np.testing.assert_array_equal(
        np.asarray(vecs[5]),
        np.pad(np.arange(m) >= lp.m_eq, (0, op.m_pad - m),
               constant_values=True))


def _dense_from_sharded(op):
    """Rebuild the dense matrix from the shard-major row-direction pack."""
    from repro.kernels import pdhg_spmv as ps
    offsets, widths, bm, m_loc = op.row_meta
    dense = np.zeros((op.m_pad, op.n))
    size = len(op.row_idx) // op.shards
    for s in range(op.shards):
        idx = op.row_idx[s * size:(s + 1) * size]
        val = op.row_val[s * size:(s + 1) * size]
        for b, (off, w) in enumerate(zip(offsets, widths)):
            blk_i = idx[off:off + bm * w].reshape(bm, w)
            blk_v = val[off:off + bm * w].reshape(bm, w)
            for r in range(bm):
                g = s * m_loc + b * bm + r
                if g < dense.shape[0]:
                    np.add.at(dense[g], blk_i[r], blk_v[r])
    return dense


def test_ell_pack_sharded_reconstructs_operator():
    from repro.kernels import pdhg_spmv as ps
    rng = np.random.default_rng(0)
    m, n, nnz = 37, 23, 200
    row = rng.integers(0, m, nnz)
    col = rng.integers(0, n, nnz)
    val = rng.standard_normal(nnz).astype(np.float32)
    ref = np.zeros((m, n))
    np.add.at(ref, (row, col), val)
    for shards in (1, 2, 4):
        op = ps.ell_pack_sharded(row, col, val, m, n, shards)
        assert op.m_pad == shards * op.m_loc
        assert op.m_pad >= m and op.m_loc % 8 == 0
        dense = _dense_from_sharded(op)
        np.testing.assert_allclose(dense[:m], ref, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(dense[m:], 0.0)
