"""Compile rehearsals: the main path's device programs, compiled by the
TPU compiler at fat-tree-k16 size for a described v5e that is not
attached.

Nothing runs here, so these tests say nothing about results or times.
They catch what interpret mode and the CPU backend forgive: a kernel
Mosaic refuses, a program that does not fit the chip, a sharded program
without its collective.  The topology is described inside a fixture,
never at import time (only one process may hold the TPU library, and
pytest-xdist workers import every test file); keep every such compile in
this one file.

The LP is the fat-tree-k16 instance of benchmarks/scale_bench.py:
1,024 servers, 20 mappers x 12 reducers, 120 Gbit, seed 0.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import pdhg, solver, timeslot, topology, traffic

V5E_HBM_BYTES = 16e9
ITERS = 1500


@pytest.fixture(scope="module")
def tpu_topology():
    """A described v5e:2x2, with the persistent compilation cache off
    (entries compiled for a chip that is absent cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(tpu_topology):
    return SingleDeviceSharding(tpu_topology.devices[0])


@pytest.fixture(scope="module")
def lp_k16():
    """The fat-tree-k16 routing LP (min-energy), built once."""
    topo = topology.build("fat-tree", k=16)
    pat = traffic.pattern("uniform", n_map=20, n_reduce=12,
                          total_gbits=120.0)
    cf = traffic.generate(topo, pat, seed=0)
    p = timeslot.ScheduleProblem(
        topo, cf, n_slots=timeslot.suggest_n_slots(topo, cf), path_slack=0)
    lp, _ = solver.build_routing_lp(p, "energy")
    assert len(lp.val) > 100_000
    return lp


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _lp_specs(lp, sharding):
    """(c, row, col, val, b, h, xmax) as the solver uploads them."""
    f32, i32 = jnp.float32, jnp.int32
    nnz = len(lp.val)
    return [_spec((lp.n,), f32, sharding), _spec((nnz,), i32, sharding),
            _spec((nnz,), i32, sharding), _spec((nnz,), f32, sharding),
            _spec((lp.m_eq,), f32, sharding),
            _spec((lp.m - lp.m_eq,), f32, sharding),
            _spec((lp.n,), f32, sharding)]


def _fits_one_chip(compiled):
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, ma


def _compile_resume(gp, one_chip, tiles=None):
    """_pdhg_resume (solve_lp's rungs) for `gp`, `tiles` the tile plan's
    arrays or None for the COO operator."""
    f32, i32 = jnp.float32, jnp.int32
    tile_specs = None if tiles is None else tuple(
        _spec(a.shape, i32, one_chip) for a in tiles)
    return solver._pdhg_resume.lower(
        *_lp_specs(gp, one_chip), _spec((gp.n,), f32, one_chip),
        _spec((gp.m,), f32, one_chip), gp.m, gp.n, gp.m_eq, ITERS,
        tile_specs).compile()


def test_pdhg_resume_compiles_for_one_v5e(one_chip, lp_k16):
    """The resumable PDHG of solve_lp's rungs with the COO operator."""
    _fits_one_chip(_compile_resume(lp_k16, one_chip))


def test_tiled_pdhg_resume_compiles_for_one_v5e(one_chip, lp_k16):
    """solve_fast's path on a TPU: the resumable PDHG with the tile
    operator, unbucketed as solve_lp stages it; no scatter inside the
    loop moves one update per nonzero."""
    g = solver.block_stack([lp_k16]).lp
    gp, st = solver._tile_layout([lp_k16], g, False)
    compiled = _compile_resume(gp, one_chip, st.arrays())
    _fits_one_chip(compiled)
    updates = _loop_scatter_updates(compiled)
    assert st.kx.tiles in updates and st.kty.tiles in updates
    assert len(gp.val) not in updates


def _loop_scatter_updates(compiled) -> list[int]:
    """Updates (leading dim of the update operand) of every scatter in
    the optimized HLO whose op sits inside a while loop's body."""
    text = compiled.as_text()
    shapes = dict(re.findall(r"%([\w.\-]+) = \w+\[([\d,]*)\]", text))
    out = []
    for line in text.splitlines():
        m = re.search(r"scatter\(%[\w.\-]+, %[\w.\-]+, %([\w.\-]+)\)",
                      line)
        if m and "/while/body" in line:
            out.append(int(shapes[m.group(1)].split(",")[0]))
    return out


def _compile_adaptive(gp, one_chip, tiles=None):
    """_pdhg_run_adaptive for one instance `gp`, `tiles` the tile plan's
    arrays or None for the COO operator."""
    f32, i32 = jnp.float32, jnp.int32
    chunk = 500
    tile_specs = None if tiles is None else tuple(
        _spec(a.shape, i32, one_chip) for a in tiles)
    return solver._pdhg_run_adaptive.lower(
        *_lp_specs(gp, one_chip), _spec((gp.n,), f32, one_chip),
        _spec((gp.m,), f32, one_chip), _spec((1,), f32, one_chip),
        _spec((gp.n,), i32, one_chip), _spec((gp.m,), i32, one_chip),
        1, gp.m, gp.n, gp.m_eq, chunk, 4 * ITERS // chunk,
        tile_specs).compile()


def test_pdhg_run_adaptive_compiles_for_one_v5e(one_chip, lp_k16):
    """The batched path (sweep, run_online, run_service) with the COO
    operator: the fused adaptive loop over the shape-bucketed stacked
    LP, one scalar scatter-add per nonzero inside the loop."""
    gp, _ = solver._pad_for_buckets(solver.block_stack([lp_k16]).lp)
    compiled = _compile_adaptive(gp, one_chip)
    _fits_one_chip(compiled)
    assert len(gp.val) in _loop_scatter_updates(compiled)


def test_tiled_pdhg_run_adaptive_compiles_for_one_v5e(one_chip, lp_k16):
    """The same loop with the tile operator, as a TPU runs it: it fits
    the chip, and no scatter inside the loop moves one update per
    nonzero (the tiles' segment-sums move one per tile)."""
    g = solver.block_stack([lp_k16]).lp
    gp, st = solver._tile_layout([lp_k16], g, True)
    compiled = _compile_adaptive(gp, one_chip, st.arrays())
    _fits_one_chip(compiled)
    updates = _loop_scatter_updates(compiled)
    assert st.kx.tiles in updates and st.kty.tiles in updates
    assert len(gp.val) not in updates
    assert max(updates) < len(g.val)


def test_sharded_burst_compiles_for_v5e_2x2(tpu_topology, lp_k16):
    """The row-sharded burst (`--mesh 4`, solve_fast(shards=4)) on a
    four-chip mesh: one all-reduce of K^T.y per iteration."""
    op, vecs, ell = solver._pack_sharded(solver.block_stack([lp_k16]).lp, 4)
    mesh = Mesh(np.asarray(tpu_topology.devices[:4]), ("shard",))
    args = [*vecs, *ell, jnp.zeros(op.n_pad), jnp.zeros(op.m_pad)]
    # x-side arrays (c, tau, xmax, x0) are replicated, the rest split by
    # rows — core.pdhg._sharded_burst_fn's in_specs
    replicated = {0, 1, 2, 10}
    specs = [_spec(a.shape, a.dtype,
                   NamedSharding(mesh, P() if i in replicated
                                 else P("shard")))
             for i, a in enumerate(args)]
    fn = pdhg._sharded_burst_fn(mesh, "shard", op.row_meta, op.col_meta,
                                ITERS)
    compiled = fn.lower(*specs).compile()
    assert "all-reduce" in compiled.as_text()
    _fits_one_chip(compiled)
