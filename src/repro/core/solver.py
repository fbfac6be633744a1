"""JAX fast-path solver: PDHG routing LP + slot packing + re-solves.

The exact oracle (core.oracle) is branch-and-cut and cannot run inside a
training loop.  The production path decomposes the paper's time-expanded
MILP into:

  1. a *routing LP* over (flow, edge, wavelength) volumes for the whole
     horizon — solved with diagonally-preconditioned PDHG
     (Chambolle-Pock, core.pdhg) written entirely in JAX.  Many instances
     solve in one dispatch: block-diagonal stacking with a fused
     in-graph adaptive convergence loop (solve_lp_batch /
     solve_fast_batch);
  2. a *temporal packing* pass that quantizes the fractional routing into
     the paper's discrete slots (greedy earliest-slot water-filling, with
     the PON3 one-wavelength-per-server-per-slot rule honoured);
  3. exact re-evaluation with core.timeslot.evaluate — so reported E and M
     are always true paper-model numbers, never LP estimates.

For the completion-time objective the LP solves `min theta` with
capacities scaled by theta (the continuous-time lower bound on M); for
energy it minimizes the true linear energy terms (NIC offload J/Gbit)
plus a path-length regularizer, leaving the ON/OFF concentration to the
packing stage.

Incremental re-solves (core.failures): because a degraded topology keeps
the healthy instance's device/edge indexing, a healthy solve's PDHG
state projects onto the degraded LP — surviving routing paths keep their
volume, duals map row-by-row — and `resolve_incremental` /
`solve_fast_ensemble(warm=...)` restart PDHG from that state instead of
from zero.

Problem construction is itself a fast path (docs/SOLVER.md §8): LP
assembly is vectorized index arithmetic, constraint sparsity and
RoutingIndex are cached across solves keyed by a structure hash
(ProblemStructure; arrival epochs, horizon retries, and scaled
degradations rebuild nothing — build_cache_stats() counts hits), the
tile layout is plan-cached per sparsity pattern, and batched/
warm dispatches are padded onto shape buckets so compiled executables
are reused across grid cells instead of recompiled per exact shape.

Units follow the paper throughout: flow sizes and shipped volumes in
Gbits, link/egress/ingress rates in Gbps, slot duration and completion
time in seconds, energy in Joules.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import trace
from . import pdhg, tile_spmv
from .timeslot import Metrics, ScheduleProblem, evaluate

Array = jax.Array


# ---------------------------------------------------------------------------
# Structured LP + PDHG
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StructuredLP:
    """min c.x  s.t.  K_eq x = b,  K_ub x <= h,  0 <= x <= xmax.

    K is stored in COO; the eq block occupies rows [0, m_eq)."""

    c: np.ndarray
    row: np.ndarray
    col: np.ndarray
    val: np.ndarray
    b: np.ndarray
    h: np.ndarray
    xmax: np.ndarray

    @property
    def n(self) -> int:
        return len(self.c)

    @property
    def m_eq(self) -> int:
        return len(self.b)

    @property
    def m(self) -> int:
        return len(self.b) + len(self.h)


@dataclasses.dataclass
class PDHGResult:
    x: np.ndarray
    primal_residual: float
    duality_gap_rel: float
    iterations: int
    # final dual iterate (rows ordered [equalities; inequalities]) — kept so
    # incremental re-solves can warm-start both sides of the saddle point
    y: np.ndarray | None = None


def _operator(row, col, val, m, n, tiles):
    """The COO pair, or with `tiles` (core.tile_spmv.StackedTiles.arrays)
    the tile pair, whose tables are filled here, outside any loop."""
    if tiles is None:
        return pdhg.coo_pair(row, col, val, m, n)
    return tile_spmv.operator_pair(tiles, val, m, n)


def _pdhg_kernel_state(c, row, col, val, b, h, xmax, x0, y0,
                       m, n, m_eq, iters, tiles=None):
    """`iters` PDHG iterations (core.pdhg) from (x0, y0), resumable:
    returns the final (x, y, primal, gap) so restarts continue the
    trajectory instead of re-running from zero."""
    q, tau, sig, ub = pdhg.preconditioners(row, col, val, b, h, m, n, m_eq)
    Kx, KTy = _operator(row, col, val, m, n, tiles)
    x, y = pdhg.burst(Kx, KTy, c, tau, sig, q, xmax, ub, x0, y0, iters)
    primal = pdhg.residual(Kx, q, ub, x).max(initial=0.0)
    # crude gap proxy: |c.x + q.y_clamped| / (1+|c.x|)
    obj = c @ x
    gap = jnp.abs(obj + q @ y) / (1.0 + jnp.abs(obj))
    return x, y, primal, gap


_pdhg_resume = functools.partial(jax.jit, static_argnames=(
    "m", "n", "m_eq", "iters"))(_pdhg_kernel_state)


def _check_shards(shards: int) -> None:
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")


def _solve_lp_trivial(lp: StructuredLP) -> PDHGResult:
    """Closed-form solve for degenerate LPs (no variables or no rows).

    A zero-flow CoflowSet — possible when a rolling-horizon arrival
    epoch is empty — produces an LP with no constraint rows (and, for
    the energy objective, no variables at all).  The box-constrained
    minimum is then coordinate-wise: x_j = 0 for c_j >= 0 (every real
    objective here is nonnegative), xmax_j otherwise."""
    x = np.where(lp.c < 0.0,
                 np.where(np.isfinite(lp.xmax), lp.xmax, 0.0), 0.0)
    return PDHGResult(x, 0.0, 0.0, 0, y=np.zeros(lp.m))


def _tile_plan_cached(lp: StructuredLP) -> tile_spmv.InstancePlan:
    """One instance's tile plan (core.tile_spmv.instance_plan), cached
    per sparsity pattern: keyed by a digest of (row, col) and (m, n,
    m_eq); counters land in BUILD_STATS."""
    key = (lp.m, lp.n, lp.m_eq, len(lp.val),
           hashlib.blake2b(np.ascontiguousarray(lp.row).tobytes()
                           + np.ascontiguousarray(lp.col).tobytes(),
                           digest_size=16).digest())
    plan = _TILE_PLAN_CACHE.get(key)
    if plan is None:
        plan = tile_spmv.instance_plan(lp.row, lp.col, lp.m, lp.n, lp.m_eq)
        BUILD_STATS.tile_misses += 1
        if len(_TILE_PLAN_CACHE) >= _TILE_PLAN_CACHE_MAX:
            _TILE_PLAN_CACHE.pop(next(iter(_TILE_PLAN_CACHE)))
        _TILE_PLAN_CACHE[key] = plan
    else:
        BUILD_STATS.tile_hits += 1
    return plan


def _pack_sharded(g: StructuredLP, shards: int):
    """Pack a (max-normalized, xmax-clamped) LP for the row-sharded
    burst: the blocked-ELL tables of kernels.pdhg_spmv.ell_pack_sharded
    and the vectors of core.pdhg.preconditioners, padded to the pack.
    The y side is padded to shards * m_loc, the rows of every shard.
    Padded x-slots carry tau=c=xmax=0 and padded rows sig=q=0 and
    ub=True, so they stay at zero and add nothing to the psum."""
    from repro.kernels import pdhg_spmv

    op = pdhg_spmv.ell_pack_sharded(g.row, g.col, g.val, g.m, g.n, shards)
    q, tau, sig, ub = pdhg.preconditioners(
        *(jnp.asarray(a) for a in (g.row, g.col, g.val, g.b, g.h)),
        g.m, g.n, g.m_eq)

    def padn(a):
        return jnp.pad(jnp.asarray(a, jnp.float32), (0, op.n_pad - g.n))

    def padm(a, value=0.0):
        return jnp.pad(a, (0, op.m_pad - g.m), constant_values=value)

    vecs = (padn(g.c), padn(tau), padn(g.xmax), padm(q), padm(sig),
            padm(ub, True))
    ell = tuple(jnp.asarray(a) for a in (op.row_idx, op.row_val,
                                         op.col_idx, op.col_val))
    return op, vecs, ell


class _Staged(NamedTuple):
    """One staged dispatch (_stage_xla, _stage_sharded), laid out and
    uploaded.  `place(x0, y0)` uploads host iterates in the coordinates
    of the stacked LP; `run(x, y, budget)` runs up to `budget`
    iterations from device iterates and returns (x, y, iterations per
    instance, primal, gap) with x and y left on the device (primal and
    gap device scalars, or None where the dispatch has none); `unpad(x,
    y)` brings iterates back to the host.  `key` is the static shape of
    the dispatch but its budget, `nnz` the dispatched nonzeros and
    `tile_slots` a direction's tile entries (0 but for the tiles)."""
    place: Callable
    run: Callable
    unpad: Callable
    key: tuple
    nnz: int
    tile_slots: int


def _stage_sharded(g: StructuredLP, shards: int, n_inst: int) -> _Staged:
    """Stage a dispatch of the stacked LP `g` (of `n_inst` instances)
    across `shards` devices (runtime.sharding.solver_mesh).  Each run is
    one fixed burst, whose instances all run `budget` iterations; its
    primal is the worst row's residual and it has no gap."""
    from repro.runtime.sharding import solver_mesh

    mesh = solver_mesh(shards)
    op, vecs, ell = _pack_sharded(g, shards)

    def place(x0, y0):
        return (jnp.pad(jnp.asarray(x0, jnp.float32), (0, op.n_pad - g.n)),
                jnp.pad(jnp.asarray(y0, jnp.float32), (0, op.m_pad - g.m)))

    def run(x, y, budget):
        x, y, worst = pdhg.burst_sharded(
            mesh, *vecs, *ell, x, y, row_meta=op.row_meta,
            col_meta=op.col_meta, iters=budget)
        # padded rows contribute 0 to the worst residual
        return x, y, np.full(n_inst, budget), jnp.max(worst), None

    def unpad(x, y):
        return np.asarray(x)[:g.n], np.asarray(y)[:g.m]

    return _Staged(place, run, unpad, ("sharded", shards, op.n_pad,
                                       op.m_pad, n_inst),
                   (op.row_idx.size + op.col_idx.size) // 2, 0)


def _gap(lp: StructuredLP, x: np.ndarray, y: np.ndarray) -> float:
    """The relative duality-gap proxy |c.x + q.y| / (1 + |c.x|) of an
    instance, on the host, with c max-normalized as block_stack does."""
    cscale = max(float(np.abs(lp.c).max(initial=0.0)), 1e-12)
    obj = float(lp.c @ x) / cscale
    return abs(obj + float(np.concatenate([lp.b, lp.h]) @ y)) \
        / (1.0 + abs(obj))


@functools.partial(jax.jit, static_argnames=(
    "num_inst", "m", "n", "m_eq", "chunk", "max_chunks"))
def _pdhg_run_adaptive(c, row, col, val, b, h, xmax, x0, y0, tols,
                       inst_n, inst_m,
                       num_inst, m, n, m_eq, chunk, max_chunks, tiles=None):
    """Fused adaptive PDHG over a block-stacked instance batch.

    Runs `chunk`-iteration bursts (core.pdhg.burst) inside one jitted
    lax.while_loop, computing per-instance primal residuals on-device
    (segment-max over the instance id of each row) after every burst.
    An instance whose residual meets its tolerance is *frozen* — its
    coordinates stop updating — so every instance follows exactly the
    trajectory it would have followed solving alone with the same chunk
    schedule, while the batch stops as soon as the last straggler
    converges.  This replaces the per-instance Python restart ladder
    (which overshoots by up to 2x per doubling and pays a host
    round-trip per restart) with a single dispatch of near-minimal
    total iterations.

    Coordinates may be storage-padded (shape bucketing, see
    _pad_for_buckets): `inst_n`/`inst_m` map padded slots to the dump
    segment `num_inst`, which is always treated as frozen and sliced off
    the residual vector.

    `tiles` (core.tile_spmv.StackedTiles.arrays) selects the tile
    operator; its tables are filled once, before the loop.

    Returns (x, y, per-instance residuals, per-instance chunks used)."""
    q, tau, sig, ub = pdhg.preconditioners(row, col, val, b, h, m, n, m_eq)
    Kx, KTy = _operator(row, col, val, m, n, tiles)

    def residuals(x):
        with jax.named_scope("pdhg/residual"):
            return jax.ops.segment_max(pdhg.residual(Kx, q, ub, x), inst_m,
                                       num_segments=num_inst + 1)[:num_inst]

    def step(state):
        x, y, k, frozen, used = state
        frozen_ext = jnp.concatenate([frozen, jnp.ones((1,), bool)])
        x, y = pdhg.burst(Kx, KTy, c, tau, sig, q, xmax, ub, x, y, chunk,
                          keep_n=frozen_ext[inst_n],
                          keep_m=frozen_ext[inst_m])
        frozen_new = frozen | (residuals(x) <= tols)
        used = jnp.where(frozen, used, k + 1)
        return x, y, k + 1, frozen_new, used

    def cond(state):
        _, _, k, frozen, _ = state
        return (k < max_chunks) & ~frozen.all()

    frozen0 = jnp.zeros(num_inst, dtype=bool)
    used0 = jnp.zeros(num_inst, dtype=jnp.int32)
    x, y, k, _, used = jax.lax.while_loop(
        cond, step, (x0, y0, 0, frozen0, used0))
    return x, y, residuals(x), used


def solve_lp(lp: StructuredLP, iters: int = 4000, *,
             tol: float | None = None, max_restarts: int = 3,
             x0: np.ndarray | None = None,
             y0: np.ndarray | None = None,
             shards: int = 1) -> PDHGResult:
    """Solve with PDHG; objective is max-normalized (the schedule is re-scored
    exactly afterwards, so only the argmin matters).  If the primal residual
    exceeds `tol`, continue the trajectory with doubled iterations (warm
    restart — prior progress is never discarded).  `x0`/`y0` seed the
    primal/dual iterates (e.g. a projected healthy solution for a degraded
    re-solve, see project_warm_start); default is a cold start from zero.

    K is applied as _stage_xla chooses: dense tiles on a TPU, COO
    scatters elsewhere.  `shards` > 1 partitions the constraint rows
    across that many devices (runtime.sharding.solver_mesh — on CPU
    requires XLA_FLAGS=--xla_force_host_platform_device_count) and
    applies K as row-sharded blocked ELL — see docs/SOLVER.md §9."""
    _check_shards(shards)
    if tol is None:
        tol = 1e-4 * max(float(np.abs(lp.b).max(initial=0.0)), 1.0)
    if lp.n == 0 or lp.m == 0:
        return _solve_lp_trivial(lp)
    bs = block_stack([lp])
    st = (_stage_sharded(bs.lp, shards, 1) if shards > 1
          else _stage_xla([lp], bs, bucket=False))
    # the iterates stay on the device between rungs; each rung sends
    # the host one scalar, the primal residual
    x, y = st.place(np.zeros(lp.n) if x0 is None else x0,
                    np.zeros(lp.m) if y0 is None else y0)
    total = 0
    for _ in range(max_restarts + 1):
        x, y, _, primal, gap = st.run(x, y, iters)
        total += iters
        if float(primal) <= tol:
            break
        iters *= 2
    x, y = st.unpad(x, y)
    if gap is None:
        x, y = x.astype(np.float64), y.astype(np.float64)
        gap = _gap(lp, x, y)
    return PDHGResult(x, float(primal), float(gap), total, y=y)


# ---------------------------------------------------------------------------
# Routing LP assembly
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RoutingIndex:
    kf: np.ndarray   # (K,) flow of each admissible (f,e,w) triple
    ke: np.ndarray   # (K,) edge
    kw: np.ndarray   # (K,) wavelength
    n_inj: int       # F*W injection variables
    n_theta: int     # 1 for min-time, else 0
    # row identities, used to map dual iterates between structurally related
    # LPs (healthy -> degraded instance; see project_warm_start).  eq_keys[i]
    # names equality row i, ub_keys[j] names inequality row m_eq + j:
    #   ("c", f, u, w|-1) conservation   ("d", f) demand
    #   ("ew", e, w) link cap            ("srv", u) egress   ("sw", v) ingress
    eq_keys: list | None = None
    ub_keys: list | None = None


def _admissible(p: ScheduleProblem):
    """Admissible (flow, edge, wavelength) triples, lexicographic (f, e, w)
    order — one vectorized nonzero over flow_edge_mask x edge_w_ok (the
    same triples, in the same order, the historical per-flow Python loop
    emitted; `_admissible_loops` keeps that loop as the pinned reference)."""
    adm = p.flow_edge_mask[:, :, None] & p.edge_w_ok[None, :, :]
    kf, ke, kw = np.nonzero(adm)
    return kf.astype(np.int64), ke.astype(np.int64), kw.astype(np.int64)


def _admissible_loops(p: ScheduleProblem):
    """Pre-vectorization reference implementation of `_admissible` (kept
    for the equivalence tests and benchmarks/build_bench.py's baseline)."""
    F, E, W, _ = p.shape_x
    trip_f, trip_e, trip_w = [], [], []
    for f in range(F):
        es = np.flatnonzero(p.flow_edge_mask[f])
        for e in es:
            ws = np.flatnonzero(p.edge_w_ok[e])
            trip_f.append(np.full(len(ws), f))
            trip_e.append(np.full(len(ws), e))
            trip_w.append(ws)
    if not trip_f:          # zero-flow instance (e.g. an empty arrival epoch)
        z = np.zeros(0, dtype=np.int64)
        return z, z.copy(), z.copy()
    kf = np.concatenate(trip_f).astype(np.int64)
    ke = np.concatenate(trip_e).astype(np.int64)
    kw = np.concatenate(trip_w).astype(np.int64)
    return kf, ke, kw


def _rank_by_first_use(codes: np.ndarray):
    """Rank the distinct values of `codes` by first appearance.

    Returns (rank_of_each_entry, codes_in_rank_order).  This is the
    vectorized equivalent of the historical row-allocation dicts: a row
    keyed by `codes[i]` gets the id a Python dict populated on first
    touch would have assigned, so the vectorized assembly reproduces the
    loop builder's row numbering exactly."""
    if len(codes) == 0:
        z = np.zeros(0, dtype=np.int64)
        return z, z.copy()
    uniq, first, inv = np.unique(codes, return_index=True,
                                 return_inverse=True)
    order = np.argsort(first, kind="stable")
    rank = np.empty(len(uniq), dtype=np.int64)
    rank[order] = np.arange(len(uniq))
    return rank[inv], uniq[order]


def _ub_block(row0: int, rank: np.ndarray, cols_k: np.ndarray,
              n_theta: int, i_theta: int):
    """COO entries of one inequality-row family (link cap / egress /
    ingress): per entry its row `row0 + rank` and column `cols_k`, with
    — when minimizing time — a theta coupling entry interleaved at each
    row's first occurrence, exactly where the loop builder's lazy
    `ub_row` emitted it.  Returns (rows, cols, vals, theta_positions);
    theta coefficient slots hold 0.0 and are refreshed from the current
    capacity limits by `_fill_lp`."""
    L = len(rank)
    if not n_theta:
        return (row0 + rank, cols_k, np.ones(L),
                np.zeros(0, dtype=np.int64))
    first = np.zeros(L, dtype=bool)
    if L:
        first[np.unique(rank, return_index=True)[1]] = True
    pos_own = np.arange(L, dtype=np.int64) + np.cumsum(first)
    total = L + int(first.sum())
    rows = np.empty(total, dtype=np.int64)
    cols = np.empty(total, dtype=np.int64)
    vals = np.ones(total)
    rows[pos_own] = row0 + rank
    cols[pos_own] = cols_k
    pos_theta = pos_own[first] - 1
    rows[pos_theta] = row0 + rank[first]
    cols[pos_theta] = i_theta
    vals[pos_theta] = 0.0
    return rows, cols, vals, pos_theta


def _device_cost_per_gbit(p: ScheduleProblem) -> np.ndarray:
    """(V,) surrogate device-power cost per Gbit (the energy objective's
    `p_max / incident_capacity` term), memoized on the topology object —
    it depends only on the topology's capacities and device powers, and
    sweeps build hundreds of problems over the same handful of graphs
    (degraded topologies are fresh objects, so they get fresh caches)."""
    t = p.topo
    cached = getattr(t, "_device_cost_cache", None)
    if cached is not None:
        return cached
    out = np.zeros(t.n_vertices)
    for vert in range(t.n_vertices):
        if p.p_max[vert] > 0:
            inc = t.cap[p.e_src == vert].sum() + t.cap[p.e_dst == vert].sum()
            out[vert] = p.p_max[vert] / max(float(inc), 1e-9)
    t._device_cost_cache = out
    return out


@dataclasses.dataclass
class ProblemStructure:
    """Everything about a routing LP that does not depend on capacity,
    demand, or horizon *values*: the admissible triples, the COO
    sparsity pattern with its constant +/-1 coefficients, the row
    identities, and the gather indices `_fill_lp` needs to refresh the
    value-dependent arrays (c, b, h, xmax, theta coefficients) in
    O(nnz).  Cached across solves keyed by `_structure_key` — arrival
    epochs re-solving the same merged co-flow set, brown-out/scaled
    degradations (cap pattern preserved), and horizon-doubling retries
    all reuse one entry and skip the assembly entirely."""

    idx: RoutingIndex
    n: int
    K: int
    n_cons: int               # conservation equality rows
    m_eq: int
    m: int
    n_theta: int
    row: np.ndarray           # COO rows (shared, treat as read-only)
    col: np.ndarray
    val_base: np.ndarray      # constant coefficients; theta slots hold 0
    theta_pos: np.ndarray     # COO positions of theta coefficients
    ew_e: np.ndarray          # per link-cap row (rank order): edge
    ew_w: np.ndarray          # ... and wavelength
    n_srv: int                # server-egress rows
    sw_verts: np.ndarray      # per switch-ingress row: vertex
    objective: str = "energy"  # which c-vector _fill_lp refreshes


@dataclasses.dataclass
class BuildCacheStats:
    """Counters for the problem-construction fast path (structure cache
    and tile plan cache).  Read via `build_cache_stats()`, cleared
    via `reset_build_caches()`; `python -m repro.sweep --profile` prints
    per-cell deltas."""

    structure_hits: int = 0
    structure_misses: int = 0
    tile_hits: int = 0
    tile_misses: int = 0

    def snapshot(self) -> "BuildCacheStats":
        return dataclasses.replace(self)


BUILD_STATS = BuildCacheStats()
_STRUCTURE_CACHE: dict = {}
_STRUCTURE_CACHE_MAX = 256
_TILE_PLAN_CACHE: dict = {}
_TILE_PLAN_CACHE_MAX = 256
_TILE_CAPACITY: dict = {}


def build_cache_stats() -> BuildCacheStats:
    """The live build-path cache counters (see BuildCacheStats)."""
    return BUILD_STATS


def reset_build_caches() -> None:
    """Drop the structure and tile-plan caches and zero the counters."""
    _STRUCTURE_CACHE.clear()
    _TILE_PLAN_CACHE.clear()
    _TILE_CAPACITY.clear()
    for f in dataclasses.fields(BuildCacheStats):
        setattr(BUILD_STATS, f.name, f.default)


@dataclasses.dataclass
class DispatchStats:
    """Counters for stacked PDHG dispatches (solve_lp_batch).

    A dispatch's compiled executable is keyed by its *post-bucketing*
    static shape (padded n/m_eq/m/nnz, instance count, chunk schedule,
    operator) — `shape_hits` counts dispatches that landed on a shape
    this process has dispatched before (the jitted kernel, and the
    persistent compilation cache of repro.compile_cache, can reuse the
    compiled executable), `shape_misses` counts first-seen shapes.  The
    multi-tenant scheduler service reads deltas of these counters to
    report its bucket-hit ratio; read via `dispatch_stats()`, clear via
    `reset_dispatch_stats()`.

    The two work counters are in nonzero-iterations, one pass over one
    nonzero of K in one PDHG iteration: `nnz_iters_run` is what the
    dispatches ran on the device (the longest instance's iterations
    times the dispatched, bucketed nnz: frozen instances, padding and
    the dump segment included), `nnz_iters_useful` what moved an
    unconverged instance (each instance's iterations times its own
    nnz).  Their ratio is the share of PDHG's scatter work that was
    not wasted.

    Dispatches that apply K as dense tiles (core.tile_spmv) count in
    `tiled_dispatches`, and their tile entries in `tile_slots_run`: the
    mean of the two directions' dispatched (bucketed) tile counts, times
    1,024 entries a tile, times the longest instance's iterations.
    `nnz_iters_useful / tile_slots_run` is then the tiles' fill."""

    dispatches: int = 0
    shape_hits: int = 0
    shape_misses: int = 0
    nnz_iters_run: int = 0
    nnz_iters_useful: int = 0
    tiled_dispatches: int = 0
    tile_slots_run: int = 0

    def snapshot(self) -> "DispatchStats":
        return dataclasses.replace(self)


DISPATCH_STATS = DispatchStats()
_DISPATCH_SHAPES: set = set()


def dispatch_stats() -> DispatchStats:
    """The live stacked-dispatch shape counters (see DispatchStats)."""
    return DISPATCH_STATS


def reset_dispatch_stats() -> None:
    """Forget seen dispatch shapes and zero the counters."""
    _DISPATCH_SHAPES.clear()
    for f in dataclasses.fields(DispatchStats):
        setattr(DISPATCH_STATS, f.name, f.default)


def _note_dispatch(shape: tuple) -> None:
    """Record one stacked dispatch's static shape (see DispatchStats)."""
    DISPATCH_STATS.dispatches += 1
    if shape in _DISPATCH_SHAPES:
        DISPATCH_STATS.shape_hits += 1
    else:
        DISPATCH_STATS.shape_misses += 1
        _DISPATCH_SHAPES.add(shape)


def _note_work(used: np.ndarray, nnz_run: int, nnz: list[int],
               tile_slots: int = 0) -> None:
    """Record one dispatch's PDHG work (see DispatchStats): `used` holds
    each instance's iterations, `nnz_run` the dispatched nnz, `nnz`
    each instance's own and `tile_slots` a direction's tile entries
    (0 where K is applied as COO)."""
    longest = int(used.max(initial=0))
    DISPATCH_STATS.nnz_iters_run += longest * nnz_run
    DISPATCH_STATS.nnz_iters_useful += sum(int(u) * k
                                           for u, k in zip(used, nnz))
    if tile_slots:
        DISPATCH_STATS.tiled_dispatches += 1
        DISPATCH_STATS.tile_slots_run += longest * tile_slots


def _structure_key(p: ScheduleProblem, objective: str) -> tuple:
    """Hashable identity of a routing LP's *structure*.

    Two problems share a ProblemStructure iff every array that shapes
    the sparsity pattern matches: the edge list, the admissibility
    masks (flow_edge_mask already folds in endpoints, path_slack and
    degraded reachability; edge_w_ok is the cap > 0 pattern), vertex
    kinds, and which rate limits are finite.  Capacity/demand/horizon
    VALUES are deliberately excluded — they only feed `_fill_lp`."""
    t = p.topo
    hh = hashlib.blake2b(digest_size=16)
    for a in (t.edges, p.edge_w_ok, p.flow_edge_mask, p.coflow.src,
              p.coflow.dst, p.is_server, p.is_switch,
              np.isfinite(p.sigma)):
        hh.update(np.ascontiguousarray(a).tobytes())
    hh.update(b"rho-finite" if np.isfinite(p.rho) else b"rho-inf")
    return (objective, t.n_vertices, t.n_edges, t.n_wavelengths,
            p.coflow.n_flows, hh.hexdigest())


def _build_structure(p: ScheduleProblem, objective: str) -> ProblemStructure:
    """Vectorized assembly of the value-independent LP skeleton.

    Pure index arithmetic — no per-row Python closures, no (f, e, w)
    dict keys.  Row numbering and COO entry order reproduce the loop
    builder (`_build_routing_lp_loops`) bit-for-bit: rows are ranked by
    first use (`_rank_by_first_use` mirrors the lazy row-allocation
    dicts) and entries are emitted in the same stream order
    (conservation interleaved per triple, injections, demand, then the
    three inequality families with theta couplings at row creation)."""
    F, E, W, _ = p.shape_x
    V = p.topo.n_vertices
    kf, ke, kw = _admissible(p)
    K = len(kf)
    n_inj = F * W
    n_theta = 1 if objective == "time" else 0
    n = K + n_inj + n_theta
    i_theta = n - 1
    passive = ~(p.is_server | p.is_switch)
    src = p.coflow.src.astype(np.int64)
    dst = p.coflow.dst.astype(np.int64)
    u, v = p.e_src[ke], p.e_dst[ke]

    # --- equality rows ----------------------------------------------------
    # conservation rows keyed ("c", f, vertex, w | -1): per-wavelength at
    # passive vertices, wavelength-summed at electronic ones.  The stream
    # is [u-entry, v-entry] per triple (dst rows skipped — implied), then
    # the injection entries; first use allocates the row.
    stride = np.int64(W + 1)
    codes2 = np.empty(2 * K, dtype=np.int64)
    codes2[0::2] = (kf * V + u) * stride + np.where(passive[u], kw, -1) + 1
    codes2[1::2] = (kf * V + v) * stride + np.where(passive[v], kw, -1) + 1
    valid2 = np.empty(2 * K, dtype=bool)
    valid2[0::2] = u != dst[kf]          # never False (masked), keep guard
    valid2[1::2] = v != dst[kf]
    cols2 = np.repeat(np.arange(K, dtype=np.int64), 2)
    vals2 = np.tile(np.array([1.0, -1.0]), K)

    finj = np.repeat(np.arange(F, dtype=np.int64), W)
    winj = np.tile(np.arange(W, dtype=np.int64), F)
    sv = src[finj]
    inj_codes = (finj * V + sv) * stride + np.where(passive[sv], winj, -1) + 1

    stream = np.concatenate([codes2[valid2], inj_codes])
    row_ids, cons_codes = _rank_by_first_use(stream)
    n_cons = len(cons_codes)
    m_eq = n_cons + F

    inj_cols = K + np.arange(n_inj, dtype=np.int64)
    rows_eq = np.concatenate([
        row_ids, np.repeat(n_cons + np.arange(F, dtype=np.int64), W)])
    cols_eq = np.concatenate([cols2[valid2], inj_cols, inj_cols])
    vals_eq = np.concatenate([vals2[valid2], np.full(n_inj, -1.0),
                              np.full(n_inj, 1.0)])

    w_eff = cons_codes % stride - 1
    rest = cons_codes // stride
    eq_keys = [("c", int(f_), int(vt), int(w_))
               for f_, vt, w_ in zip(rest // V, rest % V, w_eff)]
    eq_keys += [("d", f_) for f_ in range(F)]

    # --- inequality rows --------------------------------------------------
    # shared capacity per (e, w)
    ew_rank, ew_uniq = _rank_by_first_use(ke * W + kw)
    n_ew = len(ew_uniq)
    ew_e, ew_w = ew_uniq // W, ew_uniq % W
    rows_ew, cols_ew, vals_ew, theta_ew = _ub_block(
        m_eq, ew_rank, np.arange(K, dtype=np.int64), n_theta, i_theta)

    # server egress rate
    if np.isfinite(p.rho):
        srv_k = np.flatnonzero(p.is_server[u])
        srv_rank, srv_uniq = _rank_by_first_use(u[srv_k])
    else:
        srv_k = np.zeros(0, dtype=np.int64)
        srv_rank, srv_uniq = _rank_by_first_use(srv_k)
    n_srv = len(srv_uniq)
    rows_srv, cols_srv, vals_srv, theta_srv = _ub_block(
        m_eq + n_ew, srv_rank, srv_k, n_theta, i_theta)

    # switch ingress rate
    sw_k = np.flatnonzero(p.is_switch[v] & np.isfinite(p.sigma[v]))
    sw_rank, sw_uniq = _rank_by_first_use(v[sw_k])
    rows_sw, cols_sw, vals_sw, theta_sw = _ub_block(
        m_eq + n_ew + n_srv, sw_rank, sw_k, n_theta, i_theta)

    ub_keys = [("ew", int(e), int(w_)) for e, w_ in zip(ew_e, ew_w)]
    ub_keys += [("srv", int(x)) for x in srv_uniq]
    ub_keys += [("sw", int(x)) for x in sw_uniq]

    row = np.concatenate([rows_eq, rows_ew, rows_srv, rows_sw])
    col = np.concatenate([cols_eq, cols_ew, cols_srv, cols_sw])
    val_base = np.concatenate([vals_eq, vals_ew, vals_srv, vals_sw])
    off_ew = len(rows_eq)
    off_srv = off_ew + len(rows_ew)
    off_sw = off_srv + len(rows_srv)
    theta_pos = np.concatenate([off_ew + theta_ew, off_srv + theta_srv,
                                off_sw + theta_sw])

    idx = RoutingIndex(kf, ke, kw, n_inj, n_theta,
                       eq_keys=eq_keys, ub_keys=ub_keys)
    return ProblemStructure(
        idx=idx, n=n, K=K, n_cons=n_cons, m_eq=m_eq,
        m=m_eq + n_ew + n_srv + len(sw_uniq), n_theta=n_theta,
        row=row, col=col, val_base=val_base, theta_pos=theta_pos,
        ew_e=ew_e, ew_w=ew_w, n_srv=n_srv, sw_verts=sw_uniq,
        objective=objective)


def _fill_lp(st: ProblemStructure, p: ScheduleProblem) -> StructuredLP:
    """Refresh a cached structure's value arrays from the current problem:
    capacities/rates (h, theta coefficients, xmax), demand (b, xmax) and
    the objective vector.  O(nnz) gathers — no Python per-row work."""
    F, E, W, T = p.shape_x
    horizon = T * p.topo.slot_duration
    kf, ke, kw = st.idx.kf, st.idx.ke, st.idx.kw
    K = st.K
    cap = p.topo.cap
    size = p.coflow.size.astype(np.float64)
    total = max(p.coflow.total_gbits, 1e-9)

    limits = np.concatenate([cap[st.ew_e, st.ew_w],
                             np.full(st.n_srv, p.rho),
                             p.sigma[st.sw_verts]])
    if st.n_theta:
        h = np.zeros(len(limits))
        val = st.val_base.copy()
        val[st.theta_pos] = -limits
    else:
        h = limits * horizon
        val = st.val_base          # fully constant; shared, read-only

    b = np.concatenate([np.zeros(st.n_cons), size])

    c = np.zeros(st.n)
    if st.n_theta:
        c[st.n - 1] = 1.0
        c[:K] += 1e-6 / total          # cycle/path-length regularizer
    else:
        # exact NIC J/Gbit + surrogate device-power-per-Gbit terms, same
        # accumulation order as the loop builder (bit-for-bit)
        contrib = _device_cost_per_gbit(p)
        u, v = p.e_src[ke], p.e_dst[ke]
        eps_u = np.where(p.is_server[u], p.eps[u], 0.0)
        eps_v = np.where(p.is_server[v], p.eps[v], 0.0)
        c[:K] = (eps_u + eps_v) + (contrib[u] + contrib[v]) + 1e-6
        if st.objective == "fair" and p.flow_weight is not None:
            # weighted max-min fairness surrogate: a flow's transport is
            # priced inversely to its weight, so higher-weight tenants
            # are served preferentially under contention.  Uniform
            # weights rescale c by a constant, and solve_lp normalizes
            # by max|c| — so "fair" then coincides with "energy".
            c[:K] /= p.flow_weight[kf]

    xmax = np.full(st.n, np.inf)
    xmax[:K] = np.minimum(cap[ke, kw] * horizon, total)
    xmax[K:K + F * W] = np.repeat(size, W)
    if st.n_theta:
        xmax[st.n - 1] = horizon
    return StructuredLP(c=c, row=st.row, col=st.col, val=val,
                        b=b, h=h, xmax=xmax)


@trace.spanned("lp.build")
def build_routing_lp(p: ScheduleProblem, objective: str, *,
                     cache: bool = True
                     ) -> tuple[StructuredLP, RoutingIndex]:
    """Assemble the routing LP (see docs/SOLVER.md §1 and §8).

    Vectorized fast path: the value-independent skeleton (sparsity
    pattern, row numbering, RoutingIndex) is built once per structure
    and cached across solves keyed by `_structure_key`; only the value
    arrays (c, b, h, xmax, theta coefficients) are refreshed per call.
    `cache=False` rebuilds the skeleton unconditionally (equivalence
    tests; the arrays produced are identical either way).  The returned
    row/col/kf/ke/kw arrays are shared with the cache — treat them as
    read-only."""
    # "fair" shares the energy structure (n_theta = 0) with a per-flow
    # reweighted c vector; see _fill_lp and docs/POLICIES.md
    assert objective in ("energy", "time", "fair")
    key = _structure_key(p, objective) if cache else None
    st = _STRUCTURE_CACHE.get(key) if cache else None
    if st is None:
        st = _build_structure(p, objective)
        BUILD_STATS.structure_misses += 1
        if cache:
            if len(_STRUCTURE_CACHE) >= _STRUCTURE_CACHE_MAX:
                _STRUCTURE_CACHE.pop(next(iter(_STRUCTURE_CACHE)))
            _STRUCTURE_CACHE[key] = st
    else:
        BUILD_STATS.structure_hits += 1
    return _fill_lp(st, p), st.idx


def _build_routing_lp_loops(p: ScheduleProblem, objective: str
                            ) -> tuple[StructuredLP, RoutingIndex]:
    """Pre-vectorization reference builder (pure Python row emission).

    Kept verbatim so tests/test_build_cache.py can pin the vectorized
    assembly bit-for-bit against it and benchmarks/build_bench.py can
    measure the speedup against the real historical baseline.  Do not
    optimize this function."""
    assert objective in ("energy", "time")
    F, E, W, T = p.shape_x
    V = p.topo.n_vertices
    D = p.topo.slot_duration
    horizon = T * D
    kf, ke, kw = _admissible_loops(p)
    K = len(kf)
    n_inj = F * W
    n_theta = 1 if objective == "time" else 0
    n = K + n_inj + n_theta
    i_theta = n - 1

    passive = ~(p.is_server | p.is_switch)
    src, dst = p.coflow.src, p.coflow.dst
    e_src, e_dst = p.e_src, p.e_dst

    rows, cols, vals = [], [], []
    b_rows: list[float] = []
    eq_keys: list[tuple] = []

    # --- equality rows ----------------------------------------------------
    # conservation rows: passive vertices per-w -> id (f, u, w); electronic
    # intermediates summed over w -> id (f, u, 0 "summed").
    # Allocate: r_cons(f,u,w) only for rows that get entries.
    row_of: dict[tuple, int] = {}

    def cons_row(f, u, w):
        key = ("c", f, u, w if passive[u] else -1)
        if key not in row_of:
            row_of[key] = len(b_rows)
            b_rows.append(0.0)
            eq_keys.append(key)
        return row_of[key]

    for k in range(K):
        f, e, w = int(kf[k]), int(ke[k]), int(kw[k])
        u, v = int(e_src[e]), int(e_dst[e])
        if u != dst[f]:          # never happens (masked), keep guard
            r = cons_row(f, u, w)
            rows.append(r); cols.append(k); vals.append(1.0)
        if v != dst[f]:
            r = cons_row(f, v, w)
            rows.append(r); cols.append(k); vals.append(-1.0)
        # dst rows intentionally skipped (implied)

    # injection variables: appear in source conservation rows (per wavelength
    # if the source is... sources are servers => electronic => summed rows)
    for f in range(F):
        for w in range(W):
            r = cons_row(f, int(src[f]), w)
            rows.append(r); cols.append(K + f * W + w); vals.append(-1.0)

    # demand rows: sum_w inj = size_f
    for f in range(F):
        r = len(b_rows)
        b_rows.append(float(p.coflow.size[f]))
        eq_keys.append(("d", f))
        for w in range(W):
            rows.append(r); cols.append(K + f * W + w); vals.append(1.0)

    m_eq = len(b_rows)

    # --- inequality rows ----------------------------------------------------
    h_rows: list[float] = []
    ub_keys: list[tuple] = []

    def ub_row(limit_times_theta: float | None, limit: float | None, key):
        """Create an inequality row; couple to theta when minimizing time."""
        r = m_eq + len(h_rows)
        if n_theta and limit_times_theta is not None:
            h_rows.append(0.0)
            rows.append(r); cols.append(i_theta); vals.append(-limit_times_theta)
        else:
            h_rows.append(limit if limit is not None else np.inf)
        ub_keys.append(key)
        return r

    # shared capacity per (e, w)
    ew_ids: dict[tuple[int, int], int] = {}
    for k in range(K):
        e, w = int(ke[k]), int(kw[k])
        if (e, w) not in ew_ids:
            cap = float(p.topo.cap[e, w])
            ew_ids[(e, w)] = ub_row(cap, cap * horizon, ("ew", e, w))
        rows.append(ew_ids[(e, w)]); cols.append(k); vals.append(1.0)

    # server egress rate
    srv_rows: dict[int, int] = {}
    if np.isfinite(p.rho):
        for k in range(K):
            u = int(e_src[int(ke[k])])
            if p.is_server[u]:
                if u not in srv_rows:
                    srv_rows[u] = ub_row(p.rho, p.rho * horizon, ("srv", u))
                rows.append(srv_rows[u]); cols.append(k); vals.append(1.0)

    # switch ingress rate
    sw_rows: dict[int, int] = {}
    for k in range(K):
        v = int(e_dst[int(ke[k])])
        if p.is_switch[v] and np.isfinite(p.sigma[v]):
            if v not in sw_rows:
                sw_rows[v] = ub_row(float(p.sigma[v]),
                                    float(p.sigma[v]) * horizon, ("sw", v))
            rows.append(sw_rows[v]); cols.append(k); vals.append(1.0)

    # --- objective ------------------------------------------------------------
    c = np.zeros(n)
    total = max(p.coflow.total_gbits, 1e-9)
    if objective == "time":
        c[i_theta] = 1.0
        c[:K] += 1e-6 / total          # cycle/path-length regularizer
    else:
        for k in range(K):
            e = int(ke[k])
            w_eps = 0.0
            u, v = int(e_src[e]), int(e_dst[e])
            if p.is_server[u]:
                w_eps += p.eps[u]
            if p.is_server[v]:
                w_eps += p.eps[v]
            # exact NIC J/Gbit + surrogate device-power-per-Gbit terms
            dev_cost = 0.0
            for vert in (u, v):
                if p.p_max[vert] > 0:
                    inc = p.topo.cap[e_src == vert].sum() + p.topo.cap[e_dst == vert].sum()
                    dev_cost += p.p_max[vert] / max(float(inc), 1e-9)
            c[k] = w_eps + dev_cost + 1e-6

    xmax = np.full(n, np.inf)
    xmax[:K] = np.minimum(p.topo.cap[ke, kw] * horizon, total)
    for f in range(F):
        xmax[K + f * W: K + (f + 1) * W] = float(p.coflow.size[f])
    if n_theta:
        xmax[i_theta] = horizon

    lp = StructuredLP(
        c=c, row=np.asarray(rows, np.int64), col=np.asarray(cols, np.int64),
        val=np.asarray(vals, np.float64), b=np.asarray(b_rows, np.float64),
        h=np.asarray(h_rows, np.float64), xmax=xmax)
    return lp, RoutingIndex(kf, ke, kw, n_inj, n_theta,
                            eq_keys=eq_keys, ub_keys=ub_keys)


# ---------------------------------------------------------------------------
# Path decomposition (clean up approximate LP flows)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FlowPath:
    """One src->dst path of a flow with an assigned volume share."""

    flow: int
    triples: np.ndarray        # indices into the (kf, ke, kw) triple arrays
    volume: float              # Gbits assigned to this path
    tx_wavelength: int         # wavelength on the first hop (eq. 47 bookkeeping)


def _out_edges(p: ScheduleProblem) -> list[list[int]]:
    """Outgoing-edge adjacency, memoized on the topology object — the
    decomposition/search helpers run once per flow per solve, and sweeps
    build hundreds of problems over the same handful of graphs (degraded
    topologies are fresh objects, so they get fresh caches)."""
    t = p.topo
    cached = getattr(t, "_out_edges_cache", None)
    if cached is not None:
        return cached
    out: list[list[int]] = [[] for _ in range(t.n_vertices)]
    for e in range(t.n_edges):
        out[int(t.edges[e, 0])].append(e)
    t._out_edges_cache = out
    return out


def _route_search(p: ScheduleProblem, out_edges, src: int, dst: int,
                  usable, convert_ok) -> list[tuple[int, int]] | None:
    """DFS over (vertex, arrival wavelength) states; usable(e, w) gates
    which hops may be taken, convert_ok[u] whether vertex u may change
    wavelength (electronic O/E conversion).  Returns [(edge, w), ...] or
    None if dst is unreachable."""
    W = p.topo.n_wavelengths
    e_dst = p.e_dst
    stack = [(src, -1, [])]
    seen = set()
    while stack:
        u, w_in, trail = stack.pop()
        if u == dst:
            return trail
        if (u, w_in) in seen:
            continue
        seen.add((u, w_in))
        convert = (w_in == -1) or convert_ok[u]
        for e in out_edges[u]:
            for w in range(W):
                if not convert and w != w_in:
                    continue
                if usable(e, w):
                    stack.append((int(e_dst[e]), w, trail + [(e, w)]))
    return None


@dataclasses.dataclass
class DecomposeStats:
    """Counters for path_decompose: `paths` peeled (fallback routes
    included) and DFS `states` expanded, summed over every search; their
    ratio is the backtracking a peel does.  Read via `decompose_stats()`;
    `python -m repro.sweep --profile` prints per-cell deltas."""

    paths: int = 0
    states: int = 0

    def snapshot(self) -> "DecomposeStats":
        return dataclasses.replace(self)


DECOMPOSE_STATS = DecomposeStats()


def decompose_stats() -> DecomposeStats:
    """The live path-decomposition counters (see DecomposeStats)."""
    return DECOMPOSE_STATS


def _peel_search(adj: dict, live: list, src: int, dst: int,
                 convert_ok: list) -> list[int] | None:
    """`_route_search` over one flow's support: `adj[u]` lists the flow's
    (local triple, w, head) entries leaving u in `_out_edges(p)[u]` order,
    then w ascending, and `live[j]` gates a hop — the same stack DFS,
    push order and `seen` test, so the same route.  A trail is a chain of
    (j, parent trail) pairs, shared by its extensions, not a copied list.
    Returns the route's local triples ([] when src == dst) or None if dst
    is unreachable."""
    stack = [(src, -1, None)]
    seen = set()
    route = None
    while stack:
        u, w_in, trail = stack.pop()
        if u == dst:
            route = []
            while trail is not None:
                j, trail = trail
                route.append(j)
            route.reverse()
            break
        if (u, w_in) in seen:
            continue
        seen.add((u, w_in))
        convert = w_in == -1 or convert_ok[u]
        for j, w, v in adj.get(u, ()):
            if live[j] and (convert or w == w_in):
                stack.append((v, w, (j, trail)))
    DECOMPOSE_STATS.states += len(seen)
    return route


@trace.spanned("pack.decompose")
def path_decompose(p: ScheduleProblem, idx: RoutingIndex,
                   vol: np.ndarray) -> list[FlowPath]:
    """Decompose per-flow (edge, wavelength) volumes into src->dst paths.

    PDHG solutions carry O(residual) conservation error and possibly cycles;
    a path decomposition conserves *exactly* (wavelength-continuous at
    passive vertices, free conversion at electronic ones), drops cyclic
    residue, and — crucially for PON3 — tags each path with the wavelength
    its source transmits on, so eq. 47 can be enforced per path.

    Each flow's paths are peeled over its own support: an adjacency of its
    triples built once, and its remaining volume as a list indexed by
    local triple, searched by `_peel_search` in `_route_search`'s order."""
    F, E, W, _ = p.shape_x
    kf, ke, kw = idx.kf, idx.ke, idx.kw
    convert_ok = (p.is_server | p.is_switch).tolist()
    # per-flow triple ranges: kf is sorted by construction (lexicographic
    # (f, e, w) order), so each flow owns one contiguous slice, and a
    # vertex's triples within it come in `_out_edges` order, w ascending
    bounds = np.searchsorted(kf, np.arange(F + 1))
    tail, head, wl = p.e_src[ke].tolist(), p.e_dst[ke].tolist(), kw.tolist()
    vol_live = np.where(vol > 1e-9, vol, 0.0).tolist()

    paths: list[FlowPath] = []
    for f in range(F):
        lo, hi = int(bounds[f]), int(bounds[f + 1])
        adj: dict[int, list] = {}
        for j, k in enumerate(range(lo, hi)):
            adj.setdefault(tail[k], []).append((j, wl[k], head[k]))
        g = vol_live[lo:hi]
        live = [x > 1e-9 for x in g]
        n_live = sum(live)
        src, dst = int(p.coflow.src[f]), int(p.coflow.dst[f])
        budget = float(p.coflow.size[f])
        n_before = len(paths)
        guard = 4 * E * W + 16
        while budget > 1e-9 and guard > 0 and n_live:
            guard -= 1
            route = _peel_search(adj, live, src, dst, convert_ok)
            if not route:  # no route, or degenerate src == dst
                break
            amt = min(budget, min(g[j] for j in route))
            for j in route:
                g[j] -= amt
                if g[j] <= 1e-9:
                    live[j] = False
                    n_live -= 1
            budget -= amt
            paths.append(FlowPath(f, np.array(route, np.int64) + lo, amt,
                                  wl[lo + route[0]]))
        if len(paths) > n_before and budget > 1e-9:
            # the LP iterate routed less than the demand (loose tolerance
            # or dropped cyclic residue): rescale this flow's paths so the
            # decomposition conserves per-flow volume exactly.  The common
            # factor leaves temporal_pack's proportional shares unchanged.
            scale = float(p.coflow.size[f]) / (float(p.coflow.size[f])
                                               - budget)
            for fp in paths[n_before:]:
                fp.volume *= scale
        if len(paths) == n_before:
            # no LP volume survived the 1e-9 gate (tiny flows under a loose
            # LP tolerance) — ship the whole demand on any admissible route
            # so temporal_pack never silently drops a flow
            route = _peel_search(adj, [True] * (hi - lo), src, dst,
                                 convert_ok)
            if route:      # empty route (src == dst) has no tx wavelength
                paths.append(FlowPath(f, np.array(route, np.int64) + lo,
                                      budget, wl[lo + route[0]]))
    DECOMPOSE_STATS.paths += len(paths)
    return paths


# ---------------------------------------------------------------------------
# Temporal packing (fractional routing -> discrete slots)
# ---------------------------------------------------------------------------

@trace.spanned("pack.slots")
def temporal_pack(p: ScheduleProblem, idx: RoutingIndex,
                  x_route: np.ndarray, *,
                  paths: list[FlowPath] | None = None) -> np.ndarray:
    """Quantize routed path volumes into slots, earliest-first water-filling.

    Every decomposed path ships volume v_p <= remaining_p per slot subject
    to link/server/switch caps; for PON3 each source server transmits on a
    single wavelength per slot (eq. 47), chosen greedily as the wavelength
    with the largest remaining demand at that server.  `paths` skips the
    decomposition when the caller already ran path_decompose on x_route."""
    F, E, W, T = p.shape_x
    D = p.topo.slot_duration
    kf, ke, kw = idx.kf, idx.ke, idx.kw
    K = len(kf)
    if paths is None:
        paths = path_decompose(p, idx, np.maximum(x_route[:K], 0.0))
    if not paths:
        return np.zeros((F, E, W, T))
    P = len(paths)
    # path -> triple incidence as flat arrays, with every gather the slot
    # loop needs (edge, wavelength, endpoints, flow) precomputed once —
    # the loop body below runs up to 60 capacity-scaling rounds per slot
    # and must not re-index the triple arrays each time
    pk_path = np.concatenate([np.full(len(pp.triples), i)
                              for i, pp in enumerate(paths)])
    pk_k = np.concatenate([pp.triples for pp in paths])
    pk_e, pk_w, pk_f = ke[pk_k], kw[pk_k], kf[pk_k]
    pk_u, pk_v = p.e_src[pk_e], p.e_dst[pk_e]
    p_flow = np.array([pp.flow for pp in paths])
    p_txw = np.array([pp.tx_wavelength for pp in paths])
    p_src = p.coflow.src[p_flow]
    # ragged per-path views of the same gathers, for the greedy raise
    p_e = [ke[pp.triples] for pp in paths]
    p_w = [kw[pp.triples] for pp in paths]
    p_u = [p.e_src[e_] for e_ in p_e]
    p_v = [p.e_dst[e_] for e_ in p_e]

    # per-flow demand split over its paths, proportional to decomposed volume
    vol_by_flow = np.zeros(F)
    p_vol = np.array([pp.volume for pp in paths])
    np.add.at(vol_by_flow, p_flow, p_vol)
    share = p_vol / np.maximum(vol_by_flow[p_flow], 1e-30)
    remaining = share * p.coflow.size[p_flow]

    # does this path's source hit an AWGR ingress on its first hop?
    eq47 = np.zeros(P, dtype=bool)
    if p.topo.one_wavelength_tx and p.topo.awgr_in_ports:
        awgr_in = np.isin(p.e_dst, p.topo.awgr_in_ports)
        first_k = np.array([pp.triples[0] for pp in paths])
        eq47 = awgr_in[ke[first_k]]

    slot_cap = p.slot_cap_gbits                                   # (E, W)
    x = np.zeros((F, E, W, T))
    srv_lim = np.where(p.is_server, p.rho * D, np.inf)
    sw_lim = np.where(p.is_switch & np.isfinite(p.sigma), p.sigma * D, np.inf)

    release = (p.release_slot[p_flow] if p.release_slot is not None
               else np.zeros(P, dtype=int))
    for t in range(T):
        if remaining.max(initial=0.0) <= 1e-9:
            break
        active = (remaining > 1e-9) & (release <= t)
        if not active.any():
            continue
        if eq47.any():
            for i in np.unique(p_src[eq47]):
                sel = eq47 & (p_src == i) & active
                if not sel.any():
                    continue
                w_demand = np.zeros(W)
                np.add.at(w_demand, p_txw[sel], remaining[sel])
                w_star = int(np.argmax(w_demand))
                active &= ~(eq47 & (p_src == i) & (p_txw != w_star))

        v = np.where(active, remaining, 0.0)
        for _ in range(60):
            vk = v[pk_path]                                       # volume per hop
            used_ew = np.zeros((E, W))
            np.add.at(used_ew, (pk_e, pk_w), vk)
            with np.errstate(divide="ignore", invalid="ignore"):
                over = np.where(used_ew > slot_cap,
                                slot_cap / np.maximum(used_ew, 1e-30), 1.0)
            scale_hop = over[pk_e, pk_w]
            egress = np.zeros(p.topo.n_vertices)
            np.add.at(egress, pk_u, vk)
            with np.errstate(divide="ignore", invalid="ignore"):
                over_v = np.where(egress > srv_lim,
                                  srv_lim / np.maximum(egress, 1e-30), 1.0)
            scale_hop = np.minimum(scale_hop, over_v[pk_u])
            ingress = np.zeros(p.topo.n_vertices)
            np.add.at(ingress, pk_v, vk)
            with np.errstate(divide="ignore", invalid="ignore"):
                over_s = np.where(ingress > sw_lim,
                                  sw_lim / np.maximum(ingress, 1e-30), 1.0)
            scale_hop = np.minimum(scale_hop, over_s[pk_v])
            pscale = np.ones(P)
            np.minimum.at(pscale, pk_path, scale_hop)
            if (pscale > 1.0 - 1e-9).all():
                break
            v = v * np.minimum(pscale, 1.0)

        # greedy raise: refill slack for paths the proportional scaling
        # under-served (largest remaining first)
        vk = v[pk_path]
        used_ew = np.zeros((E, W))
        np.add.at(used_ew, (pk_e, pk_w), vk)
        egress = np.zeros(p.topo.n_vertices)
        np.add.at(egress, pk_u, vk)
        ingress = np.zeros(p.topo.n_vertices)
        np.add.at(ingress, pk_v, vk)
        want = np.where(active, remaining - v, 0.0)
        for pi in np.argsort(-want):
            if want[pi] <= 1e-9:
                continue
            slack = np.min(np.concatenate([
                slot_cap[p_e[pi], p_w[pi]] - used_ew[p_e[pi], p_w[pi]],
                srv_lim[p_u[pi]] - egress[p_u[pi]],
                sw_lim[p_v[pi]] - ingress[p_v[pi]]]))
            add = min(float(want[pi]), max(float(slack), 0.0))
            if add <= 1e-9:
                continue
            v[pi] += add
            np.add.at(used_ew, (p_e[pi], p_w[pi]), add)
            np.add.at(egress, p_u[pi], add)
            np.add.at(ingress, p_v[pi], add)

        np.add.at(x[:, :, :, t], (pk_f, pk_e, pk_w), v[pk_path])
        remaining = np.maximum(remaining - v, 0.0)
    return x


@dataclasses.dataclass
class FastPathResult:
    schedule: np.ndarray      # x[f, e, w, t] in Gbits (exact paper tensor)
    metrics: Metrics          # exact core.timeslot.evaluate numbers (J, s)
    lp_lower_bound: float     # theta (min-time) or LP objective (min-energy)
    lp_primal_residual: float
    remaining_gbits: float    # demand the packer could not place in-horizon
    # PDHG terminal state + LP indexing, retained so this solve can seed an
    # incremental re-solve on a degraded topology (resolve_incremental /
    # solve_fast_ensemble).  None only for results predating these fields.
    lp_x: np.ndarray | None = None
    lp_y: np.ndarray | None = None
    index: RoutingIndex | None = None
    paths: list[FlowPath] | None = None
    iterations: int = 0       # PDHG iterations actually spent
    lp_cscale: float = 1.0    # max|c| the LP was normalized by (duals scale)
    # True iff PDHG actually started from a projected warm state — stays
    # False when solve_fast_warm's projection fell back to a cold start,
    # so callers' warm-vs-cold accounting reflects what really ran
    warm_started: bool = False
    # core.verify.Certificate when the producer attached one (the policy
    # zoo always does); the LP fast path leaves it None and callers
    # certify on demand via core.verify.check_schedule
    certificate: object | None = None


def _assemble_fast_result(p: ScheduleProblem, lp: StructuredLP,
                          idx: RoutingIndex, res: PDHGResult
                          ) -> FastPathResult:
    """Pack the LP routing into slots and re-score it with the exact paper
    model — shared by the per-instance and batched fast paths so their
    reported numbers can never drift apart."""
    K = len(idx.kf)
    paths = path_decompose(p, idx, np.maximum(res.x[:K], 0.0))
    x = temporal_pack(p, idx, res.x, paths=paths)
    m = evaluate(p, x)
    lb = float(res.x[-1]) if idx.n_theta else float(lp.c @ res.x)
    return FastPathResult(schedule=x, metrics=m, lp_lower_bound=lb,
                          lp_primal_residual=res.primal_residual,
                          remaining_gbits=float(np.maximum(
                              p.coflow.size - m.served, 0.0).sum()),
                          lp_x=res.x, lp_y=res.y, index=idx, paths=paths,
                          iterations=res.iterations,
                          lp_cscale=max(float(np.abs(lp.c).max(initial=0.0)),
                                        1e-12))


@trace.batched
def solve_fast(p: ScheduleProblem, objective: str = "energy", *,
               iters: int = 4000, tol: float | None = None,
               shards: int = 1) -> FastPathResult:
    """Single-instance fast path: routing LP -> PDHG -> slot packing ->
    exact re-scoring.

    Args:
      p: the problem; flow sizes in Gbits, capacities/rates in Gbps.
      objective: "energy" (minimize Joules, eq. 22 surrogate), "time"
        (minimize the continuous completion-time bound theta), or "fair"
        (energy re-priced by 1/flow_weight — weighted max-min fairness
        surrogate; equals "energy" when weights are uniform).
      iters: PDHG iterations per restart rung (doubled on each restart,
        up to solve_lp's max_restarts).
      tol: primal-residual target in Gbits; default 1e-4 * max demand.
      shards: row-partition the LP across this many devices (see
        docs/SOLVER.md §9).

    Returns a FastPathResult whose `metrics` are always the exact paper
    equations evaluated on the packed schedule — never LP estimates.

    Determinism: bitwise-reproducible for a fixed (jax version, platform,
    shards); there is no RNG anywhere in the fast path, so repeated
    calls with equal inputs return identical schedules.  The operators
    (COO, tiles, sharded ELL) agree to fp tolerance (~1e-4 relative on
    metrics), not bitwise."""
    lp, idx = build_routing_lp(p, objective)
    res = solve_lp(lp, iters=iters, tol=tol, shards=shards)
    return _assemble_fast_result(p, lp, idx, res)


# ---------------------------------------------------------------------------
# Batched solve (instance axis): block-stacked LPs, one PDHG dispatch
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BlockStackedLP:
    """`B` StructuredLPs joined block-diagonally into one big LP.

    PDHG with diagonal preconditioning decouples exactly over the blocks
    — every coordinate's step size and update depends only on its own
    block — so solving the stacked LP reproduces each instance's own
    trajectory while lowering to flat 1D scatters, which XLA executes
    far better than the batched-index scatters a vmap over per-instance
    COO patterns produces.  All equality rows (across instances) come
    first so the kernel's single m_eq split still applies."""

    lp: StructuredLP               # the stacked LP
    n_off: np.ndarray              # (B+1,) variable offsets
    eq_off: np.ndarray             # (B+1,) equality-row offsets
    ub_off: np.ndarray             # (B+1,) inequality-row offsets


def block_stack(lps: list[StructuredLP]) -> BlockStackedLP:
    n_off = np.cumsum([0] + [lp.n for lp in lps])
    eq_off = np.cumsum([0] + [lp.m_eq for lp in lps])
    ub_off = np.cumsum([0] + [lp.m - lp.m_eq for lp in lps])
    m_eq = int(eq_off[-1])
    rows, cols, vals, cs, xmaxs = [], [], [], [], []
    for i, lp in enumerate(lps):
        is_eq = lp.row < lp.m_eq
        rows.append(np.where(is_eq, lp.row + eq_off[i],
                             m_eq + ub_off[i] + (lp.row - lp.m_eq)))
        cols.append(lp.col + n_off[i])
        vals.append(lp.val)
        cscale = max(float(np.abs(lp.c).max(initial=0.0)), 1e-12)
        cs.append(lp.c / cscale)
        xmaxs.append(np.where(np.isfinite(lp.xmax), lp.xmax, 1e12))
    stacked = StructuredLP(
        c=np.concatenate(cs), row=np.concatenate(rows),
        col=np.concatenate(cols), val=np.concatenate(vals),
        b=np.concatenate([lp.b for lp in lps]),
        h=np.concatenate([lp.h for lp in lps]),
        xmax=np.concatenate(xmaxs))
    return BlockStackedLP(stacked, n_off, eq_off, ub_off)


def _per_instance_residuals(bs: BlockStackedLP, x: np.ndarray) -> np.ndarray:
    """Exact per-instance primal residuals of the stacked iterate."""
    lp = bs.lp
    r = np.zeros(lp.m)
    np.add.at(r, lp.row, lp.val * x[lp.col])
    r -= np.concatenate([lp.b, lp.h])
    B = len(bs.n_off) - 1
    m_eq = lp.m_eq
    out = np.zeros(B)
    for i in range(B):
        eq = r[bs.eq_off[i]:bs.eq_off[i + 1]]
        ub = r[m_eq + bs.ub_off[i]:m_eq + bs.ub_off[i + 1]]
        out[i] = max(np.abs(eq).max(initial=0.0),
                     np.maximum(ub, 0.0).max(initial=0.0))
    return out


def _bucket(x: int, *, minimum: int = 32) -> int:
    """Round a dimension up to the next shape bucket: the smallest value
    >= x of the form mant * 2^e with 8 <= mant < 16 (a 4-bit-mantissa
    grid).  Padding waste stays under ~14% per dimension while the long
    tail of distinct (n, m_eq, m_ub, nnz) shapes a sweep grid or an
    arrival trace produces collapses onto a handful of buckets — so the
    jitted PDHG kernels recompile per bucket, not per exact shape."""
    if x <= minimum:
        return minimum
    e = max(int(x - 1).bit_length() - 4, 0)
    step = 1 << e
    return -(-x // step) * step


def _pad_for_buckets(g: StructuredLP) -> tuple[StructuredLP,
                                               tuple[int, int, int]]:
    """Pad a (stacked) LP to bucketed (n, m_eq, m_ub, nnz).

    Padding is value-neutral: extra COO entries carry val=0 at (row 0,
    col 0) — adding 0.0 to a scatter sum is an fp identity — padded
    variables have c=0/xmax=0 (clipped to 0
    every step), padded equality rows b=0 with no entries, padded
    inequality rows h=0.  Real inequality rows shift up by the equality
    padding; returns the padded LP plus the true (n, m_eq, m_ub) for
    unpadding."""
    n_t, meq_t = g.n, g.m_eq
    mub_t, nnz_t = g.m - g.m_eq, len(g.val)
    n_b, meq_b, mub_b, nnz_b = (_bucket(d)
                                for d in (n_t, meq_t, mub_t, nnz_t))
    if (n_b, meq_b, mub_b, nnz_b) == (n_t, meq_t, mub_t, nnz_t):
        return g, (n_t, meq_t, mub_t)
    row = np.where(g.row < meq_t, g.row, g.row + (meq_b - meq_t))
    pad = nnz_b - nnz_t
    return StructuredLP(
        c=np.concatenate([g.c, np.zeros(n_b - n_t)]),
        row=np.concatenate([row, np.zeros(pad, np.int64)]),
        col=np.concatenate([g.col, np.zeros(pad, np.int64)]),
        val=np.concatenate([g.val, np.zeros(pad)]),
        b=np.concatenate([g.b, np.zeros(meq_b - meq_t)]),
        h=np.concatenate([g.h, np.zeros(mub_b - mub_t)]),
        xmax=np.concatenate([g.xmax, np.zeros(n_b - n_t)]),
    ), (n_t, meq_t, mub_t)


def _tile_layout(lps: list[StructuredLP], g: StructuredLP, bucket: bool
                 ) -> tuple[StructuredLP, tile_spmv.StackedTiles]:
    """Lay out the stack `g` of `lps` (block_stack) for the tile
    operator: each instance's columns, equality rows and inequality rows
    start on a tile boundary, then (with `bucket`) the totals and the
    nnz are padded to shape buckets, and (always) each direction's tile
    count to its capacity (_tile_capacity).  The padding is
    value-neutral, as _pad_for_buckets' is.  So the shapes
    depend on the instances and not on their order, and an instance's
    tiles are the same alone or stacked."""
    plans = [_tile_plan_cached(lp) for lp in lps]
    dim = ((lambda d: _bucket(d, minimum=tile_spmv.LANES)) if bucket
           else (lambda d: d))
    n = dim(sum(p.n_a for p in plans))
    m_eq = dim(sum(p.m_eq_a for p in plans))
    m = m_eq + dim(sum(p.m_ub_a for p in plans))
    nnz = _bucket(len(g.val)) if bucket else len(g.val)
    tiles = _tile_capacity((n, m_eq, m, nnz),
                           (sum(p.kx.tiles for p in plans),
                            sum(p.kty.tiles for p in plans)))
    st = tile_spmv.stack(plans, n, m_eq, m, nnz, *tiles)
    c, xmax, q = np.zeros(n), np.zeros(n), np.zeros(m)
    c[st.pos_n], xmax[st.pos_n] = g.c, g.xmax
    q[st.pos_m] = np.concatenate([g.b, g.h])
    pad = np.zeros(nnz - len(g.val), np.int64)
    return StructuredLP(
        c=c, row=np.concatenate([st.pos_m[g.row], pad]),
        col=np.concatenate([st.pos_n[g.col], pad]),
        val=np.concatenate([g.val, np.zeros(len(pad))]),
        b=q[:m_eq], h=q[m_eq:], xmax=xmax), st


def _tile_capacity(dims: tuple, tiles: tuple[int, int]) -> tuple[int, int]:
    """Padded tile counts (K.x, K^T.y) for a dispatch of `dims` holding
    `tiles`.  Draws of one LP shape differ in their tile counts by a few
    per cent (a relabelled rack or pod moves nonzeros between tiles), so
    a count is padded to the shape bucket of itself plus 1/16, and never
    below the largest padded count given before for the same dims: the
    draws then share one compiled program, as their other dims do."""
    old = _TILE_CAPACITY.get(dims, (0, 0))
    cap = tuple(c if t <= c else _bucket(t + t // 16)
                for t, c in zip(tiles, old))
    _TILE_CAPACITY[dims] = cap
    return cap


def _tiled_spmv() -> bool:
    """Whether single-device dispatches apply K as dense tiles
    (core.tile_spmv).  On a TPU every scalar gather and scatter-add of
    the COO pair costs a fixed time, so they do; on a CPU, whose scatter
    is cheap, the COO pair stays, bit for bit."""
    return jax.default_backend() == "tpu"


def _stage_xla(lps: list[StructuredLP], bs: BlockStackedLP, *,
               bucket: bool, adaptive: bool = False, chunk: int = 0,
               tols: np.ndarray | None = None) -> _Staged:
    """Stage one single-device dispatch of `lps`, block-stacked as `bs`:
    lay it out and upload it.  K is applied as dense tiles exactly when
    _tiled_spmv() holds, else as COO scatters.  With `bucket` the dims
    (and, adaptive, the instance count) are padded to shape buckets, so
    nearby shapes share one compiled program; the padding is
    value-neutral (see _pad_for_buckets).  The tile counts are padded
    either way (_tile_capacity).

    With `adaptive`, a run is the fused loop of _pdhg_run_adaptive
    (chunks of `chunk`, instance i frozen once its residual is within
    `tols[i]`; primal and gap None), else one fixed burst of
    _pdhg_resume (the kernel's primal and gap)."""
    g = bs.lp
    B = len(lps)
    # `pos_n` / `pos_m` say where each column and row of `g` went
    if _tiled_spmv():
        gp, st = _tile_layout(lps, g, bucket)
        pos_n, pos_m = st.pos_n, st.pos_m
        tiles = tuple(jnp.asarray(a) for a in st.arrays())
        t_kx, t_kty = st.kx.tiles, st.kty.tiles
    else:
        gp, (n_t, meq_t, mub_t) = (
            _pad_for_buckets(g) if bucket
            else (g, (g.n, g.m_eq, g.m - g.m_eq)))
        pos_n = np.arange(n_t)
        pos_m = np.concatenate([np.arange(meq_t),
                                gp.m_eq + np.arange(mub_t)])
        tiles, t_kx, t_kty = None, 0, 0
    args = (jnp.asarray(gp.c), jnp.asarray(gp.row),
            jnp.asarray(gp.col), jnp.asarray(gp.val),
            jnp.asarray(gp.b), jnp.asarray(gp.h),
            jnp.asarray(gp.xmax))
    dims = (gp.m, gp.n, gp.m_eq)
    shape = (gp.n, gp.m, gp.m_eq, len(gp.val), t_kx, t_kty)
    tile_slots = (t_kx + t_kty) * tile_spmv.SLOTS // 2

    def place(x0, y0):
        x0p, y0p = np.zeros(gp.n), np.zeros(gp.m)
        x0p[pos_n], y0p[pos_m] = x0, y0
        return jnp.asarray(x0p), jnp.asarray(y0p)

    def unpad(x, y):
        return np.asarray(x)[pos_n], np.asarray(y)[pos_m]

    if not adaptive:
        def run(x, y, budget):
            x, y, primal, gap = _pdhg_resume(*args, x, y, *dims, budget,
                                             tiles)
            return x, y, np.full(B, budget), primal, gap
        return _Staged(place, run, unpad, ("fixed",) + shape,
                       len(gp.val), tile_slots)

    # padded coords go to the dump segment num_b; fake instances
    # (instance-count bucketing) have no rows and tol=inf, so they
    # freeze at the first residual check
    num_b = (1 << max(B - 1, 0).bit_length()) if bucket else B
    inst_n = np.full(gp.n, num_b, np.int32)
    inst_n[pos_n] = np.repeat(np.arange(B), np.diff(bs.n_off))
    inst_m = np.full(gp.m, num_b, np.int32)
    inst_m[pos_m] = np.concatenate(
        [np.repeat(np.arange(B), np.diff(bs.eq_off)),
         np.repeat(np.arange(B), np.diff(bs.ub_off))])
    tols_d = jnp.asarray(np.concatenate([tols, np.full(num_b - B, np.inf)]))
    inst_n_d, inst_m_d = jnp.asarray(inst_n), jnp.asarray(inst_m)

    def run(x, y, budget):
        x, y, _, used_chunks = _pdhg_run_adaptive(
            *args, x, y, tols_d, inst_n_d, inst_m_d, num_b,
            *dims, chunk, budget // chunk, tiles)
        return x, y, np.asarray(used_chunks)[:B] * chunk, None, None
    return _Staged(place, run, unpad, ("adaptive", chunk, num_b) + shape,
                   len(gp.val), tile_slots)


def solve_lp_batch(lps: list[StructuredLP], iters: int = 4000, *,
                   tol: float | None = None, max_restarts: int = 3,
                   adaptive: bool = True, chunk: int = 500,
                   warm_starts: list[tuple[np.ndarray, np.ndarray]] | None
                   = None, bucket: bool = True,
                   shards: int = 1) -> list[PDHGResult]:
    """Solve a batch of LPs over the instance axis in one jitted PDHG
    dispatch (block-diagonal stacking; see BlockStackedLP).

    Both modes run an escalation ladder that re-stacks only the
    still-unconverged instances each level, so every instance follows
    exactly the trajectory of its solo solve.  With `adaptive=True`
    (default) each level's convergence loop is fused into the dispatch:
    per-instance residuals are checked on-device every `chunk`
    iterations and converged instances freeze, so a level stops within
    `chunk` iterations of its last straggler.  With `adaptive=False`
    the levels are the exact solve_lp warm-restart ladder (iters, then
    doubled), reproducing per-instance solve_lp results bit-for-bit
    (used by equivalence tests).  Both cap at the ladder's total budget
    (sum of iters * 2**a for a <= max_restarts).

    `warm_starts[i] = (x0, y0)` seeds instance i's primal/dual iterates
    (shapes (lps[i].n,) and (lps[i].m,), y0 ordered [eq; ub]); with the
    adaptive mode an instance already near its tolerance then freezes
    after the first `chunk`-iteration burst, which is what makes whole
    failure-ensemble re-solves cheap (see solve_fast_ensemble).

    Determinism: no RNG; results are reproducible for fixed inputs and
    jax build, and independent of batch composition up to the float
    reduction order of the stacked scatters.

    Every dispatch is staged by _stage_xla, which picks the operator.
    `bucket=True` (default) pads every stacked dispatch's (n, m_eq,
    m_ub, nnz) — and the instance count — up to shape-bucket boundaries
    (_bucket: 4-bit-mantissa grid, <~14% padding waste), so grid cells
    and arrival epochs with nearby shapes reuse one compiled executable
    instead of recompiling per exact shape.  The padding is
    value-neutral (see _pad_for_buckets), so results match the
    unbucketed dispatch to fp reduction order; `bucket=False` restores
    exact-shape dispatches.

    `shards` > 1 row-partitions each stacked dispatch across that many
    devices and runs fixed sharded bursts (no in-dispatch adaptive loop —
    the outer re-stacking ladder plus the host-side per-instance
    residual check provides the convergence control; see solve_lp)."""
    _check_shards(shards)
    B = len(lps)
    all_tols = np.array([tol if tol is not None
                         else 1e-4 * max(float(np.abs(lp.b).max(initial=0.0)),
                                         1.0)
                         for lp in lps])

    def _run(sub: list[int], states, budget: int):
        """One stacked dispatch over the instances in `sub`; returns
        (x, y, residuals, iterations) split per instance."""
        with trace.span("pdhg.stack"):
            bs = block_stack([lps[i] for i in sub])
            g = bs.lp
            if states is None:
                x0, y0 = np.zeros(g.n), np.zeros(g.m)
            else:
                x0 = np.concatenate([states[i][0] for i in sub])
                y0 = np.concatenate(
                    [states[i][1][:lps[i].m_eq] for i in sub]
                    + [states[i][1][lps[i].m_eq:] for i in sub])
            st = (_stage_sharded(g, shards, len(sub)) if shards > 1
                  else _stage_xla([lps[i] for i in sub], bs, bucket=bucket,
                                  adaptive=adaptive, chunk=chunk,
                                  tols=all_tols[sub]))
            _note_dispatch(st.key + (budget,))
        with trace.span("pdhg.run"):
            x, y, used = st.run(*st.place(x0, y0), budget)[:3]
            x_np, y_np = st.unpad(x, y)
        _note_work(used, st.nnz, [len(lps[i].val) for i in sub],
                   st.tile_slots)
        with trace.span("pdhg.unstack"):
            res = _per_instance_residuals(bs, x_np)
            outs = {}
            for j, i in enumerate(sub):
                xi = x_np[bs.n_off[j]:bs.n_off[j + 1]]
                yi = np.concatenate(
                    [y_np[bs.eq_off[j]:bs.eq_off[j + 1]],
                     y_np[g.m_eq + bs.ub_off[j]:g.m_eq + bs.ub_off[j + 1]]])
                outs[i] = (xi, yi, float(res[j]), int(used[j]))
        return outs

    # escalation ladder with re-stacking: each level runs only the
    # still-unconverged instances (warm-started), so a converged instance
    # stops exactly where its solo solve would and stragglers don't drag
    # the full batch width through their extra iterations.  adaptive=True
    # fuses chunked convergence checks into the dispatch and starts from
    # a fraction of `iters` (the recompile per level shape is cheap next
    # to the width x iterations it saves); adaptive=False reproduces the
    # per-instance solve_lp ladder (iters, then doubled, warm-started)
    # exactly.  Both cap at the ladder's total budget.
    x_fin = {}
    y_fin = {}
    res_fin = np.zeros(B)
    iters_fin = np.zeros(B, dtype=int)
    states = None
    if warm_starts is not None:
        assert len(warm_starts) == B
        states = {i: (np.asarray(x0, np.float64), np.asarray(y0, np.float64))
                  for i, (x0, y0) in enumerate(warm_starts)}
        for i, (x0, y0) in states.items():
            assert x0.shape == (lps[i].n,) and y0.shape == (lps[i].m,), \
                (i, x0.shape, y0.shape, lps[i].n, lps[i].m)
            x_fin[i], y_fin[i] = x0, y0
    # degenerate members (zero-flow instances: no rows or no variables)
    # solve in closed form and never enter the stacked dispatches
    active = []
    for i in range(B):
        if lps[i].n == 0 or lps[i].m == 0:
            triv = _solve_lp_trivial(lps[i])
            x_fin[i], y_fin[i] = triv.x, triv.y
        else:
            active.append(i)
    total_budget = sum(iters * 2 ** a for a in range(max_restarts + 1))
    budget = max(chunk, iters // 4) if adaptive else iters
    spent = 0
    while active and spent < total_budget:
        budget = min(budget, total_budget - spent)
        if adaptive:
            # whole chunks only, so a level never exceeds its budget and
            # per-instance iteration accounting stays exact
            budget = max(chunk, budget - budget % chunk)
        outs = _run(active, states, budget)
        states = states or {}
        for i, (xi, yi, ri, ki) in outs.items():
            states[i] = (xi, yi)
            x_fin[i], y_fin[i] = xi, yi
            res_fin[i], iters_fin[i] = ri, iters_fin[i] + ki
        active = [i for i in active if res_fin[i] > all_tols[i]]
        spent += budget
        budget *= 2

    # the per-instance gap proxy mirrors the kernel's (|c.x + q.y| form)
    return [PDHGResult(x_fin[i], float(res_fin[i]),
                       _gap(lp, x_fin[i], y_fin[i]), int(iters_fin[i]),
                       y=y_fin[i])
            for i, lp in enumerate(lps)]


def solve_fast_batch(problems: list[ScheduleProblem],
                     objective: str = "energy", *,
                     iters: int = 4000, tol: float | None = None,
                     adaptive: bool = True, bucket: bool = True,
                     shards: int = 1) -> list[FastPathResult]:
    """Batched fast path over ScheduleProblems sharing one topology.

    The routing LPs (which differ per instance through task placement and
    flow sizes) are stacked over the instance axis and solved in a single
    jitted adaptive PDHG dispatch — one XLA call for the whole seed
    vector instead of one per instance, with the convergence loop fused
    in-graph (see solve_lp_batch); slot packing and the exact paper-model
    re-evaluation stay per-instance (they are cheap numpy passes).

    Units and determinism are as in solve_fast; each element of the
    returned list reports exact paper-model metrics for its instance.
    Instances may differ in capacities (e.g. the same topology under
    different degradations) — only vertex/edge structure must match;
    for fully heterogeneous instance lists use solve_fast_ensemble
    (which this call delegates to after the structure check)."""
    if not problems:
        return []
    t0 = problems[0].topo
    for p in problems[1:]:
        t = p.topo
        if t is not t0 and (t.n_vertices != t0.n_vertices
                            or t.n_edges != t0.n_edges
                            or not np.array_equal(t.edges, t0.edges)):
            raise ValueError("solve_fast_batch requires a shared topology "
                             f"structure; got {t0.name} and {t.name}")
    return solve_fast_ensemble(problems, objective, iters=iters, tol=tol,
                               adaptive=adaptive, chunk=500,
                               bucket=bucket, shards=shards)


# ---------------------------------------------------------------------------
# Incremental re-solves (degraded topologies, core.failures)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EnsembleStats:
    """Counters for warm re-solves.

    `members` counts the instances `solve_fast_ensemble` solved,
    `warm_members` those it started from a projected warm state, and
    `warm_iters` the PDHG iterations of those warm members, summed;
    `warm_iters / warm_members` is the iterations a warm re-plan costs.
    `kept_gbits` is the healthy path volume every `project_warm_start`
    call kept on paths whose hops all survive, `rerouted_gbits` the
    volume it moved from dead paths onto a surviving route.  Read via
    `ensemble_stats()`, clear via `reset_ensemble_stats()`."""

    members: int = 0
    warm_members: int = 0
    warm_iters: int = 0
    kept_gbits: float = 0.0
    rerouted_gbits: float = 0.0

    def snapshot(self) -> "EnsembleStats":
        return dataclasses.replace(self)


ENSEMBLE_STATS = EnsembleStats()


def ensemble_stats() -> EnsembleStats:
    """The live warm re-solve counters (see EnsembleStats)."""
    return ENSEMBLE_STATS


def reset_ensemble_stats() -> None:
    """Zero the warm re-solve counters."""
    for f in dataclasses.fields(EnsembleStats):
        setattr(ENSEMBLE_STATS, f.name, f.default)


@trace.spanned("warm.project")
def project_warm_start(warm: FastPathResult, p_dst: ScheduleProblem,
                       lp_dst: StructuredLP, idx_dst: RoutingIndex, *,
                       flow_map: np.ndarray | None = None
                       ) -> tuple[np.ndarray, np.ndarray]:
    """Map a finished solve's PDHG state onto a structurally related LP.

    Intended for healthy -> degraded re-solves where `p_dst` keeps the
    source instance's device/edge indexing (core.failures preserves it):

      * the healthy routing is re-used *by path* — each decomposed
        src->dst path whose every (edge, wavelength) hop is still
        admissible keeps its volume; paths crossing a failed link are
        dropped and their volume is re-routed onto any surviving
        admissible route (found by the same wavelength-continuity DFS
        path_decompose uses), so the primal start conserves flow exactly;
      * duals transfer row-by-row through RoutingIndex.eq_keys/ub_keys
        (rows that vanished with their edges are dropped, new rows start
        at zero).

    Returns (x0, y0) with x0 clipped into [0, xmax]; feed them to
    solve_lp or solve_lp_batch(warm_starts=...).  The projection is a
    heuristic start, not a feasible point — PDHG repairs the remaining
    demand/capacity mismatch, which for localized failures takes a small
    fraction of a cold solve's iterations.

    `flow_map` generalizes the projection to LPs whose *flow indexing*
    differs from the source solve's (the rolling-horizon arrival engine,
    core.arrivals, carries residual flows forward under new indices and
    appends newly arrived flows): flow_map[i] is the source-instance
    flow that dst flow i continues, or -1 for a brand-new flow (which
    starts cold).  None keeps the historical identity mapping."""
    src_idx = warm.index
    if src_idx is None or warm.lp_x is None:
        raise ValueError("warm result lacks PDHG state (lp_x/index); "
                         "it must come from solve_fast/solve_fast_batch")
    F, E, W, _ = p_dst.shape_x
    if flow_map is not None:
        flow_map = np.asarray(flow_map, dtype=np.int64)
        if flow_map.shape != (F,):
            raise ValueError(f"flow_map shape {flow_map.shape} != ({F},)")
    # dst flow of each source flow (identity when flow_map is None)
    dst_of = ({int(s): i for i, s in enumerate(flow_map) if s >= 0}
              if flow_map is not None else None)

    def src_key(key):
        """Translate a dst row identity to the source instance's."""
        if flow_map is not None and key[0] in ("c", "d"):
            fs = int(flow_map[key[1]])
            if fs < 0:
                return None
            return (key[0], fs) + key[2:]
        return key

    K_dst = len(idx_dst.kf)
    key_dst = (idx_dst.kf * E + idx_dst.ke) * W + idx_dst.kw   # sorted

    def dst_pos(f, e, w):
        key = (f * E + e) * W + w
        j = int(np.searchsorted(key_dst, key))
        return j if j < K_dst and key_dst[j] == key else -1

    x0 = np.zeros(lp_dst.n)
    ke_s, kw_s = src_idx.ke, src_idx.kw
    size_dst = p_dst.coflow.size
    lost = np.zeros(F)
    shipped = np.zeros(F)
    for path in warm.paths or []:
        f = (path.flow if dst_of is None else dst_of.get(path.flow, -1))
        if f < 0 or f >= F or size_dst[f] <= 0.0 or path.volume <= 0.0:
            continue
        hops = [(int(ke_s[k]), int(kw_s[k])) for k in path.triples]
        pos = [dst_pos(f, e, w) for e, w in hops]
        vol = min(path.volume, float(size_dst[f]) - shipped[f])
        if vol <= 0.0:
            continue
        if all(j >= 0 for j in pos):
            for j in pos:
                x0[j] += vol
            x0[K_dst + f * W + hops[0][1]] += vol
            shipped[f] += vol
        else:
            lost[f] += vol
    ENSEMBLE_STATS.kept_gbits += float(shipped.sum())

    # re-route volume stranded by failed hops onto any surviving route
    out_edges = _out_edges(p_dst)
    convert_ok = p_dst.is_server | p_dst.is_switch
    for f in np.flatnonzero(lost > 0.0):
        f = int(f)
        vol = min(lost[f], float(size_dst[f]) - shipped[f])
        if vol <= 0.0:
            continue
        trail = _route_search(
            p_dst, out_edges, int(p_dst.coflow.src[f]),
            int(p_dst.coflow.dst[f]),
            lambda e, w, f=f: dst_pos(f, e, w) >= 0, convert_ok)
        if not trail:
            continue
        for e, w in trail:
            x0[dst_pos(f, e, w)] += vol
        x0[K_dst + f * W + trail[0][1]] += vol
        shipped[f] += vol
        ENSEMBLE_STATS.rerouted_gbits += float(vol)

    if idx_dst.n_theta:
        # theta couples every capacity row (sum x <= limit * theta); the
        # healthy theta is stale on a degraded fabric, so lift it to the
        # smallest value that makes the projected routing capacity-feasible
        # — otherwise the warm start dumps residual on every coupled row
        theta = float(warm.lp_x[-1]) if src_idx.n_theta else 0.0
        kx = np.zeros(lp_dst.m)
        np.add.at(kx, lp_dst.row, lp_dst.val * x0[lp_dst.col])
        th = (lp_dst.col == lp_dst.n - 1) & (lp_dst.row >= lp_dst.m_eq)
        if th.any():
            limits = -lp_dst.val[th]
            need = kx[lp_dst.row[th]] / np.maximum(limits, 1e-12)
            theta = max(theta, float(need.max(initial=0.0)))
        x0[-1] = theta
    x0 = np.clip(x0, 0.0, np.where(np.isfinite(lp_dst.xmax),
                                   lp_dst.xmax, 1e12))

    y0 = np.zeros(lp_dst.m)
    if (warm.lp_y is not None and src_idx.eq_keys is not None
            and idx_dst.eq_keys is not None):
        # both LPs are solved with max-normalized objectives (c / cscale);
        # duals of the normalized problems relate by the cscale ratio, so
        # rescale before transplanting (matters when a failure changes the
        # cost vector, e.g. halved capacities double the device-cost terms)
        cscale_dst = max(float(np.abs(lp_dst.c).max(initial=0.0)), 1e-12)
        rescale = warm.lp_cscale / cscale_dst
        m_eq_src = len(src_idx.eq_keys)
        src_eq = {k: i for i, k in enumerate(src_idx.eq_keys)}
        src_ub = {k: i for i, k in enumerate(src_idx.ub_keys)}
        for i, k in enumerate(idx_dst.eq_keys):
            ks = src_key(k)
            j = src_eq.get(ks) if ks is not None else None
            if j is not None:
                y0[i] = warm.lp_y[j] * rescale
        for i, k in enumerate(idx_dst.ub_keys):
            j = src_ub.get(k)          # capacity rows carry no flow index
            if j is not None:
                y0[lp_dst.m_eq + i] = warm.lp_y[m_eq_src + j] * rescale
    return x0, y0


def stranded_volume(warm: FastPathResult, p_dst: ScheduleProblem, *,
                    flow_map: np.ndarray | None = None) -> np.ndarray:
    """(F_dst,) Gbits of `warm`'s decomposed path volume whose hops died.

    A path is *stranded* when any of its (edge, wavelength) hops is no
    longer admissible under `p_dst` (capacity zeroed by a failure, or
    the hop pruned from the flow's edge mask).  This is exactly the
    volume `project_warm_start` drops and re-routes via the surviving
    admissible routes — the chaos drivers (core.arrivals.run_online,
    service.loop.run_service) report its sum as stranded-Gbits
    re-routed.  `flow_map` has project_warm_start's semantics; per-flow
    totals are clipped to the dst residual demand.  Returns zeros when
    the warm result carries no decomposed paths."""
    F = p_dst.coflow.n_flows
    stranded = np.zeros(F)
    if warm.index is None or not warm.paths:
        return stranded
    dst_of = ({int(s): i for i, s in enumerate(np.asarray(flow_map))
               if s >= 0} if flow_map is not None else None)
    ke_s, kw_s = warm.index.ke, warm.index.kw
    for path in warm.paths:
        f = (path.flow if dst_of is None else dst_of.get(path.flow, -1))
        if f < 0 or f >= F or path.volume <= 0.0:
            continue
        dead = any(not (p_dst.edge_w_ok[int(ke_s[k]), int(kw_s[k])]
                        and p_dst.flow_edge_mask[f, int(ke_s[k])])
                   for k in path.triples)
        if dead:
            stranded[f] += path.volume
    return np.minimum(stranded, p_dst.coflow.size)


def resolve_incremental(p: ScheduleProblem, objective: str,
                        warm: FastPathResult, *, iters: int = 4000,
                        tol: float | None = None,
                        shards: int = 1) -> FastPathResult:
    """Re-solve a degraded instance starting from a healthy solution.

    `p` is the degraded problem (same coflow/flow indexing as the healthy
    one — core.failures.degrade_problem builds it); `warm` is the healthy
    instance's FastPathResult.  Routes over failed edges are dropped,
    affected flows are re-routed via the decomposed healthy paths, and
    PDHG restarts from the projected primal/dual state instead of zero.
    Output is a full FastPathResult (packed, exactly re-scored) and can
    itself warm-start further re-solves (cascading failures)."""
    lp, idx = build_routing_lp(p, objective)
    x0, y0 = project_warm_start(warm, p, lp, idx)
    res = solve_lp(lp, iters=iters, tol=tol, x0=x0, y0=y0, shards=shards)
    return _assemble_fast_result(p, lp, idx, res)


@trace.batched
def solve_fast_warm(p: ScheduleProblem, objective: str = "energy", *,
                    warm: FastPathResult | None = None,
                    flow_map: np.ndarray | None = None,
                    iters: int = 4000, tol: float | None = None,
                    chunk: int = 250, bucket: bool = True,
                    shards: int = 1) -> FastPathResult:
    """Single-instance fast path with an optional projected warm start and
    the fused adaptive convergence loop.

    This is the epoch re-solve primitive of the rolling-horizon arrival
    engine (core.arrivals): unlike solve_fast — whose restart ladder
    always spends its full first rung — the adaptive chunked dispatch
    (solve_lp_batch with B=1) freezes within one `chunk`-iteration
    residual check of convergence, so a good warm start actually shows
    up as saved iterations and wall time.

    `warm` is a previous FastPathResult to project onto this problem
    (project_warm_start); `flow_map[i]` names the warm instance's flow
    that flow i of `p` continues (-1 = new flow, identity when None).
    The start degrades gracefully to cold: if `warm` lacks PDHG state,
    its topology shape differs from `p`'s (different edge/wavelength
    indexing — the projection would be meaningless), or the projection
    itself fails, the solve silently starts from zero."""
    lp, idx = build_routing_lp(p, objective)
    warm_starts = None
    if (warm is not None and warm.index is not None
            and warm.lp_x is not None and warm.schedule is not None
            and warm.schedule.shape[1:3] == (p.topo.n_edges,
                                             p.topo.n_wavelengths)):
        try:
            warm_starts = [project_warm_start(warm, p, lp, idx,
                                              flow_map=flow_map)]
        except (ValueError, KeyError, IndexError):
            warm_starts = None         # structure changed -> cold start
    res = solve_lp_batch([lp], iters=iters, tol=tol, chunk=chunk,
                         warm_starts=warm_starts, bucket=bucket,
                         shards=shards)[0]
    out = _assemble_fast_result(p, lp, idx, res)
    out.warm_started = warm_starts is not None
    return out


@trace.batched
def solve_fast_ensemble(problems: list[ScheduleProblem],
                        objective: str = "energy", *,
                        warm: list[FastPathResult] | None = None,
                        iters: int = 4000, tol: float | None = None,
                        adaptive: bool = True, chunk: int | None = None,
                        bucket: bool = True,
                        shards: int = 1) -> list[FastPathResult]:
    """Batched fast path over a (possibly heterogeneous) instance list.

    Unlike solve_fast_batch this does not require a shared topology —
    the block-diagonal stacking never did — so a whole failure ensemble
    (one degraded topology per member) solves in the same fused adaptive
    dispatches as a seed vector.  With `warm[i]` set to the healthy
    result that instance i degrades, every member starts from its
    projected healthy state (project_warm_start) and the in-graph
    freezing stops it within one residual-check chunk of convergence;
    benchmarks/failure_bench.py measures the aggregate effect vs cold
    starts."""
    if not problems:
        return []
    built = [build_routing_lp(p, objective) for p in problems]
    lps = [lp for lp, _ in built]
    warm_starts = None
    if warm is not None:
        assert len(warm) == len(problems)
        warm_starts = [project_warm_start(w, p, lp, idx)
                       for w, p, (lp, idx) in zip(warm, problems, built)]
    if chunk is None:
        # warm starts usually converge within a burst or two, so check
        # residuals at a finer grain than the cold default — the saved
        # iterations outweigh the extra on-device segment-max checks
        chunk = 250 if warm_starts is not None else 500
    results = solve_lp_batch(lps, iters=iters, tol=tol, adaptive=adaptive,
                             chunk=chunk, warm_starts=warm_starts,
                             bucket=bucket, shards=shards)
    ENSEMBLE_STATS.members += len(problems)
    if warm_starts is not None:
        ENSEMBLE_STATS.warm_members += len(problems)
        ENSEMBLE_STATS.warm_iters += sum(r.iterations for r in results)
    return [_assemble_fast_result(p, lp, idx, res)
            for p, (lp, idx), res in zip(problems, built, results)]


@trace.batched
def solve_fast_group(problems: list[ScheduleProblem],
                     objectives: list[str] | str = "energy", *,
                     warm: list[FastPathResult | None] | None = None,
                     flow_maps: list[np.ndarray | None] | None = None,
                     iters: int = 4000, tol: float | None = None,
                     adaptive: bool = True, chunk: int = 250,
                     bucket: bool = True,
                     shards: int = 1) -> list[FastPathResult]:
    """One stacked dispatch over a heterogeneous tenant group.

    The coalescing primitive of the multi-tenant scheduler service
    (repro.service): like solve_fast_ensemble it block-stacks arbitrary
    instances into a single fused adaptive PDHG dispatch, but each
    member carries its *own* objective ("energy" or "time" — tenants
    choose independently) and its own rolling-horizon warm state.

    `warm[i]` is member i's previous-epoch FastPathResult (or None for
    a cold member) and `flow_maps[i]` names, per flow of `problems[i]`,
    the warm instance's flow it continues (-1 = new; see
    project_warm_start).  Warm projection degrades gracefully per
    member, exactly like solve_fast_warm: a member whose warm state is
    missing, shape-incompatible, or whose projection raises starts cold
    (zero iterates) without disturbing its group-mates; the returned
    results' `warm_started` flags record what really ran.

    Because stacked PDHG decouples exactly over the blocks, every
    member's trajectory — and therefore its schedule and metrics —
    matches its own solve_fast_warm solve with the same `chunk`, up to
    floating-point reduction order (the service's coalescing-
    correctness test pins this at 1e-4 relative).  Degenerate members
    (zero flows) solve in closed form inside solve_lp_batch and never
    widen the dispatch."""
    if not problems:
        return []
    B = len(problems)
    if isinstance(objectives, str):
        objectives = [objectives] * B
    if len(objectives) != B:
        raise ValueError(f"{len(objectives)} objectives for {B} problems")
    warm_list = warm if warm is not None else [None] * B
    maps = flow_maps if flow_maps is not None else [None] * B
    if len(warm_list) != B or len(maps) != B:
        raise ValueError("warm/flow_maps length must match problems")
    built = [build_routing_lp(p, o) for p, o in zip(problems, objectives)]
    starts: list[tuple[np.ndarray, np.ndarray]] = []
    flags: list[bool] = []
    for p, (lp, idx), w, fm in zip(problems, built, warm_list, maps):
        x0y0 = None
        if (w is not None and w.index is not None and w.lp_x is not None
                and w.schedule is not None
                and w.schedule.shape[1:3] == (p.topo.n_edges,
                                              p.topo.n_wavelengths)):
            try:
                x0y0 = project_warm_start(w, p, lp, idx, flow_map=fm)
            except (ValueError, KeyError, IndexError):
                x0y0 = None            # structure changed -> cold member
        starts.append(x0y0 if x0y0 is not None
                      else (np.zeros(lp.n), np.zeros(lp.m)))
        flags.append(x0y0 is not None)
    lps = [lp for lp, _ in built]
    results = solve_lp_batch(lps, iters=iters, tol=tol, adaptive=adaptive,
                             chunk=chunk,
                             warm_starts=starts if any(flags) else None,
                             bucket=bucket, shards=shards)
    out = []
    for (p, (lp, idx), res, f) in zip(problems, built, results, flags):
        r = _assemble_fast_result(p, lp, idx, res)
        r.warm_started = f
        out.append(r)
    return out
