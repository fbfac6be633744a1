"""Pallas fused PDHG iteration burst over a blocked-ELL sparse operator.

The routing-LP hot loop (core.solver) spends essentially all of its time
in two sparse mat-vecs per iteration — K.x and K^T.y over the COO
constraint matrix — plus elementwise prox/clip updates.  The XLA backend
lowers these to 1-D scatter-adds; this module provides the alternative
`backend="pallas"` lowering: the COO operator is re-packed into a padded
**blocked-ELL** layout (gather-friendly, no scatters at all) and a whole
`iters`-iteration PDHG burst — K^T.y gather, primal prox/clip against
xmax, K.x, dual ascent + inequality projection, and the terminal
residual vector — runs as ONE Pallas kernel with every vector resident
in VMEM.

Blocked-ELL layout (`ell_blocks` / `ell_pack`)
----------------------------------------------
Rows keep their original order (no permutation — PDHG vectors stay in LP
index space) and are grouped into blocks of `bm` consecutive rows; each
block is padded to its own width (the block's max row degree, rounded up
to a multiple of `align`) and stored row-major in one flat (idx, val)
pair.  Padding entries carry idx=0, val=0 so they gather slot 0 and
contribute nothing.  Per-block widths matter because the LP's row
degrees cluster hard by construction — conservation rows carry ~2-5
entries while server-egress rows carry hundreds — and a single global
width would pad the narrow majority to the wide tail.  The transpose
direction (K^T for the primal update) is the same layout built from the
column index.

Both directions ship with a pure-jnp oracle (`kernels.ref.ell_spmv` /
`ref.pdhg_ell_burst_ref`) and are validated on CPU via `interpret=True`
(tests/test_pdhg_kernels.py).  On a TPU, Mosaic refuses the fused burst:
the flat `jnp.take` in `spmv_blocks` raises "Only 2D gather is
supported" (tests/test_tpu_compile.py).  The row-sharded body
(`pdhg_update_burst_sharded`) is plain jnp under shard_map and compiles
(see docs/KERNELS.md for the layout/padding rules and the VMEM limit).

Trajectory contract: the kernel computes exactly the update of
`core.solver._pdhg_ops` — same preconditioners, same prox, same freeze
masks — so `backend="pallas"` differs from `"xla"` only by the
floating-point reduction order of the SpMV (gather row-sums vs
scatter-adds).  Metrics agree to ~1e-4 relative; bit-for-bit identity is
NOT promised and the default backend stays "xla".
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


@dataclasses.dataclass(frozen=True)
class EllBlocks:
    """One SpMV direction in blocked-ELL: per stored row, a padded gather.

    Block b holds rows [b*bm, (b+1)*bm) in row-major order at
    idx/val[offsets[b] : offsets[b] + bm*widths[b]]; `n_rows` true rows,
    padded up to `n_rows_pad = n_blocks * bm` with empty rows."""

    idx: np.ndarray            # (total,) int32 gather indices, 0 for padding
    val: np.ndarray            # (total,) float coefficients, 0 for padding
    offsets: tuple[int, ...]   # (n_blocks,) flat start of each block
    widths: tuple[int, ...]    # (n_blocks,) padded width of each block
    bm: int                    # rows per block
    n_rows: int                # true row count
    n_rows_pad: int            # n_blocks * bm

    @property
    def meta(self) -> tuple:
        """Hashable static description for jit caching."""
        return (self.offsets, self.widths, self.bm, self.n_rows_pad)

    @property
    def fill(self) -> float:
        """Fraction of stored slots that carry a real entry."""
        return float(np.count_nonzero(self.val)) / max(len(self.val), 1)


@dataclasses.dataclass(frozen=True)
class EllPlanSide:
    """The value-independent half of one blocked-ELL direction: gather
    indices and storage layout, plus the (order, flat) permutation that
    scatters COO values into storage slots.  Built once per sparsity
    pattern; `ell_refill` turns it into an EllBlocks for any coefficient
    vector in O(nnz) (core.solver caches plans across re-solves so a
    warm-started epoch never pays the argsort/width scan again)."""

    idx: np.ndarray            # (total,) int32 gather indices, 0 for padding
    order: np.ndarray          # (nnz,) stable row-sort permutation of COO
    flat: np.ndarray           # (nnz,) storage slot of each sorted entry
    size: int                  # total storage slots
    offsets: tuple[int, ...]
    widths: tuple[int, ...]
    bm: int
    n_rows: int
    n_rows_pad: int


def ell_blocks_plan(row: np.ndarray, col: np.ndarray, n_rows: int, *,
                    bm: int = 8, align: int = 8,
                    min_widths: np.ndarray | None = None) -> EllPlanSide:
    """Lay out COO entries (keyed by `row`) in blocked-ELL storage.

    Entries keep their COO appearance order within each row (stable
    sort), so repeated packs of the same operator are bit-identical.
    `bm` rows per block; each block's width is its max row degree rounded
    up to a multiple of `align` (>= align even for all-empty blocks, so
    every block is addressable with one static-shape gather).

    `min_widths` (one entry per block, already align-rounded) forces each
    block at least that wide — the sharded packer uses it to give every
    shard's pack identical static meta (the elementwise max of the
    per-shard widths), so `shard_map` traces one program for all shards.
    Extra forced slots are plain padding (idx=0, val=0)."""
    assert bm >= 1 and align >= 1
    row = np.asarray(row, np.int64)
    nnz = len(row)
    order = np.argsort(row, kind="stable")
    counts = np.bincount(row, minlength=max(n_rows, 1))
    starts = np.concatenate([[0], np.cumsum(counts)])
    # position of each entry within its row
    pos = np.arange(nnz, dtype=np.int64) - starts[row[order]]

    n_blocks = max(-(-n_rows // bm), 1)
    # per-block width: max row degree in the block, align-rounded (>= align)
    cpad = np.zeros(n_blocks * bm, np.int64)
    lim = min(len(counts), n_blocks * bm)
    cpad[:lim] = counts[:lim]
    w = cpad.reshape(n_blocks, bm).max(axis=1)
    w = np.maximum(-(-w // align) * align, align)
    if min_widths is not None:
        assert len(min_widths) == n_blocks, (len(min_widths), n_blocks)
        w = np.maximum(w, np.asarray(min_widths, np.int64))
    widths_arr = w
    offsets_arr = np.concatenate([[0], np.cumsum(bm * w)[:-1]])
    off = int(np.sum(bm * w))

    r = row[order]
    blk = r // bm
    flat = offsets_arr[blk] + (r - blk * bm) * widths_arr[blk] + pos
    idx = np.zeros(off, np.int32)
    idx[flat] = np.asarray(col, np.int64)[order].astype(np.int32)
    return EllPlanSide(idx=idx, order=order, flat=flat, size=off,
                       offsets=tuple(int(o) for o in offsets_arr),
                       widths=tuple(int(x) for x in widths_arr),
                       bm=bm, n_rows=n_rows, n_rows_pad=n_blocks * bm)


def ell_refill(plan: EllPlanSide, val: np.ndarray) -> EllBlocks:
    """Scatter a coefficient vector into a plan's storage layout —
    the O(nnz) value-refresh half of `ell_blocks`."""
    vals = np.zeros(plan.size, np.float32)
    vals[plan.flat] = np.asarray(val)[plan.order].astype(np.float32)
    return EllBlocks(idx=plan.idx, val=vals, offsets=plan.offsets,
                     widths=plan.widths, bm=plan.bm, n_rows=plan.n_rows,
                     n_rows_pad=plan.n_rows_pad)


def ell_blocks(row: np.ndarray, col: np.ndarray, val: np.ndarray,
               n_rows: int, *, bm: int = 8, align: int = 8) -> EllBlocks:
    """Pack COO entries into blocked-ELL rows keyed by `row` (plan +
    refill in one step; see ell_blocks_plan for the layout rules)."""
    return ell_refill(ell_blocks_plan(row, col, n_rows, bm=bm, align=align),
                      val)


@dataclasses.dataclass(frozen=True)
class EllOperator:
    """K (m x n) packed both ways for the fused kernel: `rows` gathers x
    to produce K.x (one stored row per constraint), `cols` gathers y to
    produce K^T.y (one stored row per variable)."""

    rows: EllBlocks
    cols: EllBlocks
    m: int
    n: int

    @property
    def m_pad(self) -> int:
        return self.rows.n_rows_pad

    @property
    def n_pad(self) -> int:
        return self.cols.n_rows_pad


@dataclasses.dataclass(frozen=True)
class EllPlan:
    """Both directions of an operator's blocked-ELL layout, values
    excluded — the cacheable product of a COO sparsity pattern."""

    rows: EllPlanSide
    cols: EllPlanSide
    m: int
    n: int


def ell_plan(row: np.ndarray, col: np.ndarray, m: int, n: int, *,
             bm: int = 8, align: int = 8) -> EllPlan:
    """Lay out a COO pattern in both blocked-ELL directions."""
    return EllPlan(rows=ell_blocks_plan(row, col, m, bm=bm, align=align),
                   cols=ell_blocks_plan(col, row, n, bm=bm, align=align),
                   m=m, n=n)


def ell_fill(plan: EllPlan, val: np.ndarray) -> EllOperator:
    """Refresh both directions of a planned operator with new values."""
    return EllOperator(rows=ell_refill(plan.rows, val),
                       cols=ell_refill(plan.cols, val),
                       m=plan.m, n=plan.n)


def ell_pack(row: np.ndarray, col: np.ndarray, val: np.ndarray,
             m: int, n: int, *, bm: int = 8, align: int = 8) -> EllOperator:
    """Pack a COO operator into both blocked-ELL directions."""
    return ell_fill(ell_plan(row, col, m, n, bm=bm, align=align), val)


def spmv_blocks(vec, idx, val, *, offsets, widths, bm, n_rows_pad):
    """Blocked-ELL SpMV as pure jnp ops: per run of equal-width blocks,
    gather `vec` at the stored indices, scale, and row-sum.  Shared
    verbatim by the Pallas kernel body and the `ref` oracle so the two
    can only differ through Pallas lowering itself (the parity tests pin
    that).

    Consecutive blocks with the same width are contiguous in storage, so
    one slice+reshape covers the whole run — the emitted program scales
    with the number of width *runs*, not blocks (large-topology LPs have
    thousands of blocks but only a few hundred runs, and a per-block
    loop would blow up trace/compile time).  Per-row gather order and
    the width-`w` row reduction are unchanged, so the result is
    bit-identical to the per-block form."""
    outs = []
    nb = len(widths)
    i = 0
    while i < nb:
        j = i + 1
        while j < nb and widths[j] == widths[i]:
            j += 1
        w = widths[i]
        rows = (j - i) * bm
        off = offsets[i]
        ib = jax.lax.slice_in_dim(idx, off, off + rows * w).reshape(rows, w)
        vb = jax.lax.slice_in_dim(val, off, off + rows * w).reshape(rows, w)
        outs.append((jnp.take(vec, ib, axis=0) * vb).sum(axis=1))
        i = j
    return jnp.concatenate(outs) if len(outs) > 1 else outs[0]


PRECISIONS = ("fp32", "bf16")


def pdhg_update_burst(x0, y0, c, tau, xmax, q, sig, ub, keep_n, keep_m,
                      row_idx, row_val, col_idx, col_val, *,
                      row_meta: tuple, col_meta: tuple, iters: int,
                      precision: str = "fp32"):
    """`iters` iterations of the exact `core.solver._pdhg_ops` update
    over the blocked-ELL operator, plus the terminal per-row residual
    vector (|K_eq x - b| on equality rows, max(K_ub x - h, 0) on
    inequality rows).  Pure traced jnp — THE shared body: the Pallas
    kernel and the `ref.pdhg_ell_burst_ref` oracle both call this
    verbatim, so they can only differ through Pallas lowering itself.
    Returns (x, y, worst).

    `precision="bf16"` stores the iterates in bfloat16 between
    iterations while every update — SpMV, prox/clip, dual ascent — and
    the terminal residual are computed in float32 (iterates are cast up
    at the top of each step and rounded back when stored).  The fp32
    path is byte-for-byte the historical trace: no casts are inserted,
    so `precision="fp32"` cannot perturb existing results."""
    assert precision in PRECISIONS, precision
    ro, rw, rbm, rp = row_meta
    co, cw, cbm, cp = col_meta

    def Kx(x):
        return spmv_blocks(x, row_idx, row_val, offsets=ro, widths=rw,
                           bm=rbm, n_rows_pad=rp)

    def KTy(y):
        return spmv_blocks(y, col_idx, col_val, offsets=co, widths=cw,
                           bm=cbm, n_rows_pad=cp)

    def update(x, y):
        x_new = jnp.clip(x - tau * (c + KTy(y)), 0.0, xmax)
        x_new = jnp.where(keep_n, x, x_new)
        x_bar = 2.0 * x_new - x
        y_new = y + sig * (Kx(x_bar) - q)
        y_new = jnp.where(ub, jnp.maximum(y_new, 0.0), y_new)
        y_new = jnp.where(keep_m, y, y_new)
        return x_new, y_new

    if precision == "bf16":
        def body(_, state):
            x, y = state
            x_new, y_new = update(x.astype(jnp.float32),
                                  y.astype(jnp.float32))
            return x_new.astype(jnp.bfloat16), y_new.astype(jnp.bfloat16)

        x, y = jax.lax.fori_loop(
            0, iters, body, (x0.astype(jnp.bfloat16),
                             y0.astype(jnp.bfloat16)))
        x, y = x.astype(jnp.float32), y.astype(jnp.float32)
    else:
        def body(_, state):
            return update(*state)

        x, y = jax.lax.fori_loop(0, iters, body, (x0, y0))
    r = Kx(x) - q
    return x, y, jnp.where(ub, jnp.maximum(r, 0.0), jnp.abs(r))


def _burst_kernel(c_ref, tau_ref, xmax_ref, q_ref, sig_ref, ub_ref,
                  keep_n_ref, keep_m_ref, rid_ref, rval_ref, cid_ref,
                  cval_ref, x0_ref, y0_ref,
                  xo_ref, yo_ref, worst_ref, *,
                  row_meta: tuple, col_meta: tuple, iters: int,
                  precision: str):
    """One fused PDHG burst, everything VMEM-resident: read the refs,
    run the shared update body, write the final iterates and residual
    vector — the caller segment-maxes it per instance, so convergence
    checks never re-run the SpMV."""
    x, y, worst = pdhg_update_burst(
        x0_ref[...], y0_ref[...], c_ref[...], tau_ref[...], xmax_ref[...],
        q_ref[...], sig_ref[...], ub_ref[...], keep_n_ref[...],
        keep_m_ref[...], rid_ref[...], rval_ref[...], cid_ref[...],
        cval_ref[...], row_meta=row_meta, col_meta=col_meta, iters=iters,
        precision=precision)
    xo_ref[...] = x
    yo_ref[...] = y
    worst_ref[...] = worst


def pdhg_burst(c, tau, xmax, q, sig, ub, keep_n, keep_m,
               row_idx, row_val, col_idx, col_val, x0, y0, *,
               row_meta: tuple, col_meta: tuple, iters: int,
               interpret: bool, precision: str = "fp32"):
    """Run one fused PDHG burst; returns (x, y, worst).

    All vectors are storage-padded: x-side arrays have length n_pad,
    y-side length m_pad (see ell_pack; padded slots carry xmax=0 / q=0
    and stay fixed at zero).  `keep_n`/`keep_m` are per-coordinate
    freeze masks (True = hold), identical in meaning to the adaptive
    batch kernel in core.solver.  `precision` selects the iterate
    storage dtype inside the burst (see pdhg_update_burst); inputs and
    outputs are float32 either way."""
    n_pad, m_pad = x0.shape[0], y0.shape[0]
    f32 = jnp.float32
    kernel = functools.partial(_burst_kernel, row_meta=row_meta,
                               col_meta=col_meta, iters=iters,
                               precision=precision)
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((n_pad,), f32),
                   jax.ShapeDtypeStruct((m_pad,), f32),
                   jax.ShapeDtypeStruct((m_pad,), f32)),
        interpret=interpret,
    )(c, tau, xmax, q, sig, ub, keep_n, keep_m,
      row_idx, row_val, col_idx, col_val, x0, y0)


# ---------------------------------------------------------------------------
# Sharded operator: row-block partition of [eq; ub] across a device mesh
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedEllOperator:
    """K (m x n) packed for an S-way row-block partition.

    Shard s owns the contiguous global rows [s*m_loc, (s+1)*m_loc) (the
    tail shard is padding-only past `m`).  Per shard there are two
    blocked-ELL directions, exactly as in EllOperator but local:

      * `row_*`: one stored row per LOCAL constraint row, gathering the
        replicated x — shard s computes its own slice of K.x;
      * `col_*`: one stored row per variable, gathering the LOCAL y —
        shard s computes its partial of K^T.y, and the full product is
        the psum over shards (each nnz lives in exactly one shard).

    Every shard's pack uses THE SAME static meta (per-block widths are
    the elementwise max across shards, see ell_blocks_plan min_widths),
    so `shard_map` traces a single program; the per-shard tables are
    concatenated shard-major into flat arrays whose leading extent
    divides evenly by S — ready for a PartitionSpec("shard") split."""

    row_idx: np.ndarray        # (S * row_size,) int32, global x indices
    row_val: np.ndarray        # (S * row_size,) float32
    col_idx: np.ndarray        # (S * col_size,) int32, LOCAL y indices
    col_val: np.ndarray        # (S * col_size,) float32
    row_meta: tuple            # unified per-shard (offsets, widths, bm, m_loc)
    col_meta: tuple            # unified per-shard (offsets, widths, bm, n_pad)
    shards: int
    m: int
    n: int
    m_loc: int                 # padded rows owned by each shard

    @property
    def m_pad(self) -> int:
        """Total padded row slots across all shards."""
        return self.shards * self.m_loc

    @property
    def n_pad(self) -> int:
        """Padded variable count (the col-direction row padding)."""
        return self.col_meta[3]


def ell_pack_sharded(row: np.ndarray, col: np.ndarray, val: np.ndarray,
                     m: int, n: int, shards: int, *, bm: int = 8,
                     align: int = 8) -> ShardedEllOperator:
    """Pack a COO operator for an S-way row-block partition.

    Two passes: the first lays each shard out independently to learn its
    natural per-block widths; the second re-packs every shard with the
    elementwise-max widths so all shards share one static meta (required
    for a single shard_map trace).  Row order inside each shard is the
    global order restricted to its rows, so gather row-sums match the
    unsharded pack bit-for-bit per row."""
    assert shards >= 1
    row = np.asarray(row, np.int64)
    col = np.asarray(col, np.int64)
    val = np.asarray(val)
    m_loc = max(-(-m // (shards * bm)), 1) * bm
    parts = []
    for s in range(shards):
        sel = (row >= s * m_loc) & (row < (s + 1) * m_loc)
        parts.append((row[sel] - s * m_loc, col[sel], val[sel]))
    row_plans = [ell_blocks_plan(r, c, m_loc, bm=bm, align=align)
                 for r, c, _ in parts]
    col_plans = [ell_blocks_plan(c, r, n, bm=bm, align=align)
                 for r, c, _ in parts]
    rw = np.maximum.reduce([np.asarray(p.widths) for p in row_plans])
    cw = np.maximum.reduce([np.asarray(p.widths) for p in col_plans])
    row_packs, col_packs = [], []
    for r, c, v in parts:
        row_packs.append(ell_refill(
            ell_blocks_plan(r, c, m_loc, bm=bm, align=align, min_widths=rw),
            v))
        col_packs.append(ell_refill(
            ell_blocks_plan(c, r, n, bm=bm, align=align, min_widths=cw),
            v))
    return ShardedEllOperator(
        row_idx=np.concatenate([p.idx for p in row_packs]),
        row_val=np.concatenate([p.val for p in row_packs]),
        col_idx=np.concatenate([p.idx for p in col_packs]),
        col_val=np.concatenate([p.val for p in col_packs]),
        row_meta=row_packs[0].meta, col_meta=col_packs[0].meta,
        shards=shards, m=m, n=n, m_loc=m_loc)


def pdhg_update_burst_sharded(x0, y0, c, tau, xmax, q, sig, ub, keep_n,
                              keep_m, row_idx, row_val, col_idx, col_val, *,
                              row_meta: tuple, col_meta: tuple, iters: int,
                              axis: str, precision: str = "fp32"):
    """Per-device body of the sharded PDHG burst (run inside shard_map).

    Same update as pdhg_update_burst — it IS the trajectory contract of
    core.solver._pdhg_ops over the blocked-ELL SpMV (spmv_blocks) — with
    the two mat-vecs split by the row partition:

      * K.x: each device computes its local constraint rows from the
        replicated x (no communication);
      * K^T.y: each device gathers its local dual slice into a full
        length-n partial and the true product is `psum` over `axis` —
        the single collective per iteration.

    x-side arrays (x0, c, tau, xmax, keep_n) are replicated; y-side
    arrays (y0, q, sig, ub, keep_m) are the local row slice.  Returns
    (x, y_local, worst_local); x is identical on every device because it
    is a deterministic function of replicated inputs and psum outputs.
    `precision="bf16"` stores both iterates in bfloat16 between
    iterations with all arithmetic (and the psum) in float32, exactly
    like the single-device body."""
    assert precision in PRECISIONS, precision
    ro, rw, rbm, rp = row_meta
    co, cw, cbm, cp = col_meta

    def Kx(x):
        return spmv_blocks(x, row_idx, row_val, offsets=ro, widths=rw,
                           bm=rbm, n_rows_pad=rp)

    def KTy(y):
        part = spmv_blocks(y, col_idx, col_val, offsets=co, widths=cw,
                           bm=cbm, n_rows_pad=cp)
        return jax.lax.psum(part, axis)

    def update(x, y):
        x_new = jnp.clip(x - tau * (c + KTy(y)), 0.0, xmax)
        x_new = jnp.where(keep_n, x, x_new)
        x_bar = 2.0 * x_new - x
        y_new = y + sig * (Kx(x_bar) - q)
        y_new = jnp.where(ub, jnp.maximum(y_new, 0.0), y_new)
        y_new = jnp.where(keep_m, y, y_new)
        return x_new, y_new

    if precision == "bf16":
        def body(_, state):
            x, y = state
            x_new, y_new = update(x.astype(jnp.float32),
                                  y.astype(jnp.float32))
            return x_new.astype(jnp.bfloat16), y_new.astype(jnp.bfloat16)

        x, y = jax.lax.fori_loop(
            0, iters, body, (x0.astype(jnp.bfloat16),
                             y0.astype(jnp.bfloat16)))
        x, y = x.astype(jnp.float32), y.astype(jnp.float32)
    else:
        def body(_, state):
            return update(*state)

        x, y = jax.lax.fori_loop(0, iters, body, (x0, y0))
    r = Kx(x) - q
    return x, y, jnp.where(ub, jnp.maximum(r, 0.0), jnp.abs(r))
