"""Blocked-ELL sparse operator for the row-sharded PDHG.

core.pdhg runs one PDHG update over any operator pair (Kx, KTy).  This
module supplies the pair that a row-sharded solve applies on each
device: the COO operator re-packed into a padded **blocked-ELL** layout
(gathers only, no scatters).  Each device holds a contiguous block of
the [eq; ub] rows (`ell_pack_sharded`); K^T.y is the psum of the
devices' parts (core.pdhg.burst_sharded).

Blocked-ELL layout (`ell_blocks_plan` / `ell_refill`)
-----------------------------------------------------
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
column index.  See docs/KERNELS.md for the layout and padding rules.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


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


@dataclasses.dataclass(frozen=True)
class EllPlanSide:
    """The value-independent half of one blocked-ELL direction: gather
    indices and storage layout, plus the (order, flat) permutation that
    scatters COO values into storage slots.  `ell_refill` turns it
    into an EllBlocks for a coefficient vector in O(nnz)."""

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
    """Scatter a coefficient vector into a plan's storage layout."""
    vals = np.zeros(plan.size, np.float32)
    vals[plan.flat] = np.asarray(val)[plan.order].astype(np.float32)
    return EllBlocks(idx=plan.idx, val=vals, offsets=plan.offsets,
                     widths=plan.widths, bm=plan.bm, n_rows=plan.n_rows,
                     n_rows_pad=plan.n_rows_pad)


def spmv_blocks(vec, idx, val, *, offsets, widths, bm, n_rows_pad):
    """Blocked-ELL SpMV as pure jnp ops: per run of equal-width blocks,
    gather `vec` at the stored indices, scale, and row-sum.

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


def ell_pair(row_idx, row_val, col_idx, col_val, row_meta: tuple,
             col_meta: tuple):
    """(Kx, KTy) over blocked-ELL tables: `row_*` store one row per
    constraint and gather x, `col_*` one row per variable and gather y
    (each `meta` is EllBlocks.meta)."""
    ro, rw, rbm, rp = row_meta
    co, cw, cbm, cp = col_meta

    def Kx(x):
        return spmv_blocks(x, row_idx, row_val, offsets=ro, widths=rw,
                           bm=rbm, n_rows_pad=rp)

    def KTy(y):
        return spmv_blocks(y, col_idx, col_val, offsets=co, widths=cw,
                           bm=cbm, n_rows_pad=cp)

    return Kx, KTy


# ---------------------------------------------------------------------------
# Sharded operator: row-block partition of [eq; ub] across a device mesh
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedEllOperator:
    """K (m x n) packed for an S-way row-block partition.

    Shard s owns the contiguous global rows [s*m_loc, (s+1)*m_loc) (the
    tail shard is padding-only past `m`).  Per shard there are two
    blocked-ELL directions (`ell_pair`):

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
