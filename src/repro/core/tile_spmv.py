"""Block-sparse tile operator for PDHG's K.x and K^T.y.

The COO pair of `core.pdhg` does one scalar gather and one scalar
scatter-add per nonzero and direction.  On a TPU each of those indexed
moves costs about the same whatever it carries, so the scatter path runs
at a fixed cost per nonzero, far from the chip's byte bound.  This
operator moves a lane-dense vector per index instead.

Layout (block-COO of dense f32 tiles).  Both directions store a table of
shape (T, SUB, LANES): tile t maps an input segment of SUB = 8
coordinates (`seg[t]`, in units of SUB) to an output block of LANES = 128
coordinates (`blk[t]`, in units of LANES); table[t, j, i] holds the
coefficient from input seg[t]*SUB + j to output blk[t]*LANES + i.

  * K.x:   128 rows x 8 columns a tile, stored transposed (input on the
           sublanes), so each tile's product is a 128-vector of rows;
  * K^T.y: 8 rows x 128 columns a tile, so each product is a
           128-vector of columns.

Only tiles holding a nonzero exist, sorted by output block.  Applying a
direction gathers each tile's input segment, multiplies and reduces over
the 8 sublanes in f32 (elementwise, no matrix unit, so no reduced-precision
pass), and adds the T 128-vectors into their output blocks with one
sorted segment-sum.  The tables are filled on the device once per
dispatch, by one scatter of the COO values into zeroed tables, through
`slot`: each nonzero's flat index in its direction's table.  Duplicate
(row, col) entries share a slot and accumulate.

Plans are per instance (`instance_plan`).  In a block-stacked dispatch
every instance's columns, equality rows and inequality rows start on a
LANES boundary (`stack`), so no tile spans two instances: the stacked
plan is the instances' plans with their offsets, its tile counts are the
sums of theirs whatever the order of the stack, and an instance's tiles
are the same alone or stacked.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128              # output block: one lane-dense vector a tile
SUB = 8                  # input segment: the sublanes a tile reduces over
SLOTS = SUB * LANES      # entries of one tile


def align(d: int) -> int:
    """`d` rounded up to a multiple of LANES."""
    return -(-d // LANES) * LANES


@dataclasses.dataclass(frozen=True)
class Direction:
    """One direction's tiles: input segment and output block of each
    tile (ascending blocks), and each nonzero's slot in the table."""

    seg: np.ndarray      # (T,) input segment, units of SUB
    blk: np.ndarray      # (T,) output block, units of LANES; sorted
    slot: np.ndarray     # (nnz,) flat index into the (T, SUB, LANES) table

    @property
    def tiles(self) -> int:
        return len(self.seg)


def direction(out: np.ndarray, inp: np.ndarray, n_in: int) -> Direction:
    """The tiles of the map taking input coordinate `inp[k]` to output
    coordinate `out[k]`, over `n_in` (a multiple of SUB) inputs."""
    n_seg = n_in // SUB
    key = (out // LANES) * n_seg + inp // SUB
    uniq, inv = np.unique(key, return_inverse=True)
    return Direction(seg=uniq % n_seg, blk=uniq // n_seg,
                     slot=inv * SLOTS
                     + (inp % SUB) * LANES + out % LANES)


@dataclasses.dataclass(frozen=True)
class InstancePlan:
    """Both directions' tiles of one LP, in its aligned coordinates:
    columns [0, n_a), equality rows from 0, inequality rows from m_eq_a
    (sizes rounded up by `align`).  K.x's tiles over equality rows come
    first (`kx_eq` of them)."""

    n: int
    m_eq: int
    m_ub: int
    kx: Direction
    kty: Direction
    kx_eq: int

    @property
    def n_a(self) -> int:
        return align(self.n)

    @property
    def m_eq_a(self) -> int:
        return align(self.m_eq)

    @property
    def m_ub_a(self) -> int:
        return align(self.m_ub)


def instance_plan(row: np.ndarray, col: np.ndarray, m: int, n: int,
                  m_eq: int) -> InstancePlan:
    """Tile both directions of one LP's COO pattern (rows [eq; ub])."""
    m_eq_a = align(m_eq)
    lrow = np.where(row < m_eq, row, row - m_eq + m_eq_a).astype(np.int64)
    col = np.asarray(col, np.int64)
    kx = direction(lrow, col, align(n))
    kty = direction(col, lrow, m_eq_a + align(m - m_eq))
    return InstancePlan(n=n, m_eq=m_eq, m_ub=m - m_eq, kx=kx, kty=kty,
                        kx_eq=int(np.searchsorted(kx.blk, m_eq_a // LANES)))


@dataclasses.dataclass(frozen=True)
class StackedTiles:
    """A block-stacked dispatch's layout: where each stacked (unaligned)
    column and row goes (`pos_n`, `pos_m`), and both directions' tiles,
    padded to `t_kx` / `t_kty` tiles (zero tiles reading segment 0 into
    the last block) and to `nnz` slots (padding nonzeros use slot 0)."""

    pos_n: np.ndarray
    pos_m: np.ndarray
    kx: Direction
    kty: Direction

    def arrays(self) -> tuple:
        """(kx slot, seg, blk, kty slot, seg, blk), int32, as the
        jitted kernel takes them."""
        return tuple(np.asarray(a, np.int32) for d in (self.kx, self.kty)
                     for a in (d.slot, d.seg, d.blk))


def stack(plans: list[InstancePlan], n: int, m_eq: int, m: int, nnz: int,
          t_kx: int, t_kty: int) -> StackedTiles:
    """Stack instance plans into one dispatch of `n` columns and `m` rows,
    equality rows first and inequality rows from `m_eq` (all multiples
    of LANES, at least the aligned sums), `nnz` COO entries and `t_kx` /
    `t_kty` tiles (at least the plans' sums).  Instance i's columns
    start at the sum of the aligned column counts before it; likewise
    its equality rows and, from `m_eq`, its inequality rows."""
    n_off = np.cumsum([0] + [p.n_a for p in plans])
    eq_off = np.cumsum([0] + [p.m_eq_a for p in plans])
    ub_off = m_eq + np.cumsum([0] + [p.m_ub_a for p in plans])
    assert n_off[-1] <= n and eq_off[-1] <= m_eq and ub_off[-1] <= m
    pos_n = np.concatenate([n_off[i] + np.arange(p.n)
                            for i, p in enumerate(plans)])
    pos_m = np.concatenate([eq_off[i] + np.arange(p.m_eq)
                            for i, p in enumerate(plans)]
                           + [ub_off[i] + np.arange(p.m_ub)
                              for i, p in enumerate(plans)])

    # K.x: every instance's equality tiles, then every inequality tile,
    # so the blocks stay sorted
    te = np.cumsum([0] + [p.kx_eq for p in plans])
    tu = te[-1] + np.cumsum([0] + [p.kx.tiles - p.kx_eq for p in plans])
    kx_seg, kx_blk, kx_slot = [], [], []
    for i, p in enumerate(plans):
        kx_seg.append(p.kx.seg[:p.kx_eq] + n_off[i] // SUB)
        kx_blk.append(p.kx.blk[:p.kx_eq] + eq_off[i] // LANES)
    for i, p in enumerate(plans):
        kx_seg.append(p.kx.seg[p.kx_eq:] + n_off[i] // SUB)
        kx_blk.append(p.kx.blk[p.kx_eq:] + (ub_off[i] - p.m_eq_a) // LANES)
    for i, p in enumerate(plans):
        t = p.kx.slot // SLOTS
        t = np.where(t < p.kx_eq, te[i] + t, tu[i] + t - p.kx_eq)
        kx_slot.append(t * SLOTS + p.kx.slot % SLOTS)

    # K^T.y: instance by instance (blocks are columns)
    tt = np.cumsum([0] + [p.kty.tiles for p in plans])
    kty_seg, kty_blk, kty_slot = [], [], []
    for i, p in enumerate(plans):
        eq_segs = p.m_eq_a // SUB
        kty_seg.append(np.where(p.kty.seg < eq_segs,
                                p.kty.seg + eq_off[i] // SUB,
                                p.kty.seg - eq_segs + ub_off[i] // SUB))
        kty_blk.append(p.kty.blk + n_off[i] // LANES)
        kty_slot.append(p.kty.slot + tt[i] * SLOTS)

    def join(seg, blk, slot, n_out, tiles):
        seg, blk, slot = (np.concatenate(a) for a in (seg, blk, slot))
        extra = tiles - len(seg)
        return Direction(
            seg=np.concatenate([seg, np.zeros(extra, np.int64)]),
            blk=np.concatenate([blk, np.full(extra, n_out // LANES - 1)]),
            slot=np.concatenate([slot, np.zeros(nnz - len(slot), np.int64)]))

    return StackedTiles(pos_n=pos_n, pos_m=pos_m,
                        kx=join(kx_seg, kx_blk, kx_slot, m, t_kx),
                        kty=join(kty_seg, kty_blk, kty_slot, n, t_kty))


# ---------------------------------------------------------------------------
# Device side
# ---------------------------------------------------------------------------

def fill(slot, val, tiles: int):
    """The (tiles, SUB, LANES) table: every value added into its slot."""
    return (jnp.zeros(tiles * SLOTS, val.dtype).at[slot].add(val)
            .reshape(tiles, SUB, LANES))


def apply(table, seg, blk, v, n_out: int):
    """One direction: gather each tile's input segment, reduce the tile
    over its sublanes in f32, and add the tiles' 128-vectors into their
    (sorted) output blocks."""
    part = jnp.sum(table * v.reshape(-1, SUB)[seg][:, :, None], axis=1)
    return jax.ops.segment_sum(part, blk, num_segments=n_out // LANES,
                               indices_are_sorted=True).reshape(n_out)


def operator_pair(tiles: tuple, val, m: int, n: int):
    """(Kx, KTy) over the tables filled from `val` (see StackedTiles.arrays
    for `tiles`)."""
    kx_slot, kx_seg, kx_blk, kty_slot, kty_seg, kty_blk = tiles
    kx = fill(kx_slot, val, kx_seg.shape[0])
    kty = fill(kty_slot, val, kty_seg.shape[0])

    def Kx(x):
        with jax.named_scope("pdhg/Kx"):
            return apply(kx, kx_seg, kx_blk, x, m)

    def KTy(y):
        with jax.named_scope("pdhg/KTy"):
            return apply(kty, kty_seg, kty_blk, y, n)

    return Kx, KTy
