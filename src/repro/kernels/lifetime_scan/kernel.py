"""Pallas TPU kernel for GainSight's lifetime-extraction hot loop.

The analytical frontend's dominant cost is the segmented reduction over
the (addr, time)-sorted event stream: find segment boundaries (new address
or write), and per segment compute first-write time, last-read time and
read count, then bin the closed lifetimes into a histogram (paper Fig 8).

On TPU this becomes a single sequential-grid pass: each grid step loads a
block of events into VMEM, computes intra-block segment reductions with
one-hot matmul-style masks (MXU/VPU friendly), merges the segment that
straddles the block boundary through SMEM carry scalars, and accumulates
the histogram in VMEM scratch.  Events stream through HBM exactly once.

Time is carried as a **split int64**: two int32 limbs (hi = t >> 30,
lo = t & (2**30 - 1)) so rebased cycle stamps up to 2**61 survive the
int32-only TPU datapath.  All segment reductions on time become
lexicographic (hi first, lo tie-break) two-pass masked reductions, the
lifetime is a borrow-normalized limb subtraction, and histogram binning
compares limb pairs against pre-ceiled integer edges (ops.py converts
float64 edges to exact int64 thresholds: for integer lifetimes,
``lt >= e`` iff ``lt >= ceil(e)`` and ``lt < e`` iff ``lt < ceil(e)``).

Inputs (sorted by (addr, time); padded by ops.py with write events at a
sentinel address; time rebased to min 0 and limb-split by ops.py):
  t_hi[N] i32, t_lo[N] i32, addr[N] i32, w[N] i32 (1 = write)
  edges_hi[NB+1] i32, edges_lo[NB+1] i32  integer bin-edge limbs (cycles)

Outputs:
  hist[NB]  f32  closed non-orphan lifetimes per bin
  stats[8]  f32  (closed, orphans, sum_lt, max_lt, reads, writes, 0, 0)
  sum_lt/max_lt are f32 aggregates of exact integer lifetimes, so past
  2**24 cycles they carry f32 rounding; the histogram itself is exact.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

I32_MAX = 2 ** 31 - 1   # python int: becomes an in-kernel literal
LO_BITS = 30            # lo limb width; 30 keeps borrow arithmetic in int32
LO_MOD = 2 ** LO_BITS


def _lifetime_kernel(th_ref, tl_ref, a_ref, w_ref, eh_ref, el_ref,
                     hist_ref, stats_ref, hist_scr, stats_scr, carry_scr,
                     *, block, n_blocks, n_bins):
    bi = pl.program_id(0)

    @pl.when(bi == 0)
    def _init():
        hist_scr[...] = jnp.zeros_like(hist_scr)
        stats_scr[...] = jnp.zeros_like(stats_scr)
        # carry: [prev_addr, start_hi, start_lo, lastr_hi, lastr_lo,
        #         n_reads, started]
        carry_scr[0] = jnp.int32(-2)   # impossible address
        carry_scr[1] = jnp.int32(0)
        carry_scr[2] = jnp.int32(0)
        carry_scr[3] = jnp.int32(-1)
        carry_scr[4] = jnp.int32(-1)
        carry_scr[5] = jnp.int32(0)
        carry_scr[6] = jnp.int32(0)

    th = th_ref[0, :]
    tl = tl_ref[0, :]
    a = a_ref[0, :]
    w = w_ref[0, :].astype(bool)
    eh = eh_ref[...]
    el = el_ref[...]

    prev_addr = carry_scr[0]
    c_start_hi = carry_scr[1]
    c_start_lo = carry_scr[2]
    c_lastr_hi = carry_scr[3]
    c_lastr_lo = carry_scr[4]
    c_nread = carry_scr[5]
    started = carry_scr[6]

    prev_a = jnp.concatenate([prev_addr[None], a[:-1]])
    boundary = (a != prev_a) | w
    # inclusive prefix count of boundaries (carry-segment = 0) as one
    # triangular matmul: Mosaic has no cumsum, and f32 counts <= block
    # are exact
    tri = (jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
           <= jax.lax.broadcasted_iota(jnp.int32, (block, block), 1))
    sid = jnp.dot(boundary.astype(jnp.float32)[None, :],
                  tri.astype(jnp.float32),
                  preferred_element_type=jnp.float32
                  ).reshape(block).astype(jnp.int32)
    nb = sid.max()                  # sid is nondecreasing: its last entry

    ids = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)  # seg cols
    O = sid[:, None] == ids                            # [event, seg]
    r = ~w
    # Mosaic turns int32 vectors into columns, not bool ones
    Or = O & (r.astype(jnp.int32)[:, None] > 0)

    # per-segment first event: lexicographic (hi, lo) min, two masked
    # passes (min hi, then min lo among events at that hi)
    sh = jnp.where(O, th[:, None], I32_MAX).min(axis=0)             # [block]
    sl = jnp.where(O & (th[:, None] == sh[None, :]),
                   tl[:, None], I32_MAX).min(axis=0)
    # per-segment last read: lexicographic (hi, lo) max over reads
    lh = jnp.where(Or, th[:, None], -1).max(axis=0)
    ll = jnp.where(Or & (th[:, None] == lh[None, :]),
                   tl[:, None], -1).max(axis=0)
    seg_nread = jnp.sum(Or.astype(jnp.int32), axis=0)

    # merge the carried segment into sid 0 (carry start predates any
    # in-block event of the same segment; last-read needs the lexi max)
    col0 = jnp.arange(block) == 0
    use_c = started > 0
    sh = jnp.where(col0, jnp.where(use_c, c_start_hi, sh), sh)
    sl = jnp.where(col0, jnp.where(use_c, c_start_lo, sl), sl)
    c_wins = (c_lastr_hi > lh) | ((c_lastr_hi == lh) & (c_lastr_lo > ll))
    lh = jnp.where(col0 & c_wins, c_lastr_hi, lh)
    ll = jnp.where(col0 & c_wins, c_lastr_lo, ll)
    seg_nread = jnp.where(col0, c_nread + seg_nread, seg_nread)

    # segments 0 .. nb-1 close in this block (segment nb stays open)
    seg_ids = jax.lax.iota(jnp.int32, block)
    closed = seg_ids < nb
    # sid 0 only exists if a carry was live or block events extend it
    sid0_events = jnp.sum((sid == 0).astype(jnp.int32))
    closed = closed & ((seg_ids > 0) | (started > 0) | (sid0_events > 0))

    has_read = seg_nread > 0
    live = closed & has_read
    orphan = closed & (~has_read)

    # lifetime = last_read - start as borrow-normalized limb subtraction;
    # inputs keep lo in [0, LO_MOD) so one borrow suffices
    d_lo = ll - sl
    borrow = (d_lo < 0).astype(jnp.int32)
    d_hi = lh - sh - borrow
    d_lo = d_lo + borrow * LO_MOD
    ok = live & (d_hi >= 0)
    d_hi = jnp.where(ok, d_hi, 0)
    d_lo = jnp.where(ok, d_lo, 0)

    # bin by limb-pair comparison against integer edges (exact)
    eh0, el0 = eh[:-1][None, :], el[:-1][None, :]     # lower edges
    eh1, el1 = eh[1:][None, :], el[1:][None, :]       # upper edges
    ge_lo = (d_hi[:, None] > eh0) | \
        ((d_hi[:, None] == eh0) & (d_lo[:, None] >= el0))
    lt_hi = (d_hi[:, None] < eh1) | \
        ((d_hi[:, None] == eh1) & (d_lo[:, None] < el1))
    in_bin = ge_lo & lt_hi & (live.astype(jnp.int32)[:, None] > 0)
    hist_scr[...] += in_bin.astype(jnp.float32).sum(axis=0)

    ltf = d_hi.astype(jnp.float32) * jnp.float32(LO_MOD) + \
        d_lo.astype(jnp.float32)
    # VMEM takes no scalar stores: place the block's sums in their lanes
    # and update the stats vector in one store (lane 3 is a running max)
    lane = jax.lax.broadcasted_iota(jnp.int32, (8,), 0)
    add = jnp.zeros((8,), jnp.float32)
    for k, v in ((0, live), (1, orphan), (4, r), (5, w)):
        add = jnp.where(lane == k, jnp.sum(v.astype(jnp.float32)), add)
    add = jnp.where(lane == 2, jnp.sum(ltf * live.astype(jnp.float32)), add)
    cur = stats_scr[...]
    stats_scr[...] = jnp.where(lane == 3, jnp.maximum(cur, ltf.max()),
                               cur + add)

    # new carry = segment nb (the still-open one); sel picks exactly one
    # element, so a masked sum extracts it (works for -1 sentinels too)
    sel = seg_ids == nb
    carry_scr[0] = jnp.sum(jnp.where(seg_ids == block - 1, a, 0))
    carry_scr[1] = jnp.sum(jnp.where(sel, sh, 0))
    carry_scr[2] = jnp.sum(jnp.where(sel, sl, 0))
    carry_scr[3] = jnp.sum(jnp.where(sel, lh, 0))
    carry_scr[4] = jnp.sum(jnp.where(sel, ll, 0))
    carry_scr[5] = jnp.sum(jnp.where(sel, seg_nread, 0))
    carry_scr[6] = jnp.int32(1)

    @pl.when(bi == n_blocks - 1)
    def _finish():
        hist_ref[...] = hist_scr[...]
        stats_ref[...] = stats_scr[...]


@functools.partial(jax.jit,
                   static_argnames=("block", "n_bins", "interpret"))
def lifetime_scan_sorted(t_hi, t_lo, addr, is_write, edges_hi, edges_lo,
                         *, block=256, n_bins=64, interpret=False):
    """Inputs pre-sorted by (addr, time), limb-split, and pre-padded to a
    block multiple (ops.py handles all three).  Returns
    (hist [n_bins], stats [8])."""
    n = t_hi.shape[0]
    assert n % block == 0
    n_blocks = n // block
    assert edges_hi.shape[0] == n_bins + 1

    hist, stats = pl.pallas_call(
        functools.partial(_lifetime_kernel, block=block, n_blocks=n_blocks,
                          n_bins=n_bins),
        grid=(n_blocks,),
        # events arrive as [n_blocks, 1, block], one row per step: a 1-D
        # block would not match XLA's tiling of a long 1-D operand
        in_specs=[
            pl.BlockSpec((None, 1, block), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, 1, block), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, 1, block), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, 1, block), lambda i: (i, 0, 0)),
            pl.BlockSpec((n_bins + 1,), lambda i: (0,)),
            pl.BlockSpec((n_bins + 1,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((n_bins,), lambda i: (0,)),
            pl.BlockSpec((8,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_bins,), jnp.float32),
            jax.ShapeDtypeStruct((8,), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_bins,), jnp.float32),
            pltpu.VMEM((8,), jnp.float32),
            pltpu.SMEM((7,), jnp.int32),
        ],
        interpret=interpret,
    )(*(x.astype(jnp.int32).reshape(n_blocks, 1, block)
        for x in (t_hi, t_lo, addr, is_write)),
      edges_hi.astype(jnp.int32), edges_lo.astype(jnp.int32))
    return hist, stats
