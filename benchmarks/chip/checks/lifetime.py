"""Plain reference of the lifetime-extraction layer: data lifetimes and
per-subpartition statistics.

NumPy only; nothing of the program under test is imported.

A lifetime (GainSight, Definitions 4.1-4.3) runs at one address from a
boundary event to the last read before the next boundary.  Events are
taken in (address, time) order, ties in trace order.  In a scratchpad a
boundary is a write; in a write-allocate cache it is a write or a miss,
and only hits count as reads.  A lifetime with no read is an orphan and
lasts 0 cycles.
"""

from __future__ import annotations

import numpy as np

LIMITS = {"lifetime_mismatch": 0, "stats_mismatch": 0}

STAT_FIELDS = ("n_reads", "n_writes", "n_unique_addrs", "duration_s",
               "write_freq_hz", "orphan_fraction", "block_bits")


def segments(time, addr, is_write, hit, mode):
    """Every lifetime of one subpartition as ``(addr, start, lifetime,
    n_reads)`` int64 arrays, in (address, start) order."""
    order = np.lexsort((time, addr))
    t = np.asarray(time, np.int64)[order]
    a = np.asarray(addr, np.int64)[order]
    w = np.asarray(is_write, bool)[order]
    h = np.asarray(hit, bool)[order]
    if len(t) == 0:
        z = np.zeros(0, np.int64)
        return z, z, z, z
    boundary = np.concatenate([[True], a[1:] != a[:-1]]) | w
    read_ok = ~w
    if mode == "cache":
        boundary |= ~h
        read_ok &= h
    elif mode != "scratchpad":
        raise ValueError(f"unknown mode {mode!r}")
    starts = np.flatnonzero(boundary)
    n_reads = np.add.reduceat(read_ok.astype(np.int64), starts)
    last = np.maximum.reduceat(np.where(read_ok, t, np.iinfo(np.int64).min),
                               starts)
    lifetime = np.where(n_reads > 0, last - t[starts], 0)
    return a[starts], t[starts], lifetime, n_reads


def subpartition_stats(time, addr, is_write, seg, *, clock_hz, block_bits):
    """The statistics the composition layer consumes, from one
    subpartition's events and its lifetimes ``seg``."""
    t = np.asarray(time, np.int64)
    w = np.asarray(is_write, bool)
    n_writes = int(w.sum())
    n_reads = len(w) - n_writes
    duration = max(float(t.max() - t.min() + 1) / clock_hz
                   if len(t) else 0.0, 1e-30)
    n_rd = seg[3]
    return {
        "n_reads": n_reads,
        "n_writes": n_writes,
        "n_unique_addrs": int(len(np.unique(addr))),
        "duration_s": duration,
        "write_freq_hz": n_writes / duration,
        "orphan_fraction": float((n_rd == 0).mean()) if len(n_rd) else 0.0,
        "block_bits": block_bits,
    }


def compare(got_segs, got_stats, ref_segs, ref_stats):
    """``lifetime_mismatch``: lifetimes present on one side only, or
    differing in start, length or read count, once both sides are put
    in (address, start) order.  ``stats_mismatch``: statistics fields
    that differ, compared exactly."""
    def table(seg):
        a, s, lt, nr = (np.asarray(x, np.int64) for x in seg)
        o = np.lexsort((s, a))
        return np.stack([a[o], s[o], lt[o], nr[o]], axis=1)

    g, r = table(got_segs), table(ref_segs)
    n = min(len(g), len(r))
    bad = int((g[:n] != r[:n]).any(axis=1).sum()) + abs(len(g) - len(r))
    fields = sum(got_stats[f] != ref_stats[f] for f in STAT_FIELDS)
    return {"lifetime_mismatch": bad, "stats_mismatch": int(fields)}


def numbers(rec, ref):
    """Both numbers summed over the subpartitions; one missing on either
    side counts all its lifetimes and fields."""
    out = {"lifetime_mismatch": 0, "stats_mismatch": 0}
    for name, (segs, stats) in ref["subs"].items():
        got = rec["subs"].get(name)
        if got is None:
            out["lifetime_mismatch"] += len(segs[0])
            out["stats_mismatch"] += len(STAT_FIELDS)
            continue
        for k, v in compare(got[0], got[1], segs, stats).items():
            out[k] += v
    out["stats_mismatch"] += len(set(rec["subs"]) - set(ref["subs"]))
    return out
