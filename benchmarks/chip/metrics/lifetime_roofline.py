"""lifetime_roofline: lifetime extraction against the least time the
chip could take for it.  Device time is the busy time inside the
benchmark's ``analyze`` spans, where ``_extract_lifetimes`` (one per
subpartition) is the only device work.  Work is counted from each
subpartition's events and lifetimes alone."""

import math

from chipbench.manifest import load_module

SPAN = "analyze"


def least_work(events, lifetimes):
    """``(ops, bytes)`` of extracting ``lifetimes`` from ``events``: a
    comparison sort on (address, time), ``n * ceil(log2 n)`` compares,
    then per event one boundary compare, one segment-number add and
    four segment reductions (6); each event's time and address (8 B
    each) and write and hit flags (1 B each) read once, each lifetime's
    address, start and length (8 B each) and read count (4 B) written
    once."""
    n = events
    ops = n * math.ceil(math.log2(n)) + 6 * n if n > 1 else 6 * n
    return ops, 18 * n + 28 * lifetimes


def read(ctx):
    if ctx.summary is None or not ctx.work:
        return None
    w = ctx.work
    ops = nbytes = 0
    for name, n in w["events"].items():
        o, b = least_work(n, w["lifetimes"][name])
        ops, nbytes = ops + o, nbytes + b
    return load_module("metrics", "_roofline").share(
        ops * ctx.n_done, nbytes * ctx.n_done, ctx.peaks["ops_int8"],
        ctx.peaks["hbm_bw"], ctx.summary.busy_in(SPAN))
