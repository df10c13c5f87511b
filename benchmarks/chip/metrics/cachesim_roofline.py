"""cachesim_roofline: the cache simulator's scans against the least time
the chip could take for the accesses they replay.  Device time is the
busy time inside the benchmark's ``cachesim`` spans, where the scans
(``_simulate_cache_sets``, one per level) are the only device work.
Work is counted from the accesses of each cache level alone, whatever
implements the replay."""

from chipbench.manifest import load_module

SPAN = "cachesim"


def least_work(accesses, ways):
    """``(ops, bytes)`` of replaying ``accesses`` through one level of
    ``ways``-way sets: each access compares its tag with every way and
    finds the least recently used of them (2 * ways compares) and
    updates one way (1); it reads its line address (8 B) and write flag
    (1 B) once and writes its hit and fill flags (1 B each), evicted
    line (8 B) and dirty flag (1 B) once."""
    return accesses * (2 * ways + 1), accesses * 20


def read(ctx):
    if ctx.summary is None or not ctx.work:
        return None
    levels = ctx.cell.config["run"]["hierarchy"]
    ops = nbytes = 0
    for name, level in (("L1", levels["l1"]), ("L2", levels["l2"])):
        o, b = least_work(ctx.work["events"][name], level["ways"])
        ops, nbytes = ops + o, nbytes + b
    return load_module("metrics", "_roofline").share(
        ops * ctx.n_done, nbytes * ctx.n_done, ctx.peaks["ops_int8"],
        ctx.peaks["hbm_bw"], ctx.summary.busy_in(SPAN))
