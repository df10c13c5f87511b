"""executor_roofline.sweep: the fused composition kernels of the
window's sweeps against the least time the chip could take for them.
Device time is the busy time inside the benchmark's ``sweep`` spans,
where the executor's ``_rf_fused`` / ``_ra_grouped`` slabs are the only
device work.  Work is counted from the candidates, devices, lifetimes
and addresses of each request alone."""

import math

from chipbench.manifest import load_module

SPAN = "sweep"


def least_work(policy, devices, lifetimes, addresses):
    """``(ops, bytes)`` of composing one subpartition under ``policy``
    for every candidate; ``devices`` lists each candidate's device
    count.

    refresh-aware: per candidate, device and lifetime a refresh count
    (divide, ceil, subtract, max) and an energy (3 multiplies, 3 adds):
    10; the least over devices (devices - 1 compares) and its sum (1);
    the per-address sums (devices adds per lifetime) and the least per
    address (devices - 1 compares per address).  Lifetimes, read counts
    and bits (8 B each) and address segment ids (4 B) read once per
    request; per candidate an energy and a count per device (8 B each)
    written once.

    refresh-free: per candidate and device a binary search of the sorted
    lifetimes and of the sorted per-address maxima (``ceil(log2 n)``
    compares each) and four prefix-sum reads and adds; sorted lifetimes
    and two prefix sums (8 B each per lifetime) and the per-address
    maxima (8 B) read once; the same outputs."""
    L, A = lifetimes, addresses
    out_bytes = sum(8 * (1 + D) for D in devices)
    if policy.split("@")[0].endswith("refresh-aware"):
        ops = sum(10 * D * L + (D - 1) * L + L + D * L + (D - 1) * A
                  for D in devices)
        return ops, 28 * L + out_bytes
    search = math.ceil(math.log2(max(L, 2))) + \
        math.ceil(math.log2(max(A, 2))) + 4
    return sum(D for D in devices) * search, 24 * L + 8 * A + out_bytes


def read(ctx):
    if ctx.summary is None or not ctx.work:
        return None
    w = ctx.work
    ops = nbytes = 0
    for name, L in w["lifetimes"].items():
        o, b = least_work(ctx.cell.traffic["policy"], w["devices"], L,
                          w["addresses"][name])
        ops, nbytes = ops + o, nbytes + b
    return load_module("metrics", "_roofline").share(
        ops * ctx.n_done, nbytes * ctx.n_done, ctx.peaks["flops_bf16"],
        ctx.peaks["hbm_bw"], ctx.summary.busy_in(SPAN))
