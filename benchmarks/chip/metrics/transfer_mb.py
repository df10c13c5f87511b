"""transfer_mb: megabytes (1e6 B) per answer moved between host and
device at the program's stage boundaries: the ``h2d_bytes`` and
``d2h_bytes`` counters of every program span in the window (cache
scans, lifetime extraction, statistics, the executor's residence and
slabs), each count held by the one span it was made in."""

from chipbench.manifest import load_module


def read(ctx):
    prog = load_module("metrics", "_program")
    got = prog.spans(ctx)
    if got is None:
        return None
    h2d = sum(s["counts"].get("h2d_bytes", 0) for s in got) * 1e-6
    d2h = sum(s["counts"].get("d2h_bytes", 0) for s in got) * 1e-6
    if not h2d + d2h:
        return None
    return prog.per_request(ctx, h2d + d2h), {
        "h2d_mb": prog.per_request(ctx, h2d),
        "d2h_mb": prog.per_request(ctx, d2h)}
