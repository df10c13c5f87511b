"""Shared reading of the program's own spans and counters
(``repro.runtime.obs``) for the per-layer metrics that read them.

A program span counts when it lies inside the window: from the start of
the first to the end of the last request-tagged span of the
benchmark's own (``ctx.spans``).  Both record ``time.perf_counter``.
Device time needs the program's spans on the trace's clock: each
benchmark span row and the same occurrence of its name among the
trace's host spans give one offset between the two clocks, and their
median moves every program span over.  On a program that has no
``repro.runtime.obs`` every function here returns None.
"""

import statistics

from chipbench import devtrace


def _obs():
    try:
        from repro.runtime import obs
    except ImportError:
        return None
    return obs


def spans(ctx):
    """The program's finished spans inside the window (dicts of
    ``repro.runtime.obs.snapshot``), or None."""
    obs = _obs()
    if obs is None or not ctx.spans:
        return None
    lo = min(r[2] for r in ctx.spans) * 1e9
    hi = max(r[3] for r in ctx.spans) * 1e9
    return [s for s in obs.snapshot()["spans"]
            if lo <= s["start_ns"] and s["end_ns"] <= hi]


def self_s(got):
    """``{id: seconds}``: each span's time outside its child spans."""
    return {k: v * 1e-9 for k, v in _obs().self_ns(got).items()}


def per_request(ctx, total):
    """``total`` per completed request, or None with none completed."""
    return total / ctx.n_done if ctx.n_done else None


def offset_ns(ctx):
    """Trace clock minus host clock in ns: the median over the
    benchmark's span rows, each matched with the same occurrence of its
    name in the trace; None without a match."""
    host, trace = {}, {}
    for name, _, t0, _ in ctx.spans:
        host.setdefault(name, []).append(t0 * 1e9)
    for name, start, _ in ctx.summary.spans:
        trace.setdefault(name, []).append(start)
    diffs = [b - a for name, starts in host.items()
             for a, b in zip(sorted(starts), sorted(trace.get(name, ())))]
    return statistics.median(diffs) if diffs else None


def on_trace(ctx):
    """A :class:`devtrace.Summary` of the window's device operations
    with the program's spans, moved onto the trace's clock, as its host
    spans; None in an untraced run or without program spans."""
    if ctx.summary is None:
        return None
    got = spans(ctx)
    off = offset_ns(ctx)
    if got is None or off is None:
        return None
    window = next(s for s in ctx.summary.spans if s[0] == devtrace.WINDOW)
    return devtrace.Summary(
        ctx.summary.ops, ctx.summary.modules,
        [window] + [[s["name"], s["start_ns"] + off,
                     s["end_ns"] - s["start_ns"]] for s in got])
