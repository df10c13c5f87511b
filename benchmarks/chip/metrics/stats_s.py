"""stats_s: seconds per answer in the program's ``frontend.stats`` spans
(subpartition statistics from the extracted lifetimes, their pull to
the host included).  In a traced run ``device_s`` gives the device busy
time inside them: the lifetime sort the pull waits for."""

from chipbench.manifest import load_module

SPAN = "frontend.stats"


def read(ctx):
    prog = load_module("metrics", "_program")
    got = prog.spans(ctx)
    if got is None:
        return None
    t = [(s["end_ns"] - s["start_ns"]) * 1e-9 for s in got
         if s["name"] == SPAN]
    if not t:
        return None
    extra = {}
    summary = prog.on_trace(ctx)
    if summary is not None:
        extra["device_s"] = prog.per_request(ctx, summary.busy_in(SPAN))
    return prog.per_request(ctx, sum(t)), extra
