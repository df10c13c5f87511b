"""trace_view_s: seconds per answer in the program's
``compose.trace_view`` spans: the host sort and longdouble prefix sums
of ``sorted_trace_view``, once per subpartition and session."""

from chipbench.manifest import load_module

SPAN = "compose.trace_view"


def read(ctx):
    prog = load_module("metrics", "_program")
    got = prog.spans(ctx)
    if got is None:
        return None
    t = [(s["end_ns"] - s["start_ns"]) * 1e-9 for s in got
         if s["name"] == SPAN]
    return prog.per_request(ctx, sum(t)) if t else None
