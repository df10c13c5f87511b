"""moe_lower_s: host seconds per answer inside the program's
``opstream.moe`` spans: lowering the sparse-expert half of every MoE
layer (routing, router, shared experts, each active expert's gather,
gated MLP and scatter-add) to the op stream.  None on a program without
those spans."""

from chipbench.manifest import load_module

SPAN = "opstream.moe"


def read(ctx):
    prog = load_module("metrics", "_program")
    got = prog.spans(ctx)
    if got is None:
        return None
    t = [(s["end_ns"] - s["start_ns"]) * 1e-9 for s in got
         if s["name"] == SPAN]
    if not t:
        return None
    return prog.per_request(ctx, sum(t)), {"spans": len(t)}
