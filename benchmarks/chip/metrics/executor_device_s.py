"""executor_device_s: device busy seconds per answer inside the
program's ``executor.slab`` spans, one per ``_rf_fused`` batch and per
``_ra_grouped``/``_ra_ungrouped`` slab; each holds the slab's upload,
kernel and pull, so the slab's device work lies inside it."""

from chipbench.manifest import load_module

SPAN = "executor.slab"


def read(ctx):
    prog = load_module("metrics", "_program")
    summary = prog.on_trace(ctx)
    if summary is None:
        return None
    slabs = sum(1 for s in summary.spans if s[0] == SPAN)
    if not slabs:
        return None
    return prog.per_request(ctx, summary.busy_in(SPAN)), {"slabs": slabs}
