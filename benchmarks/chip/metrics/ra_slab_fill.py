"""ra_slab_fill: share of the refresh-aware executor's candidate slab
rows that hold a candidate and not padding: 100 * sum(slab_real_rows) /
sum(slab_rows) over the program's ``executor.slab`` spans of the
``ra_grouped`` and ``ra_ungrouped`` kernels."""

from chipbench.manifest import load_module

SPAN = "executor.slab"


def read(ctx):
    prog = load_module("metrics", "_program")
    got = prog.spans(ctx)
    if got is None:
        return None
    slabs = [s["counts"] for s in got if s["name"] == SPAN
             and s["attrs"].get("kernel", "").startswith("ra_")]
    rows = sum(c.get("slab_rows", 0) for c in slabs)
    if not rows:
        return None
    real = sum(c.get("slab_real_rows", 0) for c in slabs)
    return 100.0 * real / rows, {"slabs": len(slabs), "rows": rows}
