"""candidates_per_s: (candidate, subpartition) compositions completed by
the window's sweeps, divided by the wall seconds of the whole window."""


def read(ctx):
    if ctx.cell.traffic["kind"] != "sweep" or not ctx.n_done:
        return None
    return ctx.units / ctx.window_s
