"""answer_s: wall seconds of the whole window divided by the answers it
completed (lower, relabel, simulate, analyze, compose under every
policy of the mix)."""


def read(ctx):
    if ctx.cell.traffic["kind"] != "answer" or not ctx.n_done:
        return None
    return ctx.window_s / ctx.n_done
