"""Share of the traced window in which no operation ran on the chip:
100 * (1 - busy / window), busy being the union of the device
operations' intervals."""


def read(ctx):
    s = ctx.summary
    if s is None or not s.chips() or s.window_s <= 0:
        return None
    return 100.0 * (1.0 - s.busy_s() / s.window_s)
