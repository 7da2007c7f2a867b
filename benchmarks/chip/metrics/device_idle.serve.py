"""Share of the traced window in which no operation ran on the device
(1 - busy / window), in percent."""


def read(ctx):
    r = ctx.reduced
    if r.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
