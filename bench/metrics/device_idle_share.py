"""Share of the traced window in which no operation ran on the device
(1 - busy union over the window, averaged over the chips), in %."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return (1 - ctx.trace.busy_s / ctx.trace.window_s) * 100
