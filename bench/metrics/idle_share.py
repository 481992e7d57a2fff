"""Share of the traced window in which no operation ran on the device
(averaged over the chips), in %."""


def read(ctx):
    if ctx.trace.window_s <= 0 or not any(len(c["ops"])
                                          for c in ctx.trace.chips):
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s)
