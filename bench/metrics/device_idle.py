"""Device: % of the traced window in which no operation ran on the chip."""


def read(ctx):
    busy, window = ctx.trace["busy_s"], ctx.trace["window_s"]
    return 100.0 * (1.0 - busy / window) if window > 0 else None
