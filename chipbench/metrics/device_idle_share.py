"""Device: the share of the traced window in which no operation ran on
the chip, 1 - (union of the operations' intervals) / window."""


def read(ctx):
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
