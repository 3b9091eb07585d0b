"""Share of the traced window in which no op ran on a chip, averaged over
the cell's chips."""


def read(ctx):
    return 100.0 * (1.0 - ctx.summary.mean("busy_ns") / ctx.summary.window_ns)
