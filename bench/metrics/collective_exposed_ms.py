"""Device time per step during which a collective op runs on a chip and no
other op does, averaged over the cell's chips. Nothing to read where the
trace holds no collective op."""


def read(ctx):
    if ctx.summary.mean("collective_ns") == 0:
        return None
    return ctx.summary.mean("exposed_collective_ns") / ctx.steps * 1e-6
