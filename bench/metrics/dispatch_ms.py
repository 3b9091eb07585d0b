"""Host time per step spent in the call of the program's entry until it
returns the pending result (the ``dispatch`` harness span), summed over the
traced window. It holds the entry's trace, lowering and executable load,
which the device waits for in a closed loop."""


def read(ctx):
    return ctx.dispatch_s / ctx.steps * 1e3
