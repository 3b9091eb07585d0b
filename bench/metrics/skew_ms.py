"""Device time per step under the program's named scope ``skew`` (Cannon's
initial skew of A and B: its shifts and the selects that pick each block),
leaf ops only, averaged over the cell's chips. Nothing to read where no op
in the window carries the scope."""


def read(ctx):
    t_ns = ctx.scoped_op_ns(lambda scopes: "skew" in scopes)
    if t_ns == 0:
        return None
    return t_ns / ctx.steps * 1e-6
