"""Share of the matmul entry's calls in the window that found their
program already built: 100 (1 - builds / calls), from the program's
counters ``matmul.builds`` and ``matmul.calls``. A call that misses traces,
lowers and loads a program while the device waits. Nothing to read where
the window counted no call."""


def read(ctx):
    calls = ctx.counters["matmul.calls"]
    if not calls:
        return None
    return 100.0 * (1.0 - ctx.counters["matmul.builds"] / calls)
