"""Share of the traced window (first to last bench.batch span) in which
no op ran on the chip, in %."""

from bench.trace import idle_share


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    return 100.0 * idle_share(ctx.trace, ctx.t0, ctx.t1)
