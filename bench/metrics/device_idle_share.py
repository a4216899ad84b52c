"""Share (%) of the traced window in which no operation ran on the
device: 1 - the union of the device's op intervals over the window."""


def read(ctx):
    span = ctx.hi - ctx.lo
    if span <= 0 or 0 not in ctx.trace.ops:
        return None
    return (1.0 - ctx.trace.busy_ns(0, ctx.lo, ctx.hi) / span) * 100.0
