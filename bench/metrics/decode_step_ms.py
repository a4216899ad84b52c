"""Device milliseconds per execution of the decode-step program, from
the trace."""
from bench.layers import program_time


def read(ctx):
    secs, n = program_time(ctx.trace, 0, "decode", ctx.lo, ctx.hi)
    return secs / n * 1e3 if n else None
