"""Device milliseconds of the prefill programs (chunk forward, insert,
carry seed and growth) per 1,000 prompt tokens computed in the traced
window."""
from bench.layers import program_time


def read(ctx):
    secs, _ = program_time(ctx.trace, 0, "prefill", ctx.lo, ctx.hi)
    a, b = ctx.host_window
    toks = sum(n for t, n in ctx.spans.prefill_tokens if a <= t < b)
    if not toks or not secs:
        return None
    return secs * 1e3 / (toks / 1e3)
