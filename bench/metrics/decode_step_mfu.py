"""The decode step's share of the chip's bf16 peak (%): the model FLOPs
of the rows each step served (weights and attention over their live
context), over the step's device time."""
from bench.layers import program_time
from bench.model import decode_flops


def read(ctx):
    secs, n = program_time(ctx.trace, 0, "decode", ctx.lo, ctx.hi)
    a, b = ctx.host_window
    rows = [r for t, r in ctx.spans.decode_rows if a <= t < b]
    if not n or not rows:
        return None
    flops = sum(decode_flops(ctx.hp, r) for r in rows) / len(rows)
    return flops / (secs / n) / ctx.peaks["bf16_flops"] * 100.0
