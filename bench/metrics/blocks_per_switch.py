"""KV blocks the swap plane moved per context switch in the window: the
swap tasks' blocks, both ways (the engine's counters), over the swap
dispatches (the benchmark's spans around them)."""


def read(ctx):
    w0, w1 = ctx.window
    n = sum(1 for name, s, _ in ctx.spans.records
            if name == "swap_copy" and w0 <= s < w1)
    if not n:
        return None
    return (ctx.counters["blocks_out"] + ctx.counters["blocks_in"]) / n
