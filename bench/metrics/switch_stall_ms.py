"""Host milliseconds per context switch spent in the swap plane: the
union of the benchmark's spans around the engine's swap-out, swap-in,
swap dispatch (which also restores a follow-up turn's retained prefix at
admission) and swap synchronisation, over the number of swap dispatches
in the window."""
from bench.stats import union_length

NAMES = ("swap_out", "swap_in", "swap_copy", "swap_sync")


def read(ctx):
    w0, w1 = ctx.window
    recs = [(n, s, e) for n, s, e in ctx.spans.records
            if n in NAMES and w0 <= s < w1]
    switches = sum(1 for n, _, _ in recs if n == "swap_copy")
    if not switches:
        return None
    return union_length((s, e) for _, s, e in recs) / switches * 1e3
