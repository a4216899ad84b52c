"""Host milliseconds per engine ``step`` spent in the engine and its
scheduler itself: the step's span less the time inside its calls into
other layers (the deferred token flush, swap-out, swap-in, swap
synchronisation, prefill), over the whole window."""
import bisect

from bench.stats import merged

CHILDREN = ("flush_wait", "swap_out", "swap_in", "swap_copy", "swap_sync",
            "prefill")


def read(ctx):
    w0, w1 = ctx.window
    recs = [r for r in ctx.spans.records if w0 <= r[1] < w1]
    steps = [(s, e) for n, s, e in recs if n == "step"]
    if not steps:
        return None
    inner = merged((s, e) for n, s, e in recs if n in CHILDREN)
    starts = [s for s, _ in inner]
    total = 0.0
    for s, e in steps:
        busy = 0.0
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(inner) and inner[i][0] < e:
            a, b = inner[i]
            busy += max(0.0, min(b, e) - max(a, s))
            i += 1
        total += (e - s) - busy
    return total / len(steps) * 1e3
