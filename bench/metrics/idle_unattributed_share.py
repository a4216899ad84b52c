"""Share (%) of the device's idle time in the traced window that no
program span below ``step`` covers, on any thread: the program's spans
(every one but ``step`` itself) are put on the trace's clock through the
window anchor (``host_window`` on ``perf_counter`` starts at the trace's
``lo``), and what of the idle gaps they leave uncovered is summed."""
from bench.stats import merged


def read(ctx):
    try:
        from repro.core import tracing
    except ImportError:                   # a program without the tracer
        return None
    if 0 not in ctx.trace.ops or ctx.host_window is None:
        return None
    idle = ctx.trace.idle_gaps(0, ctx.lo, ctx.hi)
    total = sum(b - a for a, b in idle)
    if total <= 0:
        return None
    h0 = ctx.host_window[0]
    covered = merged((ctx.lo + (s.t0 - h0) * 1e9, ctx.lo + (s.t1 - h0) * 1e9)
                     for s in tracing.tracer().spans() if s.name != "step")
    hit, j = 0.0, 0
    for a, b in idle:
        while j < len(covered) and covered[j][1] <= a:
            j += 1
        k = j
        while k < len(covered) and covered[k][0] < b:
            hit += min(b, covered[k][1]) - max(a, covered[k][0])
            k += 1
    return (total - hit) / total * 100.0
