"""Wall milliseconds the engine thread spent in the swap plane per
context switch, from the program's own spans: the union of ``swap.out``,
``swap.in``, ``swap.dispatch``, ``swap.sync`` and ``swap.conflict`` (and
so of every ``pool_lock.wait`` nested in them) on the threads that run
``step``, over the swap dispatches (``swap.dispatch`` spans), both
starting in the window.  The wall-clock twin of ``switch_stall_ms``."""
from bench.stats import union_length

NAMES = ("swap.out", "swap.in", "swap.dispatch", "swap.sync",
         "swap.conflict")


def read(ctx):
    try:
        from repro.core import tracing
    except ImportError:                   # a program without the tracer
        return None
    w0, w1 = ctx.window
    spans = [s for s in tracing.tracer().spans() if w0 <= s.t0 < w1]
    engine = {s.thread for s in spans if s.name == "step"}
    recs = [s for s in spans if s.name in NAMES and s.thread in engine]
    switches = sum(1 for s in recs if s.name == "swap.dispatch")
    if not switches:
        return None
    return union_length((s.t0, s.t1) for s in recs) / switches * 1e3
