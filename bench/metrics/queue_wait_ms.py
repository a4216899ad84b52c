"""p90 milliseconds a turn waited in the scheduler's queue, from the
program's request marks: for each turn whose ``arrive`` or ``continue``
mark falls in the window (the turns ``ttft_p90_ms`` counts), from that
mark to the first ``admit``, ``resume`` or ``swap_in`` mark of the same
request after it.  A turn not started by the window's end counts with
its wait so far."""
from bench.stats import percentile

ARRIVE = ("arrive", "continue")
START = ("admit", "resume", "swap_in")


def read(ctx):
    try:
        from repro.core import tracing
    except ImportError:                   # a program without the tracer
        return None
    w0, w1 = ctx.window
    arrived, waits = {}, []
    for m in sorted(tracing.tracer().marks(), key=lambda m: m.t):
        key = (m.engine, m.rid)
        if m.name in ARRIVE and w0 <= m.t < w1:
            arrived[key] = m.t
        elif m.name in START and key in arrived:
            waits.append(min(m.t, w1) - arrived.pop(key))
    waits += [w1 - t for t in arrived.values()]
    p90 = percentile(waits, 90)
    return None if p90 is None else p90 * 1e3
