"""Host milliseconds per engine ``step`` spent in the engine and its
scheduler themselves, from the program's own spans: each ``step`` span
that starts in the window, less the union of its descendant spans of
other layers (swap, prefill, decode, pool-lock waits, program builds),
averaged over the steps.  The step's phase spans (``step.*``) are the
engine's own work and stay in.  The in-program twin of
``engine_host_ms``."""
from collections import defaultdict

from bench.stats import union_length


def read(ctx):
    try:
        from repro.core import tracing
    except ImportError:                   # a program without the tracer
        return None
    w0, w1 = ctx.window
    spans = tracing.tracer().spans()
    children = defaultdict(list)
    for s in spans:
        children[s.parent].append(s)
    steps = [s for s in spans if s.name == "step" and w0 <= s.t0 < w1]
    if not steps:
        return None
    total = 0.0
    for step in steps:
        other, todo = [], list(children[step.id])
        while todo:
            s = todo.pop()
            todo += children[s.id]
            if not s.name.startswith("step"):
                other.append((max(s.t0, step.t0), min(s.t1, step.t1)))
        total += (step.t1 - step.t0) - union_length(
            (a, b) for a, b in other if b > a)
    return total / len(steps) * 1e3
