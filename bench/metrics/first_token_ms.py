"""Mean host milliseconds of drawing a prefill's first token: the
program's ``prefill.first_token`` spans that start in the window (the
sample from the last position's logits and its pull to the host)."""


def read(ctx):
    try:
        from repro.core import tracing
    except ImportError:                   # a program without the tracer
        return None
    w0, w1 = ctx.window
    d = [s.t1 - s.t0 for s in tracing.tracer().spans()
         if s.name == "prefill.first_token" and w0 <= s.t0 < w1]
    return sum(d) / len(d) * 1e3 if d else None
