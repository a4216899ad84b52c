"""Device milliseconds per execution of the decode-step program spent in
its LM head: the union of the intervals of the decode program's ops
whose name-scope path holds ``unembed`` (the head's cast, its matmul
and the logits).  A program without that scope reads nothing."""
from bench import scopes
from bench.layers import in_window, op_programs, program_layer, program_time
from bench.stats import union_length

SCOPE = "unembed"


def unembed_ms(red, paths, lo: float, hi: float, dev: int = 0):
    """``red``: the reduced trace; ``paths``: the scope path of each op
    name on ``dev``'s plane (``bench/scopes.py``)."""
    _, n = program_time(red, dev, "decode", lo, hi)
    ops = in_window(red.ops.get(dev, []), lo, hi)
    progs = op_programs(ops, red.modules.get(dev, []))
    head = [(s, e) for (name, s, e), prog in zip(ops, progs)
            if program_layer(prog) == "decode"
            and scopes.in_scope(paths.get(name, ""), SCOPE)]
    if not n or not head:
        return None
    return union_length(head) / n / 1e6


def read(ctx):
    from bench.run import TRACE_DIR
    return unembed_ms(ctx.trace,
                      scopes.paths(TRACE_DIR).get("/device:TPU:0", {}),
                      ctx.lo, ctx.hi)
