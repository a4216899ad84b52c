"""The paged-attention kernel's share of its roofline (%): the least time
the chip needs for one call (one layer of one decode step), set by the
bytes of the live context's K and V plus q in and out (or its FLOPs,
whichever bound is larger), over the kernel's mean device time per
call.  Live bytes come from the rows' context lengths, not the padded
pages bucket, so the number reads the same work whatever implements the
kernel."""
from bench.layers import kernel_time
from bench.model import paged_attention_bytes, paged_attention_flops


def read(ctx):
    secs, n = kernel_time(ctx.trace, 0, "paged_attention", ctx.lo, ctx.hi)
    a, b = ctx.host_window
    rows = [r for t, r in ctx.spans.decode_rows if a <= t < b]
    if not n or not rows:
        return None
    p = ctx.peaks
    bound = sum(max(paged_attention_bytes(ctx.hp, r) / p["hbm_bytes_per_s"],
                    paged_attention_flops(ctx.hp, r) / p["bf16_flops"])
                for r in rows) / len(rows)
    return bound / (secs / n) * 100.0
