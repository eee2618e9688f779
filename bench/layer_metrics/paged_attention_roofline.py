"""Kernels (kernels/paged_attention.py): the least time the chip needs
for the window's paged-attention work (the larger of its operations over
the bf16 peak and the K/V pages it must read over HBM bandwidth, counted
in ``benchlib.costs`` for each decoded token's kv length) over the
kernel's device time in the traced window, as a share."""

from benchlib import costs, trace as tracemod
from benchlib.layers import decode_tokens

# The Pallas kernel has no name in the trace: it is a tpu_custom_call
# whose first three operands are its scalar-prefetched int32 block table,
# kv lengths and position offsets (the only such kernel on the path).
KERNEL = (r'custom-call\(s32\[\d+\]\{[^}]*\} %\S+, s32\[\d+\]\{[^}]*\} %\S+, '
          r's32\[\d+\]\{[^}]*\} %\S+, .*custom_call_target="tpu_custom_call"')


def read(ctx):
    if ctx.trace is None or not ctx.peaks:
        return None
    t, n = tracemod.totals(ctx.trace, tracemod.OPS_LINE, KERNEL)
    flops = nbytes = 0.0
    for _, kv in decode_tokens(ctx, ctx.trace_t0, ctx.trace_t1):
        f, b = costs.paged_attention_cost(ctx.model, kv, ctx.page)
        flops, nbytes = flops + f, nbytes + b
    if not n or t <= 0 or flops <= 0:
        return None
    return 100.0 * costs.roofline_seconds(flops, nbytes, ctx.peaks) / t
